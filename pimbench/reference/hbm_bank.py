"""Plain model of one HBM-PIM bank in all-bank mode, for the check of the
simulator.

In all-bank mode (Samsung HBM-PIM, Aquabolt-XL) the PIM unit of every
bank runs the same command stream from the Command Register File (CRF)
in lockstep.  This model runs one bank's stream and returns the cycles
it takes and the instructions it issues.  It is written from the
command set and timing rules that the simulator states (its ``hbmpim``
module and ``DPUConfig``) and shares no code with it:

* a command is a tuple: ``("NOP",)``, ``("EXIT",)``, ``("JUMP", target,
  trips)``, ``(op, dst, a)`` for MOV and FILL, ``(op, dst, a, b)`` for
  ADD, MUL and MAC.  An operand is ``(kind, index)``: ``("bank", row)``
  is one burst of ``hbm_lanes`` words at that bank row, ``("grf_a", i)``
  and ``("grf_b", i)`` vector registers 0-7, ``("srf", i)`` scalar
  register i broadcast over the lanes.  Past the end of the stream lies
  EXIT;
* each command takes one issue cycle;
* then each bank operand, in the order a, b (ADD, MUL and MAC only),
  then the destination, takes ``t_cl`` if its row is the open row, else
  ``t_rp + t_rcd + t_cl``, DRAM cycles in DPU cycles (rounded, at least
  one), plus the burst, ``ceil(hbm_lanes * 4 / (mram_bw_bytes_per_cycle
  * mram_bw_scale * coalesced_bw_mult))`` cycles; its row is then the
  open row.  No row is open when a launch starts;
* JUMP has a single trip counter.  Empty, it loads ``trips``; while the
  counter is above 0 the jump to slot ``target`` is taken and the
  counter falls by one; at 0 the stream goes on and the counter empties;
* EXIT ends the stream;
* ``issued`` counts ``hbm_lanes`` for each compute command (MOV, FILL,
  ADD, MUL, MAC) and 1 for any other;
* values are int32 and wrap: MOV and FILL copy a, ADD gives a + b, MUL
  a * b, MAC dst + a * b; an SRF destination takes lane 0.  A burst past
  the bank's end reads its last word and writes nothing there.
"""
from __future__ import annotations

import math
from typing import List, MutableSequence, Sequence, Tuple

import numpy as np

COMPUTE = {"MOV", "FILL", "ADD", "MUL", "MAC"}
TWO_SOURCES = {"ADD", "MUL", "MAC"}
EXIT = ("EXIT",)


def i32(x: int) -> int:
    return (x + (1 << 31)) % (1 << 32) - (1 << 31)


def dram_cycles(dpu: dict, n: int) -> int:
    """``n`` DRAM cycles in DPU cycles, at least one."""
    return max(1, int(round(n * dpu["freq_mhz"] / dpu["dram_freq_mhz"])))


def run(commands: Sequence[tuple], srf: List[int],
        bank: MutableSequence[int], *,
        dpu: dict, max_commands: int = 10 ** 8) -> Tuple[int, int]:
    """Run ``commands`` on one bank whose eight SRF registers hold
    ``srf`` and whose words are ``bank`` (both changed in place).
    Returns ``(cycles, issued)``."""
    w = int(dpu["hbm_lanes"])
    hit = dram_cycles(dpu, dpu["t_cl"])
    miss = dram_cycles(dpu, dpu["t_rp"] + dpu["t_rcd"] + dpu["t_cl"])
    burst = math.ceil(w * 4 / (dpu["mram_bw_bytes_per_cycle"]
                               * dpu["mram_bw_scale"]
                               * dpu["coalesced_bw_mult"]))
    grf = {"grf_a": [[0] * w for _ in range(8)],
           "grf_b": [[0] * w for _ in range(8)]}

    def read(operand) -> List[int]:
        kind, i = operand
        if kind == "bank":
            last = len(bank) - 1
            return [int(bank[min(i * w + l, last)]) for l in range(w)]
        if kind == "srf":
            return [srf[i]] * w
        return list(grf[kind][i])

    def write(operand, values: List[int]):
        kind, i = operand
        if kind == "bank":
            for l, v in enumerate(values):
                if i * w + l < len(bank):
                    bank[i * w + l] = v
        elif kind == "srf":
            srf[i] = values[0]
        else:
            grf[kind][i] = values

    pc, counter, open_row, cycles, issued = 0, None, None, 0, 0
    for _ in range(max_commands):
        cmd = commands[pc] if pc < len(commands) else EXIT
        op = cmd[0]
        cycles += 1
        issued += w if op in COMPUTE else 1
        if op == "EXIT":
            return cycles, issued
        pc += 1
        if op == "JUMP":
            left = cmd[2] if counter is None else counter
            counter = left - 1 if left > 0 else None
            if left > 0:
                pc = cmd[1]
        elif op in COMPUTE:
            dst, a = cmd[1], cmd[2]
            sources = [a, cmd[3]] if op in TWO_SOURCES else [a]
            for kind, row in sources + [dst]:
                if kind == "bank":
                    cycles += (hit if row == open_row else miss) + burst
                    open_row = row
            va = read(a)
            if op in ("MOV", "FILL"):
                out = va
            else:
                vb = read(cmd[3])
                if op == "ADD":
                    out = [x + y for x, y in zip(va, vb)]
                elif op == "MUL":
                    out = [x * y for x, y in zip(va, vb)]
                else:
                    out = [z + x * y for z, x, y in zip(read(dst), va, vb)]
            write(dst, [i32(v) for v in out])
    raise RuntimeError("the command stream did not exit")


def bank_words(dpu: dict, launch, k: int) -> np.ndarray:
    """Bank ``k``'s words at the start of ``launch``: the launch's
    segments ``[(first word, words (n_banks, n))]`` on a bank of
    ``mram_bytes`` that is zero elsewhere."""
    bank = np.zeros(int(dpu["mram_bytes"]) // 4, np.int32)
    for first, words in launch[2]:
        bank[first:first + words.shape[1]] = words[k]
    return bank


def time_launch(dpu: dict, workload: str, launch, k: int) -> Tuple[int, int]:
    """``(cycles, issued)`` of bank ``k`` in ``launch``, an element of the
    workload's reference ``launches()``: its commands, SRF values
    ``(n_banks, 8)`` and bank words as segments (:func:`bank_words`)."""
    commands, srf = launch[0], launch[1]
    return run(commands, [int(v) for v in srf[k]],
               bank_words(dpu, launch, k), dpu=dpu)
