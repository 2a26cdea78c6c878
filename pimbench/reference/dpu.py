"""Plain model of one UPMEM DPU's timing, for the check of the simulator.

It runs a kernel, given as text (``kernels/<workload>.t<tasklets>.txt``,
one instruction per line: name, rd, ra, rb, imm, use_imm), on one DPU
and returns the cycles the kernel takes and the instructions it issues.
It is written from the DPU's description (arXiv:2308.00846 §II-A and
Table I) and shares no code with the simulator:

* one issue port: at most one instruction per cycle, from the first
  ready tasklet at or after the one after the last to issue;
* revolver scheduling: a tasklet issues again ``revolver_cycles`` after
  its last issue, ``mul_extra`` or ``div_extra`` later after a MUL or
  DIV;
* odd/even register file: an instruction that reads two registers of
  the same parity holds the issue port for the next cycle too;
* WRAM loads and stores take effect at issue; MRAM is reached only by
  DMA, which blocks the tasklet until the DMA engine has served it;
* the DMA engine serves one request at a time, first those that hit the
  open DRAM row, then the oldest: a row hit costs tCL, a miss
  tRP + tRCD + tCL, each further row crossed a miss, all in DPU cycles,
  plus the bytes at ``mram_bw_bytes_per_cycle``; the tasklet may issue
  from the cycle after the request is served;
* ACQUIRE retries until its atomic bit is free; BARRIER holds every
  tasklet until all live tasklets have reached it, releasing them for
  the next cycle;
* the kernel's cycles end one cycle after its last STOP issues.

The check calls :func:`time_launch`, the entry point that every timing
model under ``reference/`` has.
"""
from __future__ import annotations

import math
from pathlib import Path
from typing import List, Sequence, Tuple

HERE = Path(__file__).resolve().parent

RUN, DMA, BARRIER, DONE = range(4)
R_DPU, R_NDPU, R_TID, R_NT = 20, 21, 22, 23
N_REGS = 24

ALU = {"ADD", "SUB", "AND", "OR", "XOR", "SLL", "SRL", "SRA", "MUL", "DIV",
       "SLT", "SLTU"}
BRANCH = {"BEQ", "BNE", "BLT", "BGE", "BLTU", "BGEU"}
# the instructions that read ra, and those that read rb (when no imm)
READS_A = ALU | BRANCH | {"LW", "SW", "LDMA", "SDMA", "JR"}
READS_B = ALU | BRANCH | {"SW", "LDMA", "SDMA"}

Kernel = List[Tuple[str, int, int, int, int, bool]]
STOP = ("STOP", 0, 0, 0, 0, False)      # what lies past a kernel's end


def kernel(name: str) -> Kernel:
    """The kernel ``kernels/<name>.txt`` as a list of instructions."""
    out = []
    for line in (HERE / "kernels" / f"{name}.txt").read_text().splitlines():
        op, rd, ra, rb, imm, ui = line.split()
        out.append((op, int(rd), int(ra), int(rb), int(imm), ui == "1"))
    return out


def i32(x: int) -> int:
    return (x + (1 << 31)) % (1 << 32) - (1 << 31)


def u32(x: int) -> int:
    return x & 0xFFFFFFFF


def alu(op: str, a: int, b: int) -> int:
    sh = b & 31
    if op == "ADD":
        return i32(a + b)
    if op == "SUB":
        return i32(a - b)
    if op == "AND":
        return a & b
    if op == "OR":
        return a | b
    if op == "XOR":
        return a ^ b
    if op == "SLL":
        return i32(u32(a) << sh)
    if op == "SRL":
        return i32(u32(a) >> sh)
    if op == "SRA":
        return a >> sh
    if op == "MUL":
        return i32(a * b)
    if op == "DIV":
        if b == 0:
            return -1
        q = abs(a) // abs(b)
        return i32(q if (a < 0) == (b < 0) else -q)
    if op == "SLT":
        return int(a < b)
    return int(u32(a) < u32(b))                                   # SLTU


def taken(op: str, a: int, b: int) -> bool:
    return {"BEQ": a == b, "BNE": a != b, "BLT": a < b, "BGE": a >= b,
            "BLTU": u32(a) < u32(b), "BGEU": u32(a) >= u32(b)}[op]


def dram_cycles(dpu: dict, n: int) -> int:
    """``n`` DRAM cycles in DPU cycles, at least one."""
    return max(1, int(round(n * dpu["freq_mhz"] / dpu["dram_freq_mhz"])))


def run(prog: Kernel, args: Sequence[int], mram: list, *, dpu: dict,
        tasklets: int, dpu_id: int, n_dpus: int,
        max_cycles: int = 10 ** 8) -> Tuple[int, int]:
    """Run ``prog`` on one DPU whose WRAM starts with ``args`` and whose
    MRAM words are ``mram`` (changed in place).  Returns ``(cycles,
    issued)``."""
    t = tasklets
    n_wram, n_mram = dpu["wram_bytes"] // 4, len(mram)
    wram = [0] * n_wram
    wram[:len(args)] = [int(x) for x in args]
    regs = [[0] * N_REGS for _ in range(t)]
    for k, r in enumerate(regs):
        r[R_DPU], r[R_NDPU], r[R_TID], r[R_NT] = dpu_id, n_dpus, k, t
    pc, status, ready_at = [0] * t, [RUN] * t, [0] * t
    request = [None] * t                   # (mram byte address, bytes, cycle)
    atomic = [0] * dpu["atomic_bits"]
    hit_cost = dram_cycles(dpu, dpu["t_cl"])
    miss_cost = dram_cycles(dpu, dpu["t_rp"] + dpu["t_rcd"] + dpu["t_cl"])
    row_bytes, bw = dpu["row_bytes"], dpu["mram_bw_bytes_per_cycle"]
    served, served_until, open_row = -1, 0, -1
    port, nxt_rr, cycle, issued, live = 0, 0, 0, 0, t

    while live:
        if cycle > max_cycles:
            raise RuntimeError("the kernel did not stop")
        c = cycle
        # -- the DMA engine: finish the request in service, take the next
        if served >= 0 and served_until <= c:
            status[served], ready_at[served] = RUN, c + 1
            request[served], served = None, -1
        if served < 0:
            best = None
            for k in range(t):
                if request[k] is not None:
                    m, _, enq = request[k]
                    key = (m // row_bytes != open_row, enq)
                    if best is None or key < best[0]:
                        best = (key, k)
            if best is not None:
                served = best[1]
                m, nbytes, _ = request[served]
                first, last = m // row_bytes, (m + max(nbytes, 1) - 1) // row_bytes
                cost = hit_cost if first == open_row else miss_cost
                cost += (last - first) * miss_cost + math.ceil(nbytes / bw)
                served_until, open_row = c + cost, last
        # -- barrier: every live tasklet waiting releases them all
        waiting = status.count(BARRIER)
        if waiting and waiting == live:
            for k in range(t):
                if status[k] == BARRIER:
                    status[k], ready_at[k] = RUN, c + 1
        # -- issue
        sel = -1
        if port == 0:
            for i in range(t):
                k = (nxt_rr + i) % t
                if status[k] == RUN and ready_at[k] <= c:
                    sel = k
                    break
        if sel >= 0:
            issued += 1
            r = regs[sel]
            op, rd, ra, rb, imm, ui = (prog[pc[sel]] if pc[sel] < len(prog)
                                       else STOP)
            a, rb_val = r[ra], r[rb]
            b = imm if ui else rb_val
            new_pc = pc[sel] + 1
            if op in ALU:
                r[rd] = alu(op, a, b)
            elif op == "LW":
                r[rd] = wram[min(max(i32(a + imm) >> 2, 0), n_wram - 1)]
            elif op == "SW":
                wram[min(max(i32(a + imm) >> 2, 0), n_wram - 1)] = rb_val
            elif op in ("LDMA", "SDMA"):
                size = min(max(imm if ui else r[rd], 0), 2048)
                words = (size + 3) >> 2
                wi = [min(max((a >> 2) + k, 0), n_wram - 1) for k in range(words)]
                mi = [min(max((rb_val >> 2) + k, 0), n_mram - 1)
                      for k in range(words)]
                if op == "LDMA":
                    vals = [mram[j] for j in mi]
                    for j, v in zip(wi, vals):
                        wram[j] = v
                else:
                    vals = [wram[j] for j in wi]
                    for j, v in zip(mi, vals):
                        mram[j] = v
                status[sel] = DMA
                request[sel] = (rb_val, size, c)
            elif op in BRANCH:
                if taken(op, a, b):
                    new_pc = imm
            elif op == "JUMP":
                new_pc = imm
            elif op == "JAL":
                r[rd], new_pc = pc[sel] + 1, imm
            elif op == "JR":
                new_pc = a
            elif op == "ACQUIRE":
                bit = min(max(imm, 0), len(atomic) - 1)
                if atomic[bit]:
                    new_pc = pc[sel]
                else:
                    atomic[bit] = 1
            elif op == "RELEASE":
                atomic[min(max(imm, 0), len(atomic) - 1)] = 0
            elif op == "BARRIER":
                status[sel] = BARRIER
            elif op == "STOP":
                status[sel], new_pc = DONE, pc[sel]
                live -= 1
            elif op == "SPC":
                r[rd] = (r[R_TID], r[R_NT], r[R_DPU], r[R_NDPU])[
                    min(max(imm, 0), 3)]
            pc[sel] = new_pc
            ready_at[sel] = c + dpu["revolver_cycles"] + (
                dpu["mul_extra"] if op == "MUL"
                else dpu["div_extra"] if op == "DIV" else 0)
            if (op in READS_A and op in READS_B and not ui
                    and ra % 2 == rb % 2):
                port += 2
            nxt_rr = (sel + 1) % t
        # -- the next cycle at which anything can happen
        if sel < 0 and port == 0:
            soon = [ready_at[k] for k in range(t) if status[k] == RUN]
            if served >= 0:
                soon.append(served_until)
            cycle = max(c + 1, min(soon)) if soon else c + 1
        else:
            cycle = c + 1
        port = max(port - 1, 0)
    return cycle, issued


def time_launch(dpu: dict, workload: str, launch, k: int) -> Tuple[int, int]:
    """``(cycles, issued)`` of DPU ``k`` in ``launch``, an element of the
    workload's reference ``launches()``: its arguments and MRAM words,
    each ``(n_dpus, ...)``, run through the workload's kernel at the
    configuration's tasklets."""
    args, mram = launch
    t = int(dpu["n_tasklets"])
    return run(kernel(f"{workload}.t{t}"), args[k], mram[k].tolist(),
               dpu=dpu, tasklets=t, dpu_id=k, n_dpus=int(dpu["n_dpus"]))
