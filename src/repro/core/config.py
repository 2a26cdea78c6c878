"""DPU / system configuration (paper Table I defaults + case-study knobs)."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass


# host/comm-layer knobs plus the axes the compile cache buckets or keys
# separately — everything a traced engine step never reads (see
# DPUConfig.static_key)
_NON_ENGINE_FIELDS = frozenset({
    "n_dpus", "n_tasklets", "mram_bytes", "iram_instrs",
    "h2d_gbps_per_dpu", "d2h_gbps_per_dpu",
    "n_ranks", "n_channels", "channel_contention",
    "fabric", "pim_link_gbps", "pim_link_latency_us",
    "intra_rank_gbps", "intra_rank_latency_us",
    # the execution backend name is keyed explicitly by the compile cache
    # (repro.core.backend resolves it), never read by a traced step
    "backend",
})


@dataclass(frozen=True)
class DPUConfig:
    # ----- system size ------------------------------------------------------
    n_dpus: int = 1
    n_tasklets: int = 16

    # ----- DPU processor (Table I) -----------------------------------------
    freq_mhz: int = 350
    pipeline_stages: int = 14
    revolver_cycles: int = 11           # min same-thread issue distance
    wram_bytes: int = 64 * 1024
    iram_instrs: int = 4096             # 24 KB / 6 B per instruction
    atomic_bits: int = 256
    mram_bytes: int = 4 * 1024 * 1024   # per-DPU bank (64 MB on real HW;
                                        # sized to the workload here)

    # ----- DRAM system (DDR4-2400, Table I) ---------------------------------
    dram_freq_mhz: int = 1200
    t_rcd: int = 16
    t_ras: int = 39
    t_rp: int = 16
    t_cl: int = 16
    t_bl: int = 4
    row_bytes: int = 1024
    # per-DPU MRAM->WRAM streaming bandwidth.  2 B / DPU-cycle @350 MHz
    # = 700 MB/s (theoretical max; Fig. 5 notes ~600 MB/s observed).
    mram_bw_bytes_per_cycle: float = 2.0
    mram_bw_scale: float = 1.0          # Fig. 13 sweep knob

    # ----- CPU <-> DPU communication (asymmetric AVX path, Table I) ----------
    h2d_gbps_per_dpu: float = 0.296
    d2h_gbps_per_dpu: float = 0.063

    # ----- host interconnect topology (repro.comm, §II-B / Fig. 10) ----------
    # DPUs split contiguously across ranks; ranks round-robin over memory
    # channels. Transfers serialize between ranks sharing a channel and
    # overlap across channels.
    n_ranks: int = 1
    n_channels: int = 1
    # async-schedule contention: operations on *disjoint* rank sets of one
    # physical channel (or fabric) overlap; a factor > 1 stretches the
    # later arrival while they share the link.  1.0 = independent
    # per-rank shares (and reproduces the PR 3 whole-system timelines).
    # Default calibrated against the measured multi-rank weak scaling of
    # Gomez-Luna et al. (arXiv:2110.01709): two ranks driving one memory
    # channel concurrently sustain ~1.2x the single-rank aggregate
    # bandwidth, not 2x — the host AVX copy threads contend on the
    # channel bus.  The model's aggregate speedup for R concurrent
    # same-channel ranks is R/factor, so factor = 2/1.2 ~= 1.67 hits the
    # measured point (benchmarks/rank_overlap.py contention_calibration()
    # re-derives it; tests pin the value).
    channel_contention: float = 1.67

    # ----- inter-DPU fabric (pathfinding case study) --------------------------
    # "host": DPU->CPU->DPU bounce (today's hardware, §II-B)
    # "direct": hypothetical PIM-PIM interconnect (the paper's pathfinding
    #           hypothesis) with per-DPU link bandwidth + per-hop latency
    # "hier": hierarchical rank-locality fabric — fast intra-rank stage
    #         (intra_rank_* links) + cross-rank stage among rank leaders
    #         (pim_link_* links)
    fabric: str = "host"
    pim_link_gbps: float = 1.0
    pim_link_latency_us: float = 0.1
    intra_rank_gbps: float = 8.0
    intra_rank_latency_us: float = 0.05

    # ----- case study #2: ILP features (additive D/R/S/F) --------------------
    forwarding: bool = False            # (D) data forwarding
    unified_rf: bool = False            # (R) merged odd/even RF, 2x read bw
    superscalar: int = 1                # (S) issue width (2 = 2-way)
    # (F) is expressed through freq_mhz (700 doubles the clock)

    # ----- case study #1: SIMT ----------------------------------------------
    simt_width: int = 0                 # 0 = scalar baseline DPU
    coalescing: bool = False            # memory address coalescing
    # coalesced row-bursts stream at the bank's native burst bandwidth
    # (~2.4 GB/s for a DDR4-2400 x8 device) instead of the DMA engine's
    # 700 MB/s design point — the paper's "not a fundamental constraint"
    # observation (§V-B).  2.4 / 0.7 = 3.4x.
    coalesced_bw_mult: float = 3.4

    # ----- execution backend (repro.core.backend registry) -------------------
    # "" = auto: "simt" when simt_width > 0, else "scalar".  Any other
    # value names a registered ExecBackend ("scalar", "simt", "hbmpim",
    # "hbmpim_cmd", ...) — the pathfinding axis that swaps the UPMEM-style
    # MIMD DPU for the HBM-PIM all-bank SIMD model on the same workloads.
    backend: str = ""

    # ----- HBM-PIM all-bank target (repro.core.hbmpim) -----------------------
    # SIMD lanes per bank command (one GRF register = hbm_lanes words;
    # HBM-PIM's PCU operates on 256-bit vectors = 16 lanes)
    hbm_lanes: int = 16
    # CRF command slots a native command program may occupy.  The real
    # hardware holds 32 μcode slots; the default is a deliberately
    # generous pathfinding enlargement so unrolled command streams fit
    # without a host-side loop around every 32 commands.
    hbm_crf_slots: int = 2048

    # ----- case study #3: MMU -----------------------------------------------
    mmu: bool = False
    tlb_entries: int = 16
    page_bytes: int = 4096

    # ----- case study #4: on-demand cache vs scratchpad ----------------------
    cache_mode: bool = False            # LW/SW hit a DRAM-backed space via D$
    dcache_bytes: int = 64 * 1024
    dcache_ways: int = 8
    line_bytes: int = 64

    # ----- engine ------------------------------------------------------------
    max_cycles: int = 200_000_000
    event_skip: bool = True             # fast-forward to the next event
    collect_detail: bool = True         # TLP histogram + time series
    small_dma_words: int = 64           # fast-path copy width (256 B)
    mul_extra: int = 4                  # extra occupancy cycles for MUL
    div_extra: int = 16                 # ... and DIV
    wram_load_latency: int = 3          # load-to-use latency w/ forwarding
    timeseries_window: int = 2_048      # TLP time-series sampling window
    timeseries_len: int = 512

    def replace(self, **kw) -> "DPUConfig":
        return dataclasses.replace(self, **kw)

    def static_key(self) -> tuple:
        """Hashable identity of every field that shapes the *traced* engine.

        This is the config part of the compiled-engine cache key
        (``repro.core.compile_cache``): two configs with equal
        ``static_key()`` lower to the same XLA program and may share one
        executable.  Host/interconnect knobs (transfer rates, rank
        topology, fabric pricing) never enter the traced step, and the
        axes the cache buckets or keys separately are excluded here:
        ``n_dpus`` (padded to a DPU bucket), ``n_tasklets``
        (the effective thread count is keyed explicitly), ``mram_bytes``
        (the actual MRAM image width is keyed) and ``iram_instrs``
        (the program length is bucketed).  New fields are conservatively
        included by default."""
        return tuple(
            (f.name, getattr(self, f.name))
            for f in dataclasses.fields(self)
            if f.name not in _NON_ENGINE_FIELDS)

    # ----- derived -----------------------------------------------------------
    @property
    def dram_cycle_ratio(self) -> float:
        """DPU cycles per DRAM cycle."""
        return self.freq_mhz / self.dram_freq_mhz

    def dram_cycles_to_dpu(self, n: float) -> int:
        return max(1, int(round(n * self.dram_cycle_ratio)))

    @property
    def row_miss_overhead(self) -> int:
        """Precharge + activate + CAS, in DPU cycles."""
        return self.dram_cycles_to_dpu(self.t_rp + self.t_rcd + self.t_cl)

    @property
    def row_hit_overhead(self) -> int:
        return self.dram_cycles_to_dpu(self.t_cl)

    @property
    def effective_mram_bw(self) -> float:
        return self.mram_bw_bytes_per_cycle * self.mram_bw_scale

    @property
    def wram_words(self) -> int:
        return self.wram_bytes // 4

    @property
    def mram_words(self) -> int:
        return self.mram_bytes // 4

    def with_ilp(self, features: str) -> "DPUConfig":
        """'D','DR','DRS','DRSF' additive ablation (Fig. 12)."""
        kw = {}
        if "D" in features:
            kw["forwarding"] = True
        if "R" in features:
            kw["unified_rf"] = True
        if "S" in features:
            kw["superscalar"] = 2
        if "F" in features:
            kw["freq_mhz"] = 700
        return self.replace(**kw)


# paper Table I baseline
BASELINE = DPUConfig()
