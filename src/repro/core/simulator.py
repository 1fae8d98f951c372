"""Device-level simulator for the paper's evaluation (Fig. 5 / Fig. 6).

The paper's own numbers come from SCALE-Sim-style analytical models (refs
[29], [31]) plus RTL synthesis — not silicon measurements of NSFlow — so the
honest reproduction is the same methodology:

- **NSFlow (AdArray)**: DSE-chosen (H, W, N) + folding; NN/VSA streams
  overlap (dataflow pipelining); cycles from Eqs. (1)-(5) at 272 MHz.
- **TPU-like 128×128 systolic array**: NN via Eq. (1) with H=W=128, N=1;
  circular convolution has no streaming path on a weight-stationary matmul
  array, so it must materialize the circulant matrix (d× traffic
  amplification) and run memory-bound; strictly sequential NN→VSA.
- **GPU / CPU / edge SoCs / DPU**: per-node roofline max(flops/peak,
  bytes/bw) + per-kernel launch overhead; symbolic nodes are memory-bound
  exactly as the paper's Fig. 1c roofline shows.

Device constants are public datasheet numbers (annotated); ratios — not the
absolute seconds — are the reproduced claim.
"""

from __future__ import annotations

import dataclasses

from repro.core import analytical as ana
from repro.core import dataflow as dfl
from repro.core import dse as dse_mod
from repro.core.opgraph import OpGraph


@dataclasses.dataclass(frozen=True)
class Device:
    name: str
    peak_flops: float          # effective FLOP/s for NN kernels
    dram_bw: float             # bytes/s
    launch_overhead: float     # s per op node (kernel launch / dispatch)
    symbolic_native: bool      # has a circular-conv streaming path
    freq: float = 272e6        # array clock (systolic models)

    def nn_time(self, flops: int, bytes_: int) -> float:
        return max(flops / self.peak_flops, bytes_ / self.dram_bw) + self.launch_overhead

    def vsa_time(self, nvec: int, d: int, dtype_bytes: int = 4) -> float:
        if self.symbolic_native:
            raise RuntimeError("use array model for native devices")
        # circulant materialization: d× traffic amplification, memory bound
        traffic = nvec * d * d * dtype_bytes + nvec * 2 * d * dtype_bytes
        flops = 2 * nvec * d * d
        return max(flops / self.peak_flops, traffic / self.dram_bw) + self.launch_overhead

    def simd_time(self, elems: int, bytes_: int) -> float:
        return max(elems / (self.peak_flops / 16), bytes_ / self.dram_bw) \
            + self.launch_overhead


# Datasheet-derived constants of the Fig. 5 baselines
# (``benchmarks/bench_tables.py:bench_fig5_runtime`` reports against them).
DEVICES = {
    "tx2": Device("Jetson TX2", 1.33e12, 59.7e9, 12e-6, False),
    "nx": Device("Xavier NX", 6.0e12, 51.2e9, 10e-6, False),
    "xeon": Device("Xeon CPU", 1.0e12, 94e9, 2e-6, False),
    "rtx2080": Device("RTX 2080 Ti", 13.4e12, 616e9, 5e-6, False),
    "coral": Device("Coral edge TPU", 4.0e12, 25.6e9, 30e-6, False),
    "dpu": Device("Xilinx DPU (U250)", 4.0e12, 77e9, 8e-6, False),
}

NSFLOW_FREQ = 272e6   # paper Tab. III
NSFLOW_DRAM_BW = 77e9  # U250 DDR4 (4 channels)
TPU_LIKE_FREQ = 272e6  # same fabric as NSFlow for apples-to-apples (Fig. 5)


@dataclasses.dataclass
class SimResult:
    device: str
    total: float
    nn: float
    vsa: float
    simd: float
    detail: dict = dataclasses.field(default_factory=dict)


def simulate_generic(graph: OpGraph, device: Device) -> SimResult:
    """Sequential per-node roofline execution (GPU/CPU/SoC/DPU model)."""
    t_nn = t_vsa = t_simd = 0.0
    for n in graph:
        r = n.dims.get("repeat", 1)
        if n.kind == "nn":
            t_nn += device.nn_time(n.flops, (n.in_bytes + n.out_bytes) * r)
        elif n.kind == "vsa":
            t_vsa += device.vsa_time(n.dims["nvec"] * r, n.dims["d"])
        elif n.kind == "simd":
            t_simd += device.simd_time(n.dims.get("elems", 1) * r,
                                       (n.in_bytes + n.out_bytes) * r)
    return SimResult(device.name, t_nn + t_vsa + t_simd, t_nn, t_vsa, t_simd)


def simulate_tpu_like(graph: OpGraph, array: int = 128,
                      freq: float = TPU_LIKE_FREQ,
                      dram_bw: float = 600e9,
                      staging_factor: float = 1.0) -> SimResult:
    """Weight-stationary 128×128 systolic array, sequential NN→VSA.

    Circular convolution has no native mapping on a weight-stationary
    matmul array: the standard lowering (what XLA emits today) gathers the
    circulant matrix per binding pair — d× DRAM traffic amplification —
    then runs batched mat-vecs at poor MXU occupancy (~1/8). This DRAM-
    materialization model reproduces the paper's own Fig. 1b measurement
    that symbolic ops take ~90% of runtime on real accelerators.
    ``staging_factor`` > 1 would model on-chip circulant staging (not
    available in stock lowerings; kept as a sensitivity knob).
    """
    t_nn_cyc = ana.t_nn(array, array, [1] * len(graph.nn_nodes()),
                        graph.nn_nodes())
    t_nn = t_nn_cyc / freq
    peak = 2 * array * array * freq  # MAC/s of the array
    bmm_util = 1.0 / 8.0  # batched per-pair mat-vecs: poor MXU occupancy
    t_vsa = 0.0
    for n in graph.vsa_nodes():
        r = n.dims.get("repeat", 1)
        nvec, d = n.dims["nvec"] * r, n.dims["d"]
        # best TPU mapping = batched (d,d)@(d,) circulant mat-vecs:
        # compute at ~1/8 occupancy, circulants staged via on-chip SRAM
        traffic = nvec * d * d * 4
        io = nvec * 2 * d * 4
        flops = 2 * nvec * d * d
        t_vsa += max(flops / (peak * bmm_util),
                     traffic / (staging_factor * dram_bw) + io / dram_bw)
    t_simd = sum(ana.cdiv(n.dims.get("elems", 1), 128) * n.dims.get("repeat", 1)
                 for n in graph.simd_nodes()) / freq
    return SimResult(f"TPU-like SA {array}x{array}", t_nn + t_vsa + t_simd,
                     t_nn, t_vsa, t_simd)


def simulate_nsflow(graph: OpGraph, max_pes: int = 16384, iter_max: int = 8,
                    freq: float = NSFLOW_FREQ, dram_bw: float = NSFLOW_DRAM_BW,
                    n_loops: int = 4, force_mode: str | None = None,
                    phase2_enabled: bool = True) -> SimResult:
    """NSFlow AdArray: DSE config + folding overlap + SIMD hiding."""
    df = dfl.build(graph)
    cfg = dse_mod.phase1(df, max_pes)
    if force_mode == "sequential":
        cfg = dataclasses.replace(cfg, mode="sequential",
                                  t_para=cfg.t_seq)
    elif phase2_enabled:
        cfg = dse_mod.phase2(df, cfg, iter_max)
    mem = ana.memory_plan(graph, cfg.t_best)
    layers, vnodes = df.nn_nodes, df.vsa_nodes
    if cfg.mode == "parallel":
        t_nn_cyc = ana.t_nn(cfg.H, cfg.W, cfg.n_l, layers)
        t_vsa_cyc = ana.t_vsa(cfg.H, cfg.W, cfg.n_v, vnodes)
        overlap = dfl.interloop_overlap(df, t_nn_cyc, t_vsa_cyc, n_loops)
        cycles = overlap["pipelined"] / n_loops
    else:
        t_nn_cyc = ana.t_nn(cfg.H, cfg.W, [cfg.N] * len(layers), layers) if layers else 0
        t_vsa_cyc = ana.t_vsa(cfg.H, cfg.W, [cfg.N] * len(vnodes), vnodes) if vnodes else 0
        cycles = t_nn_cyc + t_vsa_cyc
    # SIMD stream is sized to hide under the array runtime (Sec V-C)
    t_simd_cyc = ana.t_simd(mem.simd_lanes, graph.simd_nodes())
    hidden = min(t_simd_cyc, cycles)
    total_cycles = cycles + (t_simd_cyc - hidden)
    # off-chip transfer overlapped with compute via double buffering; only
    # the non-overlappable excess stalls
    bytes_total = graph.total_bytes()
    t_mem = bytes_total / dram_bw
    t_compute = total_cycles / freq
    total = max(t_compute, t_mem)
    return SimResult("NSFlow", total, t_nn_cyc / freq, t_vsa_cyc / freq,
                     t_simd_cyc / freq,
                     detail={"config": cfg.summary(), "mem_stall_bound": t_mem,
                             "cycles_per_loop": cycles})
