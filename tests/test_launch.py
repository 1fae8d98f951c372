"""Launch-layer unit tests: roofline HLO parsing, memory planning tiles,
mesh DSE sanity (no compiles — the dry-run itself runs out-of-band)."""

import jax.numpy as jnp
import numpy as np

from repro.core import memplan, workloads
from repro.core.analytical import memory_plan
from repro.launch import roofline as rl


SYNTH_HLO = """
HloModule jit_train_step

%region_1.100 (a: f32[16,1024]) -> f32[16,1024] {
  %p = f32[16,1024]{1,0} parameter(0)
  %ar = f32[16,1024]{1,0} all-reduce(f32[16,1024]{1,0} %p), replica_groups={}
  ROOT %r = f32[16,1024]{1,0} add(%ar, %ar)
}

ENTRY %main (x: bf16[8,512]) -> bf16[8,512] {
  %x = bf16[8,512]{1,0} parameter(0)
  %ag = bf16[64,512]{1,0} all-gather(bf16[8,512]{1,0} %x), dimensions={0}
  %w = s32[] while(s32[] %c), condition=%cond.1, body=%region_1.100
  %cp = bf16[8,512]{1,0} collective-permute(bf16[8,512]{1,0} %x), source_target_pairs={{0,1}}
  ROOT %out = bf16[8,512]{1,0} add(%cp, %x)
}
"""


def test_parse_collectives_counts_and_trips():
    bytes_, counts = rl.parse_collectives(SYNTH_HLO, default_trips=7)
    # all-gather operand: 8*512*2 bytes in entry (trips 1)
    assert bytes_["all-gather"] == 8 * 512 * 2
    # all-reduce lives inside the while body -> scaled by 7
    assert bytes_["all-reduce"] == 16 * 1024 * 4 * 7
    assert counts["all-reduce"] == 7
    assert bytes_["collective-permute"] == 8 * 512 * 2
    assert bytes_["reduce-scatter"] == 0.0


def test_roofline_terms_dominance():
    t = rl.roofline_terms(flops_per_device=197e12, bytes_per_device=0,
                          collective_bytes_total=0, chips=1)
    assert abs(t["compute_s"] - 1.0) < 1e-9 and t["dominant"] == "compute"
    t = rl.roofline_terms(0, 819e9, 0, 1)
    assert abs(t["memory_s"] - 1.0) < 1e-9 and t["dominant"] == "memory"
    t = rl.roofline_terms(0, 0, 200e9 * 4, 4)
    assert t["dominant"] == "collective"


def test_memplan_tiles_fit_vmem():
    g = workloads.nvsa_graph()
    mem = memory_plan(g, t_parallel=10**6)
    tiles = memplan.plan_tiles(mem, d=256)
    assert tiles.circ_elem_tile_n >= 1
    # circulant working set within the VMEM budget
    assert tiles.circ_elem_tile_n * 256 * 256 * 4 * 2 <= tiles.vmem_budget
    assert tiles.qmm_bm % 128 == 0
    merged = memplan.plan_tiles(mem, d=256, concurrent=False)
    assert merged.circ_elem_tile_n >= tiles.circ_elem_tile_n  # A1/A2 merge


def test_shape_bytes_parser():
    assert rl._shape_bytes("f32[4,4]") == 64
    assert rl._shape_bytes("bf16[2,3] , s8[10]") == 12 + 10
    assert rl._shape_bytes("pred[]") == 1  # scalar: empty dims


def test_device_peaks_by_kind():
    """The mesh search reads peaks by device_kind: a CPU host plans
    against the modelled v5e, a known chip gets its own row, and an
    unknown accelerator raises instead of borrowing v5e numbers."""
    import types

    import pytest

    from repro.launch.mesh import HW, PEAKS, device_peaks

    assert device_peaks() is HW  # the CPU test host
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_peaks(v5e) is PEAKS["TPU v5 lite"]
    with pytest.raises(ValueError, match="TPU v9"):
        device_peaks(types.SimpleNamespace(platform="tpu",
                                           device_kind="TPU v9"))
