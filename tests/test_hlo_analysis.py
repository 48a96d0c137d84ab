"""Trip-count-aware HLO collective accounting."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import (collective_bytes,
                                       computation_multipliers,
                                       split_computations, while_trip_count)

FAKE_HLO = """
HloModule jit_step

%body.1 (arg.1: (s32[], f32[64,128])) -> (s32[], f32[64,128]) {
  %p = (s32[], f32[64,128]) parameter(0)
  %x = f32[64,128]{1,0} get-tuple-element(%p), index=1
  %ar = f32[64,128]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %t = (s32[], f32[64,128]) tuple(%i, %ar)
}

%cond.1 (arg.2: (s32[], f32[64,128])) -> pred[] {
  %p2 = (s32[], f32[64,128]) parameter(0)
  %i2 = s32[] get-tuple-element(%p2), index=0
  %c = s32[] constant(9)
  ROOT %lt = pred[] compare(%i2, %c), direction=LT
}

ENTRY %main (a: f32[64,128]) -> f32[64,128] {
  %a = f32[64,128]{1,0} parameter(0)
  %ag = f32[256,128]{1,0} all-gather(%a), replica_groups={{0,1,2,3}}, dimensions={0}
  %w = (s32[], f32[64,128]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[64,128]{1,0} get-tuple-element(%w), index=1
}
"""


def test_split_and_trip_count():
    comps = split_computations(FAKE_HLO)
    assert {"body.1", "cond.1", "main"} <= set(comps)
    assert while_trip_count(comps["cond.1"]) == 9


def test_multipliers_count_loop_trips():
    mult = computation_multipliers(FAKE_HLO)
    assert mult["main"] == 1.0
    assert mult["body.1"] == 9.0


def test_collective_bytes_trip_aware():
    out = collective_bytes(FAKE_HLO)
    # all-gather in entry: result 256*128*4 bytes, g=4 -> (3/4)*r, once
    ag = (3 / 4) * 256 * 128 * 4
    # all-reduce in body: r = 64*128*4, g=4 -> 2*(3/4)*r, nine times
    ar = 9 * 2 * (3 / 4) * 64 * 128 * 4
    assert abs(out["per_kind"]["all-gather"] - ag) < 1e-6
    assert abs(out["per_kind"]["all-reduce"] - ar) < 1e-6
    assert out["ops"] == 2


def test_real_hlo_scan_multiplier():
    """End-to-end: a jitted scan with a psum-like collective is scaled."""
    import functools
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    # single-device "mesh" won't emit collectives; instead check scan body
    # counting with a dot inside a loop via collective-free sanity: the
    # multiplier machinery must find trip count 7 for a length-7 scan.
    def f(x):
        def body(c, _):
            return c * 1.5 + 1.0, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    txt = jax.jit(f).lower(jnp.ones((8, 128))).compile().as_text()
    mult = computation_multipliers(txt)
    assert any(abs(v - 7.0) < 1e-6 for v in mult.values()), \
        sorted(set(mult.values()))
