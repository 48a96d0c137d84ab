"""Quantised wire codecs (DESIGN.md §3.8): the bit-width compression axis.

The fused pack+quantise kernel family, the ``[Q, Q]`` / ``[L, Q, Q]``
width-map plumbing through both aggregation oracles, the wire-bit
accounting (payload at width + fp32 scales), the error-feedback residual
loop, the controllers' rate × width allocation, and the bounded-recompile
contract of the width-keyed train step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from parity import build_setup, mixed_map, mixed_width_map

from repro.core import CommPolicy, fixed
from repro.dist.gnn_parallel import (DistMeta, _make_aggregate_emulated,
                                     _packed_k_for, _packed_pair_k_for,
                                     _packed_pair_w_for, _snap_width)
from repro.dist.ratectl import (RatePlan, budget_controller, error_controller,
                                exchange_widths, init_wire_residuals,
                                make_auto_train_step, make_pacing,
                                stale_controller, width_candidates,
                                width_cost)
from repro.kernels import ref
from repro.kernels.ops import (LANE, pack_quant, per_block_wire_bits,
                               quant_dequant, unpack_quant)
from repro.kernels.varco_pack import block_mask_indices
from repro.nn.gnn import gnn_forward
from repro.train.optim import sgd

F = 512
Q = 4
NB = F // LANE
WIDTHS = (2, 4, 8)


@pytest.fixture(scope="module")
def setup():
    _, cfg, params, pg, graph = build_setup(Q, f=F, layers=2, n=256)
    return cfg, params, pg, graph


def _uniform(rate: float) -> np.ndarray:
    rm = np.full((Q, Q), rate, np.float32)
    np.fill_diagonal(rm, 1.0)
    return rm


def _wmap(width: float) -> np.ndarray:
    wm = np.full((Q, Q), width, np.float32)
    np.fill_diagonal(wm, 32.0)
    return wm


def _agg(graph, meta, rm, key, wm=None, resid=None, resid_out=None):
    kb = dict(_packed_pair_k_for(meta, rm))
    return _make_aggregate_emulated(
        graph, meta, fixed(4.0, compressor="blockmask"), None,
        jnp.ones((), jnp.float32), key, packed_k=kb,
        rate_map=jnp.asarray(rm),
        width_map=None if wm is None else jnp.asarray(wm),
        resid=resid, resid_out=resid_out)


# ---------------------------------------------------------------------------
# fused pack+quantise kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", WIDTHS)
def test_pack_quant_matches_reference(width):
    """The fused Pallas kernel (interpret mode) and the jnp oracle agree
    bit-for-bit on both the bit-packed uint8 payload and the fp32
    scales, and the decode reproduces ``quant_dequant`` of the packed
    payload exactly."""
    key = jax.random.key(3)
    x = jax.random.normal(key, (24, F), jnp.float32) * \
        10.0 ** jax.random.uniform(jax.random.fold_in(key, 1), (24, 1),
                                   minval=-2.0, maxval=2.0)
    kept, inv = block_mask_indices(key, NB, 2.0)
    k = int(kept.shape[0])
    packed_k, scales_k = pack_quant(x, kept, width=width, interpret=True)
    packed_r, scales_r = ref.pack_quant_reference(x, kept, width)
    # true sub-byte storage: uint8 bytes, 8/width lanes per byte — the
    # buffer IS the ledger's LANE·width payload bits per kept block
    assert packed_k.dtype == jnp.uint8 and scales_k.dtype == jnp.float32
    assert packed_k.shape == (24, k * LANE * width // 8)
    np.testing.assert_array_equal(np.asarray(packed_k), np.asarray(packed_r))
    # the kernel folds 1/qmax into a multiply — scales match to fp32 ulp
    np.testing.assert_allclose(np.asarray(scales_k), np.asarray(scales_r),
                               rtol=1e-6)
    # w == 8 is bitwise the former int8-lane storage
    if width == 8:
        levels, _ = ref.quant_levels_reference(
            ref.pack_reference(x, kept), 8)
        np.testing.assert_array_equal(
            np.asarray(packed_k),
            np.asarray(jax.lax.bitcast_convert_type(levels, jnp.uint8)))
    # decode == quant_dequant of the packed fp32 payload (same scale rule)
    dq = ref.unpack_quant_reference(packed_r, scales_r, width)
    from repro.kernels.ops import wire_pack
    payload = wire_pack(x, kept, inv)
    np.testing.assert_allclose(np.asarray(dq),
                               np.asarray(quant_dequant(payload, width)),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("width", WIDTHS)
def test_unpack_quant_matches_reference(width):
    """The fused decode kernel (interpret mode) rebuilds, bitwise, what
    the oracles decode from the same bytes — dequantised kept blocks
    scattered back, dropped blocks zero."""
    key = jax.random.key(5)
    x = jax.random.normal(key, (40, F), jnp.float32)
    kept, inv = block_mask_indices(key, NB, 2.0)
    payload, scales = ref.pack_quant_reference(x, kept, width)
    got = unpack_quant(payload, scales, inv, width=width, interpret=True)
    want = ref.unpack_reference(
        ref.unpack_quant_reference(payload, scales, width), inv)
    assert got.shape == (40, F) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_per_block_wire_bits_values():
    assert float(per_block_wire_bits(32)) == LANE * 32.0
    for w in WIDTHS:
        assert float(per_block_wire_bits(w)) == LANE * w + 32.0


# ---------------------------------------------------------------------------
# width snapping and the static distinct-width key
# ---------------------------------------------------------------------------


def test_snap_width_grid():
    vals = [1.0, 2.0, 2.1, 4.0, 5.5, 8.0, 9.0, 31.0, 32.0, 40.0]
    assert [_snap_width(v) for v in vals] == \
        [2, 2, 4, 4, 8, 8, 32, 32, 32, 32]


def test_packed_pair_w_for_distinct_sub32(setup):
    _, params, pg, _ = setup
    meta = DistMeta.build(pg, params, wire="p2p")
    assert _packed_pair_w_for(meta, None) == ()
    assert _packed_pair_w_for(meta, _wmap(32.0)) == ()
    wm = _wmap(4.0)
    wm[0, 1] = 2.0
    wm[2, 3] = 7.5          # snaps up to 8
    assert _packed_pair_w_for(meta, wm) == (2, 4, 8)
    # the [L, Q, Q] tensor pools widths across layers
    wml = np.stack([_wmap(8.0), _wmap(32.0)])
    assert _packed_pair_w_for(meta, wml) == (8,)


def test_packed_k_shared_quantiser_consistency(setup):
    """Satellite: `_packed_k_for` and `_packed_pair_k_for` share one
    exchanged-width table — a uniform map must quantise identically to
    the scalar rate on every exchanged lane-block count."""
    _, params, pg, _ = setup
    for wire in ("packed", "p2p"):
        meta = DistMeta.build(pg, params, wire=wire)
        for rate in (1.0, 1.5, 2.0, 3.9, 16.0):
            assert dict(_packed_k_for(meta, rate)) == \
                dict(_packed_pair_k_for(meta, _uniform(rate))), (wire, rate)


# ---------------------------------------------------------------------------
# ledger: transport == analytic wire bits at every width
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", WIDTHS)
def test_p2p_transport_quant_matches_analytic(setup, width):
    """Per-pair transport at uniform (rate, width) is ``rows · K ·
    (128·w + 32)`` per exchange — and sums to the analytic
    ``DistMeta.transport_bits_quant`` at every width."""
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire="p2p")
    rate = 2.0
    agg = _agg(graph, meta, _uniform(rate), jax.random.key(5),
               wm=_wmap(width))
    _, bits = gnn_forward(params, cfg, graph["features"], agg)
    pair_t = np.asarray(bits[2:2 + Q * Q]).reshape(Q, Q)
    rows = meta.pair_table().astype(np.float64)
    k = np.maximum(np.floor(NB / _uniform(rate)), 1.0)
    np.fill_diagonal(k, 0.0)
    expect = 2 * rows * k * (LANE * width + 32.0)   # two exchanges at F
    np.testing.assert_allclose(pair_t, expect, rtol=1e-6)
    analytic = 2 * float(meta.transport_bits_quant(F, rate, width))
    np.testing.assert_allclose(pair_t.sum(), analytic, rtol=1e-6)
    # fp32 "width" reproduces the unquantised ledger bit-for-bit
    agg32 = _agg(graph, meta, _uniform(rate), jax.random.key(5),
                 wm=_wmap(32.0))
    _, bits32 = gnn_forward(params, cfg, graph["features"], agg32)
    agg_none = _agg(graph, meta, _uniform(rate), jax.random.key(5))
    _, bits_none = gnn_forward(params, cfg, graph["features"], agg_none)
    np.testing.assert_array_equal(np.asarray(bits32), np.asarray(bits_none))


def test_packed_transport_quant_per_sender(setup):
    """The all-gather wire quantises each sender's payload once at the
    max width any receiver wants — transport charges the realised
    ``k_send · per_block_wire_bits(w_send)`` to every pair in the
    column."""
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire="packed")
    rm = mixed_map(Q, 4)
    wm = mixed_width_map(Q, 4)
    agg = _agg(graph, meta, rm, jax.random.key(5), wm=wm)
    _, bits = gnn_forward(params, cfg, graph["features"], agg)
    pair_t = np.asarray(bits[2:2 + Q * Q]).reshape(Q, Q)
    rows = meta.pair_table().astype(np.float64)
    k = np.maximum(np.floor(NB / rm), 1.0)
    np.fill_diagonal(k, 0.0)
    k_send = np.maximum(k.max(axis=0), 1.0)
    off_w = np.where(np.eye(Q, dtype=bool), 0.0, wm)
    w_send = off_w.max(axis=0)
    blk = np.where(w_send >= 32.0, LANE * 32.0, LANE * w_send + 32.0)
    expect = 2 * rows * (k_send * blk)[None, :]
    np.testing.assert_allclose(pair_t, expect, rtol=1e-6)


def test_analytic_ledger_scales_with_width(setup):
    """The analytic (requested-rate) column charges payload at width —
    ``w/32`` of the fp32 charge, scale overhead excluded by
    convention."""
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire="p2p")
    rm = _uniform(2.0)
    _, b32 = gnn_forward(params, cfg, graph["features"],
                         _agg(graph, meta, rm, jax.random.key(5)))
    _, b4 = gnn_forward(params, cfg, graph["features"],
                        _agg(graph, meta, rm, jax.random.key(5),
                             wm=_wmap(4.0)))
    np.testing.assert_allclose(float(b4[0]), float(b32[0]) * 4.0 / 32.0,
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# quantisation is actually applied (and bounded)
# ---------------------------------------------------------------------------


def test_width_map_quantises_hops_within_bound(setup):
    """A w-bit wire perturbs the logits (quantisation is real) but the
    perturbation shrinks as the width grows."""
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire="p2p")
    rm = _uniform(1.0)
    exact, _ = gnn_forward(params, cfg, graph["features"],
                           _agg(graph, meta, rm, jax.random.key(5)))
    errs = []
    for w in WIDTHS:
        lq, _ = gnn_forward(params, cfg, graph["features"],
                            _agg(graph, meta, rm, jax.random.key(5),
                                 wm=_wmap(w)))
        errs.append(float(jnp.abs(lq - exact).max()))
    assert errs[0] > 0.0
    assert errs[0] > errs[1] > errs[2]            # 2 > 4 > 8 bit error


# ---------------------------------------------------------------------------
# error feedback through the cache channel
# ---------------------------------------------------------------------------


def test_error_feedback_residuals_and_carry(setup):
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire="p2p")
    policy = CommPolicy.parse("auto:budget:1e9:w2", 10)
    opt = sgd(1e-2)
    step = make_auto_train_step(cfg, policy, opt, meta)
    cache = init_wire_residuals(meta, cfg)
    assert len(cache) == len(exchange_widths(cfg))
    p, s = params, opt.init(params)
    plan_q = RatePlan(jnp.asarray(_uniform(1.0)),
                      jnp.zeros((Q, Q), jnp.float32),
                      jnp.asarray(_wmap(2.0)))
    p, s, m, cache1 = step(p, s, graph, jax.random.key(0), plan_q, cache)
    assert len(cache1) == len(cache)
    for r0, r1 in zip(cache, cache1):
        assert r1.shape == r0.shape
    # residuals are the quantisation error — nonzero at w=2
    assert any(float(jnp.abs(r).max()) > 0.0 for r in cache1)
    # an exact step (widths=None, or an all-32 map) carries the EF state
    # unchanged instead of wiping it
    for widths in (None, jnp.asarray(_wmap(32.0))):
        plan_x = RatePlan(jnp.asarray(_uniform(1.0)),
                          jnp.zeros((Q, Q), jnp.float32), widths)
        _, _, _, cache2 = step(p, s, graph, jax.random.key(1), plan_x,
                               cache1)
        assert len(cache2) == len(cache1)
        for a, b in zip(cache1, cache2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_error_feedback_recurrence_time_average():
    """The EF recurrence the wire runs — ``q_t = Q(x + r_t)``,
    ``r_{t+1} = x + r_t − q_t`` — makes the time-averaged wire output
    converge to the exact payload at rate 1/T (the bounded-bias
    property residual shipping buys)."""
    key = jax.random.key(11)
    x = jax.random.normal(key, (8, 2 * LANE), jnp.float32)
    for width in (2, 4):
        r = jnp.zeros_like(x)
        outs = []
        for _ in range(16):
            q = quant_dequant(x + r, width)
            r = x + r - q
            outs.append(q)
        qmax = 2.0 ** (width - 1) - 1.0
        amax = np.abs(np.asarray(x)).max()
        # |mean_t q_t − x| = |r_T| / T ≤ scale_max / T
        bound = (amax * (1.0 + 1.0 / qmax)) / qmax / len(outs)
        err = np.abs(np.asarray(jnp.mean(jnp.stack(outs), 0) - x)).max()
        assert err <= bound + 1e-6, (width, err, bound)


def test_quantising_policy_routes_ef_cache_end_to_end():
    """train_gnn at a w<32 p2p policy initialises the EF residual cache
    and trains; a stale policy keeps hop-reuse ownership of the
    channel."""
    from repro.graph import tiny_graph
    from repro.train.trainer import train_gnn

    g = tiny_graph(n=128, feat_dim=256)
    res = train_gnn(g, q=2, scheme="random",
                    policy=CommPolicy.parse("auto:budget:5e6:w4", 4),
                    epochs=4, eval_every=4, hidden=128, seed=0, wire="p2p")
    assert np.isfinite(res.history.loss[-1])


# ---------------------------------------------------------------------------
# bounded recompiles across a mixed rate × width sweep (satellite)
# ---------------------------------------------------------------------------


def test_rate_width_sweep_bounds_recompiles(setup):
    """Rates quantise to kept-block counts and widths snap to the storage
    grid — a sweep of distinct (rate, width) plans whose static keys
    coincide must share compiled steps."""
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire="p2p")
    policy = CommPolicy.parse("auto:budget:1e9:w4", 10)
    opt = sgd(1e-2)
    step = make_auto_train_step(cfg, policy, opt, meta)
    p, s = params, opt.init(params)
    cache = init_wire_residuals(meta, cfg)
    sweep = [(1.5, 4.0), (2.0, 3.7),     # same k (nb/r floors to 2), w→4
             (1.6, 4.0),                 # again
             (2.0, 32.0), (1.5, None)]   # exact wire: shares ONE variant
    for i, (rate, width) in enumerate(sweep):
        widths = None if width is None else jnp.asarray(_wmap(width))
        plan = RatePlan(jnp.asarray(_uniform(rate)),
                        jnp.zeros((Q, Q), jnp.float32), widths)
        p, s, m, cache = step(p, s, graph, jax.random.key(i), plan, cache)
    # two compiled variants: (k=2, w=(4,)) and (k=2, exact)
    assert step._jit_step._cache_size() == 2


# ---------------------------------------------------------------------------
# controllers allocate along the rate × width frontier
# ---------------------------------------------------------------------------


def _pacing(pg, params, budget, steps=20):
    meta = DistMeta.build(pg, params, wire="p2p")
    return meta, make_pacing(meta, (F, F), steps, budget)


def test_budget_controller_width_under_squeeze(setup):
    """A squeezed budget drops the uniform wire below 32 bits; a generous
    one stays exact (an all-32 pick, which the step collapses to the
    pre-quantisation compiled program)."""
    _, params, pg, _ = setup
    meta, pacing = _pacing(pg, params, budget=1e12)
    ctl = budget_controller(Q, pacing, max_width=2)
    plan, _ = ctl.plan(ctl.init(), 0)
    assert np.all(np.asarray(plan.widths) == 32.0)   # generous → fp32
    assert _packed_pair_w_for(meta, np.asarray(plan.widths)) == ()
    # the plan stays jit-compatible with the width axis on
    plan_j, _ = jax.jit(ctl.plan)(ctl.init(), jnp.asarray(0))
    assert plan_j.widths.shape == (Q, Q)
    meta, pacing = _pacing(pg, params, budget=0.02 * pacing.d_full * 20)
    ctl = budget_controller(Q, pacing, max_width=2)
    plan, _ = ctl.plan(ctl.init(), 0)
    assert plan.widths is not None
    wm = np.asarray(plan.widths)
    assert np.all(np.diag(wm) == 32.0)
    off = wm[~np.eye(Q, dtype=bool)]
    assert np.all(off < 32.0) and set(np.unique(off)) <= {2.0, 4.0, 8.0}
    # max_width=32 turns the axis off entirely
    ctl32 = budget_controller(Q, pacing, max_width=32)
    plan32, _ = ctl32.plan(ctl32.init(), 0)
    assert plan32.widths is None


def test_error_controller_refines_widths(setup):
    _, params, pg, _ = setup
    meta, pacing = _pacing(pg, params, budget=1.0)
    meta, pacing = _pacing(pg, params, budget=0.05 * pacing.d_full * 20)
    ctl = error_controller(Q, pacing, meta.pair_table(), max_width=2)
    state = ctl.init()
    plan, state = ctl.plan(state, 0)
    assert plan.widths is not None
    wm = np.asarray(plan.widths)
    live = meta.pair_table() > 0
    np.fill_diagonal(live, False)
    assert np.all(wm[~live] == 32.0)                 # dead pairs exact
    assert np.all(np.isin(wm[live], [2.0, 4.0, 8.0, 32.0]))
    # committed y stays monotone across steps (Prop. 2 untouched)
    y0 = np.asarray(state["y"])
    state = ctl.observe(state, {
        "transport_bits": jnp.zeros(()),
        "pair_err": jnp.asarray(meta.pair_table(), jnp.float32)})
    _, state = ctl.plan(state, 1)
    assert np.all(np.asarray(state["y"]) >= y0 - 1e-7)


def test_stale_controller_static_width(setup):
    _, params, pg, _ = setup
    meta, pacing = _pacing(pg, params, budget=1e9)
    ctl = stale_controller(Q, pacing, max_width=8)
    plan, _ = ctl.plan(ctl.init(), 0)
    wm = np.asarray(plan.widths)
    assert np.all(np.diag(wm) == 32.0)
    assert np.all(wm[~np.eye(Q, dtype=bool)] == 8.0)
    # the cheaper wire lets the same allowance afford a lower rate
    ctl32 = stale_controller(Q, pacing, max_width=32)
    plan32, _ = ctl32.plan(ctl32.init(), 0)
    off = ~np.eye(Q, dtype=bool)
    assert np.all(np.asarray(plan.rates)[off] <=
                  np.asarray(plan32.rates)[off] + 1e-6)


def test_width_candidates_and_cost():
    assert width_candidates(32) == (32,)
    assert width_candidates(8) == (32, 8)
    assert width_candidates(2) == (32, 8, 4, 2)
    assert width_cost(32) == 1.0
    assert width_cost(4) == pytest.approx((4 + 32.0 / LANE) / 32.0)


# ---------------------------------------------------------------------------
# backend parity at mixed rate × width (subprocess; the fast cases —
# the full sweep lives in test_parity_matrix)
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# ledger-vs-buffer conservation (the tentpole's closing check)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_wire_conservation_ledger_matches_buffers():
    """On BOTH backends, at w ∈ {2, 4, 8, 32}: every p2p hop's
    transported array (bit-packed uint8 payload + fp32 scales under
    ``store_w``; fp32 rows at 32) has ``nbytes == ceil(per-pair ledger
    transport bits / 8)`` — hop by hop, per-pair in total, and with
    byte-identical buffers across backends.  The packed wire conforms
    per transported row (its ledger charges halo demand, not the padded
    all-gather buffer)."""
    from parity import run_wire_conservation

    out = run_wire_conservation(4)
    assert out.count(" OK ") == 4, out


@pytest.mark.slow
def test_forward_parity_mixed_rate_width():
    from parity import run_forward_parity

    cases = [
        {"wire": "p2p", "policy": "fixed:4", "map": "pair",
         "width_map": "pair"},
        {"wire": "p2p", "policy": "fixed:4", "map": "layer",
         "width_map": "layer"},
        {"wire": "packed", "policy": "fixed:4", "map": "pair",
         "width_map": "pair"},
        {"wire": "packed", "policy": "fixed:4", "map": "layer",
         "width_map": "layer"},
    ]
    out = run_forward_parity(4, cases, f=256)
    assert out.count(" OK ") == len(cases), out
