"""Packed-wire halo exchange: packed vs dense parity + transport ledger.

The packed wire (DESIGN.md §3.3) must be a pure transport change: for the
same per-exchange key it delivers exactly the values of the dense
``blockmask`` round trip — forward and backward — while shipping only the
``[B, K·128]`` lane-block payload.  These tests pin that contract at every
rate the acceptance sweep uses ({1, 2, 4, 16}), on the emulated backend
here and on the real shard_map collectives in ``test_multidevice.py``
style subprocesses below.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FULL_COMM, fixed, get_compressor, varco
from repro.core.varco import CommLedger
from repro.dist.gnn_parallel import (DistMeta, _make_aggregate_emulated,
                                     make_eval_step, make_train_step)
from repro.graph import partition_graph, tiny_graph
from repro.kernels import ops, ref
from repro.kernels.varco_pack import block_mask_indices
from repro.nn import GNNConfig, init_gnn
from repro.nn.gnn import gnn_forward
from repro.train.optim import adamw

RATES = [1.0, 2.0, 4.0, 16.0]
F = 256                                  # 2 lane-blocks; rate 16 floors to 1


@pytest.fixture(scope="module")
def setup():
    g = tiny_graph(n=256, feat_dim=F)
    cfg = GNNConfig(conv="sage", in_dim=F, hidden=128,
                    out_dim=g.num_classes, layers=3)
    params = init_gnn(jax.random.key(0), cfg)
    pg = partition_graph(g, 4, scheme="random")
    graph = pg.device_arrays()
    return cfg, params, pg, graph


def _metas(pg, params):
    return (DistMeta.build(pg, params),
            DistMeta.build(pg, params, wire="packed"))


def _policy(rate):
    return FULL_COMM if rate == 1.0 else fixed(rate, compressor="blockmask")


# ---------------------------------------------------------------------------
# wire ops / compressor agreement
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rate", RATES)
def test_blockmask_roundtrip_equals_wire_path(rate):
    """Dense blockmask compressor == wire_unpack(wire_pack(x)), bitwise."""
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (64, 512)),
                    jnp.float32)
    key = jax.random.key(7)
    dense, bits = get_compressor("blockmask")(key, x, jnp.asarray(rate))
    kept, inv = block_mask_indices(key, 512 // 128, rate)
    wired = ops.wire_unpack(ops.wire_pack(x, kept, inv), kept, inv)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(wired))
    # dense ledger charge counts exactly the packed payload elements
    assert float(bits) == kept.shape[0] * 128 * 64 * 32


@pytest.mark.parametrize("rate", RATES)
def test_wire_ops_gradient_is_block_mask(rate):
    """Custom VJPs: d/dx of the wire round trip is the kept-block mask."""
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (16, 512)),
                    jnp.float32)
    kept, inv = block_mask_indices(jax.random.key(3), 512 // 128, rate)

    def loss(x_):
        return jnp.sum(ops.wire_unpack(ops.wire_pack(x_, kept, inv),
                                       kept, inv) ** 2)

    g = jax.grad(loss)(x)
    mask = np.zeros(512 // 128, bool)
    mask[np.asarray(kept)] = True
    expect = 2 * np.asarray(x).reshape(16, -1, 128) * mask[None, :, None]
    np.testing.assert_allclose(np.asarray(g), expect.reshape(16, 512),
                               rtol=1e-6, atol=0)


def test_pallas_row_padding_matches_oracle():
    """The TPU dispatch pads arbitrary row counts (B = halo_size) to what
    the Pallas grid accepts; padded-kernel-then-slice must equal the oracle
    on the original rows.  Exercised here in interpret mode."""
    from repro.kernels.ops import _padded_rows
    from repro.kernels.varco_pack import varco_pack, varco_unpack

    for n in (3, 100, 300, 512):
        x = jnp.asarray(np.random.default_rng(n).normal(0, 1, (n, 256)),
                        jnp.float32)
        kept, inv = block_mask_indices(jax.random.key(0), 2, 2.0)
        pad = _padded_rows(n) - n
        assert _padded_rows(n) % min(256, _padded_rows(n)) == 0
        xp = jnp.pad(x, ((0, pad), (0, 0)))
        packed = varco_pack(xp, kept, interpret=True)[:n]
        np.testing.assert_array_equal(np.asarray(packed),
                                      np.asarray(ref.pack_reference(x, kept)))
        up = varco_unpack(jnp.pad(packed, ((0, pad), (0, 0))), inv,
                          interpret=True)[:n]
        np.testing.assert_array_equal(
            np.asarray(up), np.asarray(ref.unpack_reference(packed, inv)))


def test_packed_k_quantisation_bounds_recompiles():
    """Annealing rates map to the static kept-block counts, so nearby rates
    share a compiled step (128.0 and 96.25 both keep 1 block of 2)."""
    from repro.dist.gnn_parallel import _packed_k_for

    meta = DistMeta(q=2, part_size=1, halo_size=1, num_nodes=2,
                    feat_dim=256, num_classes=2, halo_demand=1,
                    cross_edges=1, n_train=1, n_val=0, n_test=1,
                    layer_dims=(256, 128), wire="packed")
    # exchanged widths: 256 (nb=2) and 128 (nb=1)
    assert _packed_k_for(meta, 128.0) == _packed_k_for(meta, 96.25) \
        == ((1, 1), (2, 1))
    assert _packed_k_for(meta, 1.0) == ((1, 1), (2, 2))
    assert len({_packed_k_for(meta, r)
                for r in np.linspace(1.0, 128.0, 200)}) <= 2


def test_packed_rejects_off_lane_widths_at_build():
    g = tiny_graph(n=64, feat_dim=96)              # 96 % 128 != 0
    cfg = GNNConfig(conv="sage", in_dim=96, hidden=128,
                    out_dim=g.num_classes, layers=2)
    params = init_gnn(jax.random.key(0), cfg)
    pg = partition_graph(g, 2, scheme="random")
    with pytest.raises(ValueError, match="divisible"):
        DistMeta.build(pg, params, wire="packed")
    DistMeta.build(pg, params)                     # dense wire: fine


def test_packed_width_matches_kernel_selection():
    for rate in RATES + [3.0, 7.0, 100.0]:
        for f in (128, 256, 1024):
            meta_args = dict(q=2, part_size=1, halo_size=1, num_nodes=2,
                             feat_dim=f, num_classes=2, halo_demand=1,
                             cross_edges=1, n_train=1, n_val=0, n_test=1,
                             layer_dims=(f,), wire="packed")
            meta = DistMeta(**meta_args)
            kept, _ = block_mask_indices(jax.random.key(0), f // 128, rate)
            assert meta.packed_width(f, rate) == kept.shape[0] * 128


# ---------------------------------------------------------------------------
# emulated runtime parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rate", RATES)
def test_packed_forward_matches_dense_blockmask(setup, rate):
    cfg, params, pg, graph = setup
    meta_d, meta_p = _metas(pg, params)
    pol = _policy(rate)
    comp = pol.compressor() if pol.compresses else None
    agg_d = _make_aggregate_emulated(graph, meta_d, pol, comp,
                                     jnp.asarray(rate), jax.random.key(2))
    agg_p = _make_aggregate_emulated(graph, meta_p, pol, comp, rate,
                                     jax.random.key(2))
    ld, bd = gnn_forward(params, cfg, graph["features"], agg_d)
    lp, bp = gnn_forward(params, cfg, graph["features"], agg_p)
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
    # same analytic charge; transports differ (dense ships full F always)
    np.testing.assert_allclose(float(bd[0]), float(bp[0]), rtol=1e-6)
    assert float(bp[1]) <= float(bd[1]) + 1e-6


@pytest.mark.parametrize("rate", RATES)
def test_packed_backward_matches_dense_blockmask(setup, rate):
    cfg, params, pg, graph = setup
    meta_d, meta_p = _metas(pg, params)
    pol = _policy(rate)
    comp = pol.compressor() if pol.compresses else None

    def loss(p, meta, r):
        agg = _make_aggregate_emulated(graph, meta, pol, comp, r,
                                       jax.random.key(4))
        logits, _ = gnn_forward(p, cfg, graph["features"], agg)
        return jnp.sum(logits ** 2)

    gd = jax.grad(loss)(params, meta_d, jnp.asarray(rate))
    gp = jax.grad(loss)(params, meta_p, rate)
    for a, b in zip(jax.tree_util.tree_leaves(gd),
                    jax.tree_util.tree_leaves(gp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_rate1_training_matches_dense_full_comm(setup):
    """Acceptance: packed rate-1 training bitwise-close to dense full comm."""
    cfg, params, pg, graph = setup
    meta_d, meta_p = _metas(pg, params)
    opt = adamw(5e-3)
    outs = []
    for meta in (meta_d, meta_p):
        p, s = params, opt.init(params)
        step = make_train_step(cfg, FULL_COMM, opt, meta)
        for i in range(5):
            p, s, m = step(p, s, graph, jnp.asarray(i), jax.random.key(i))
        outs.append((p, float(m["loss"])))
    (pd, lossd), (pp, lossp) = outs
    assert abs(lossd - lossp) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(pd),
                    jax.tree_util.tree_leaves(pp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=0)


def test_packed_varco_schedule_trains(setup):
    """A VARCO blockmask policy runs on the packed wire (recompiling only
    per kept-block map) and the transport charge tracks the wire width."""
    cfg, params, pg, graph = setup
    _, meta_p = _metas(pg, params)
    pol = varco(total_steps=8, slope=5, compressor="blockmask")
    opt = adamw(5e-3)
    step = make_train_step(cfg, pol, opt, meta_p)
    p, s = params, opt.init(params)
    losses = []
    for i in range(6):
        p, s, m = step(p, s, graph, jnp.asarray(i), jax.random.key(i))
        losses.append(float(m["loss"]))
        rate = float(m["rate"])
        widths = [meta_p.packed_width(f, rate)
                  for f in (cfg.in_dim, cfg.hidden, cfg.hidden)]
        expect = 2 * meta_p.halo_demand * 32.0 * sum(widths)
        np.testing.assert_allclose(float(m["transport_bits"]), expect,
                                   rtol=1e-6)
    assert losses[-1] < losses[0]
    accs = make_eval_step(cfg, meta_p)(p, graph)
    assert 0.0 <= float(accs["test"]) <= 1.0


# ---------------------------------------------------------------------------
# ledger: analytic vs transport
# ---------------------------------------------------------------------------


def test_transport_equals_analytic_at_rate1(setup):
    """Acceptance: transport_bits ≈ analytic_bits for packed at rate 1."""
    cfg, params, pg, graph = setup
    _, meta_p = _metas(pg, params)
    for f in (128, 256, 512):
        np.testing.assert_allclose(float(meta_p.transport_bits(f, 1.0)),
                                   float(meta_p.ledger_bits(f, 1.0)),
                                   rtol=1e-7)
    # and end-to-end through a train step's metrics
    opt = adamw(5e-3)
    step = make_train_step(cfg, FULL_COMM, opt, meta_p)
    _, _, m = step(params, opt.init(params), graph, jnp.asarray(0),
                   jax.random.key(0))
    np.testing.assert_allclose(float(m["transport_bits"]),
                               float(m["halo_bits"]), rtol=1e-6)


@pytest.mark.parametrize("rate", [2.0, 4.0, 16.0])
def test_packed_transport_within_block_quantised_bound(setup, rate):
    """Packed wire bytes ≤ (1/r + 1/(F/128)) × dense bytes (acceptance)."""
    cfg, params, pg, graph = setup
    meta_d, meta_p = _metas(pg, params)
    for f in (256, 512, 1024):
        dense = float(meta_d.transport_bits(f))        # ships full F always
        packed = float(meta_p.transport_bits(f, rate))
        bound = (1.0 / rate + 128.0 / f) * dense
        assert packed <= bound + 1e-6, (f, rate, packed, bound)
        assert packed < dense                          # strict shrink, F>128


def test_dense_wire_transport_is_rate_independent(setup):
    """The dense wire ships the masked buffer at full width — the honest
    transport number the packed wire exists to fix."""
    cfg, params, pg, graph = setup
    meta_d, _ = _metas(pg, params)
    assert float(meta_d.transport_bits(F, 1.0)) == \
        float(meta_d.transport_bits(F, 16.0))


def test_ledger_tracks_both_charges():
    led = CommLedger.zero().add_bits(jnp.float32(64.0),
                                     transport=jnp.float32(256.0))
    led = led.add_bits(jnp.float32(32.0))              # transport defaults
    assert float(led.bits) == 96.0
    assert float(led.transport) == 288.0
    assert float(led.floats) == 3.0


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------


def test_packed_requires_blockmask_compressor(setup):
    cfg, params, pg, graph = setup
    _, meta_p = _metas(pg, params)
    with pytest.raises(ValueError, match="blockmask"):
        make_train_step(cfg, fixed(4.0), adamw(1e-3), meta_p)


def test_unknown_wire_rejected(setup):
    cfg, params, pg, graph = setup
    with pytest.raises(ValueError, match="wire"):
        DistMeta.build(pg, params, wire="carrier-pigeon")


def test_blockmask_rejects_off_lane_width():
    with pytest.raises(ValueError, match="divisible"):
        get_compressor("blockmask")(jax.random.key(0),
                                    jnp.ones((4, 100)), jnp.asarray(2.0))


# ---------------------------------------------------------------------------
# shard_map backend (subprocess: needs 8 virtual devices)
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

PACKED_SHARD_EQUIV = """
import jax, jax.numpy as jnp
from repro.graph import tiny_graph, partition_graph
from repro.nn import GNNConfig, init_gnn
from repro.dist.gnn_parallel import (DistMeta, make_train_step,
                                     make_worker_mesh, shard_graph)
from repro.core import FULL_COMM, fixed
from repro.train.optim import adamw

g = tiny_graph(n=256, feat_dim=256)
cfg = GNNConfig(conv='sage', in_dim=256, hidden=128,
                out_dim=g.num_classes, layers=3)
params = init_gnn(jax.random.key(0), cfg)
pg = partition_graph(g, 8, scheme='random')
graph = pg.device_arrays()
meta = DistMeta.build(pg, params, wire='packed')
opt = adamw(1e-2)
mesh = make_worker_mesh(8)
gs = shard_graph(graph, mesh)

for rate in (1.0, 2.0, 4.0, 16.0):
    pol = FULL_COMM if rate == 1.0 else fixed(rate, compressor='blockmask')
    p_e, s_e = params, opt.init(params)
    step_e = make_train_step(cfg, pol, opt, meta)
    p_s, s_s = params, opt.init(params)
    step_s = make_train_step(cfg, pol, opt, meta, mesh=mesh)
    for i in range(4):
        p_e, s_e, m_e = step_e(p_e, s_e, graph, jnp.asarray(i),
                               jax.random.key(i))
        p_s, s_s, m_s = step_s(p_s, s_s, gs, jnp.asarray(i),
                               jax.random.key(i))
    d = max(float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(p_e), jax.tree.leaves(p_s)))
    assert d < 1e-5, (rate, d)
    assert abs(float(m_e['loss']) - float(m_s['loss'])) < 1e-5, rate
    assert abs(float(m_e['transport_bits']) -
               float(m_s['transport_bits'])) < 1.0, rate
print('PACKED_SHARD_OK')
"""


@pytest.mark.slow
def test_packed_shard_map_matches_emulated():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", PACKED_SHARD_EQUIV], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "PACKED_SHARD_OK" in out.stdout
