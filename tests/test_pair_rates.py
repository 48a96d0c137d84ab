"""Per-pair rate-map plumbing (DESIGN.md §3.6): wires + ledger + parity.

The ``[Q, Q]`` rate-map mechanism must be a pure refinement of the scalar
wires: a uniform map is bitwise the scalar path, a mixed map delivers
each ordered pair's rows at exactly the dense ``blockmask`` round trip of
that pair's own rate (nested kept sets under one shared permutation), the
ledger decomposes into per-pair charges that sum to the totals, and the
emulated and shard_map backends agree to 1e-6 at mixed rates drawn from
{1, 2, 4, 16} on both the packed and p2p wires.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from parity import build_setup, mixed_map, run_forward_parity

from repro.core import fixed
from repro.core.compression import get_compressor
from repro.dist.gnn_parallel import (DistMeta, _make_aggregate_emulated,
                                     _packed_k_for, _packed_pair_k_for,
                                     _pair_keep)
from repro.nn.gnn import gnn_forward

F = 512
Q = 4


@pytest.fixture(scope="module")
def setup():
    _, cfg, params, pg, graph = build_setup(Q, f=F, layers=2, n=256)
    return cfg, params, pg, graph


def _mixed_map(seed: int = 0) -> np.ndarray:
    return mixed_map(Q, seed)


def _agg(graph, meta, rm, key, pol=None):
    pol = pol or fixed(4.0, compressor="blockmask")
    kb = dict(_packed_pair_k_for(meta, rm))
    return _make_aggregate_emulated(graph, meta, pol, None,
                                    jnp.ones((), jnp.float32), key,
                                    packed_k=kb, rate_map=jnp.asarray(rm))


# ---------------------------------------------------------------------------
# scalar-path equivalence + per-pair blockmask semantics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wire", ["packed", "p2p"])
@pytest.mark.parametrize("rate", [1.0, 4.0])
def test_uniform_map_is_scalar_path(setup, wire, rate):
    """A constant rate map must reproduce the scalar wire bitwise (same
    keys, same kept sets, same ledger totals)."""
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire=wire)
    pol = fixed(rate, compressor="blockmask")
    kb = dict(_packed_k_for(meta, rate))
    agg_s = _make_aggregate_emulated(graph, meta, pol, None,
                                     jnp.asarray(rate), jax.random.key(3),
                                     packed_k=kb)
    rm = np.full((Q, Q), rate, np.float32)
    np.fill_diagonal(rm, 1.0)
    agg_p = _agg(graph, meta, rm, jax.random.key(3), pol=pol)
    ls, bs = gnn_forward(params, cfg, graph["features"], agg_s)
    lp, bp = gnn_forward(params, cfg, graph["features"], agg_p)
    assert float(jnp.abs(ls - lp).max()) == 0.0
    np.testing.assert_allclose(np.asarray(bs), np.asarray(bp[:2]), rtol=1e-6)


def test_p2p_pair_rows_match_per_pair_blockmask(setup):
    """Pair (i, j)'s delivered rows equal the dense ``blockmask`` round
    trip of sender j's boundary block at rate ``r[i, j]`` — the nested
    kept-set construction realises every pair's own rate exactly."""
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire="p2p")
    rm = _mixed_map(1)
    key = jax.random.key(11)
    agg = _agg(graph, meta, rm, key)
    x = graph["features"]
    out_pair, _ = agg(0, x)

    # reference: dense-wire aggregation where receiver i's halo block from
    # sender j is blockmask-compressed at rate r[i, j] under j's key stream
    comp = get_compressor("blockmask")
    k_call = jax.random.fold_in(key, 0)
    publish = jax.vmap(lambda xq, idx, v: xq[idx] * v[:, None])(
        x, graph["send_idx"], graph["send_valid"])
    p_sz, b_sz = meta.part_size, meta.halo_size
    outs = []
    for i in range(Q):
        halo_i = jnp.concatenate([
            comp(jax.random.fold_in(k_call, j), publish[j],
                 jnp.asarray(rm[i, j]))[0]
            for j in range(Q)], axis=0)                    # [Q*B, F]
        out = jnp.zeros((p_sz + 1, F), x.dtype)
        out = out.at[graph["local_dst"][i]].add(
            graph["local_w"][i][:, None] * x[i][graph["local_src"][i]])
        out = out.at[graph["remote_dst"][i]].add(
            graph["remote_w"][i][:, None] * halo_i[graph["remote_src"][i]])
        outs.append(out[:p_sz])
    ref = jnp.stack(outs)
    np.testing.assert_allclose(np.asarray(out_pair), np.asarray(ref),
                               rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# ledger decomposition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wire", ["packed", "p2p"])
def test_pair_ledger_decomposes(setup, wire):
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire=wire)
    rm = _mixed_map(2)
    agg = _agg(graph, meta, rm, jax.random.key(5))
    _, bits = gnn_forward(params, cfg, graph["features"], agg)
    bits = np.asarray(bits)
    assert bits.shape == (2 + 3 * Q * Q,)
    pair_t = bits[2:2 + Q * Q].reshape(Q, Q)
    # per-pair transports sum to the transport total, diagonal never charged
    np.testing.assert_allclose(pair_t.sum(), bits[1], rtol=1e-6)
    assert np.all(np.diag(pair_t) == 0.0)
    # analytic charge is the requested-rate point-to-point sum over calls
    rows = meta.pair_table().astype(np.float64)
    expect = sum(float((rows * w * 32.0 / rm).sum())
                 for w in (F, F))                    # two exchanges at F
    np.testing.assert_allclose(bits[0], expect, rtol=1e-6)


def test_p2p_pair_transport_charges_own_rate(setup):
    """On the p2p wire each pair ships its OWN kept columns: transport of
    pair (i, j) is rows[i, j] × k(r[i, j]) × 128 × 32 per exchange."""
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire="p2p")
    rm = _mixed_map(3)
    agg = _agg(graph, meta, rm, jax.random.key(5))
    _, bits = gnn_forward(params, cfg, graph["features"], agg)
    pair_t = np.asarray(bits[2:2 + Q * Q]).reshape(Q, Q)
    rows = meta.pair_table().astype(np.float64)
    nb = F // 128
    k = np.maximum(np.floor(nb / rm), 1.0)
    np.fill_diagonal(k, 0.0)
    expect = 2 * rows * k * 128 * 32.0              # two exchanges at F
    np.testing.assert_allclose(pair_t, expect, rtol=1e-6)


def test_packed_pair_transport_is_per_sender(setup):
    """The all-gather wire serves every receiver one payload, so sender
    j's realised kept count is max_i k[i, j] and every pair in column j
    is charged that width."""
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire="packed")
    rm = _mixed_map(4)
    agg = _agg(graph, meta, rm, jax.random.key(5))
    _, bits = gnn_forward(params, cfg, graph["features"], agg)
    pair_t = np.asarray(bits[2:2 + Q * Q]).reshape(Q, Q)
    rows = meta.pair_table().astype(np.float64)
    nb = F // 128
    k = np.maximum(np.floor(nb / rm), 1.0)
    np.fill_diagonal(k, 0.0)
    k_send = np.maximum(k.max(axis=0), 1.0)
    expect = 2 * rows * k_send[None, :] * 128 * 32.0
    np.testing.assert_allclose(pair_t, expect, rtol=1e-6)


def test_pair_err_positive_only_when_dropping(setup):
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire="p2p")
    # rate 1 everywhere → nothing dropped → zero per-pair error
    rm1 = np.ones((Q, Q), np.float32)
    agg = _agg(graph, meta, rm1, jax.random.key(5))
    _, bits = gnn_forward(params, cfg, graph["features"], agg)
    assert float(np.asarray(bits[2 + Q * Q:2 + 2 * Q * Q]).sum()) == 0.0
    rm = _mixed_map(5)
    agg = _agg(graph, meta, rm, jax.random.key(5))
    _, bits = gnn_forward(params, cfg, graph["features"], agg)
    err = np.asarray(bits[2 + Q * Q:2 + 2 * Q * Q]).reshape(Q, Q)
    assert err.sum() > 0.0
    # pairs at rate 1 drop nothing (nb/1 == nb kept)
    assert np.all(err[rm <= 1.0] == 0.0)


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------


def test_dense_wire_rejects_rate_map(setup):
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire="dense")
    with pytest.raises(ValueError, match="scalar"):
        _make_aggregate_emulated(graph, meta, fixed(2.0, "blockmask"), None,
                                 jnp.ones(()), jax.random.key(0),
                                 rate_map=jnp.ones((Q, Q)))


def test_pair_table_requires_built_meta(setup):
    cfg, params, pg, graph = setup
    meta = DistMeta.build(pg, params, wire="p2p")
    import dataclasses
    bare = dataclasses.replace(meta, pair_rows=())
    with pytest.raises(ValueError, match="pair_rows"):
        bare.pair_table()
    assert meta.pair_table().sum() == meta.halo_demand


def test_pair_keep_matches_blockmask_floor():
    rm = np.asarray([[1.0, 2.0], [3.0, 16.0]], np.float32)
    k = np.asarray(_pair_keep(4, jnp.asarray(rm), 4))
    np.testing.assert_array_equal(k, [[4, 2], [1, 1]])
    # quantiser agrees with the static host-side maximum
    meta_nb = [(4, int(k.max()))]
    assert meta_nb[0][1] == 4


def test_neighbor_exchange_pair_k_needs_n_keep():
    from repro.core.collectives import neighbor_exchange

    def run():
        def worker(x):
            return neighbor_exchange(x, jnp.zeros((1, 2), jnp.int32),
                                     jnp.ones((1, 2)), "w",
                                     pair_k=jnp.ones((2, 2), jnp.int32))[0]
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("w",))
        return jax.jit(shard_map(worker, mesh=mesh, in_specs=P("w"),
                                 out_specs=P("w"), check_vma=False))(
            jnp.zeros((1, 4, 256)))

    with pytest.raises(ValueError, match="n_keep"):
        run()


# ---------------------------------------------------------------------------
# emulated ≡ shard_map at mixed per-pair AND per-layer rates (shared
# harness of tests/parity.py; subprocess: 4 devices)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_pair_rates_emulated_matches_shard_map():
    run_forward_parity(Q, [
        {"wire": wire, "policy": "fixed:4", "map": mode, "seed": 0}
        for wire in ("p2p", "packed") for mode in ("pair", "layer")])


@pytest.mark.slow
def test_single_layer_tensor_shard_parity():
    """[1, Q, Q] tensors (per-layer controller on a 1-layer model) on the
    real collectives — regression for the rank-vs-L selection bug."""
    run_forward_parity(2, [
        {"wire": wire, "policy": "fixed:4", "map": "layer", "seed": 1}
        for wire in ("p2p", "packed")], layers=1)
