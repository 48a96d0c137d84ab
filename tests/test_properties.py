"""Property-based suites for the wire format and the rate-map masks.

Real randomised properties (hypothesis, or the conftest engine when
hypothesis is absent) over the invariants the runtime's bitwise-parity
guarantees rest on:

* pack/unpack round-trip at arbitrary ``(rate, Q, F)`` draws — the wire
  payload reconstructs exactly the dense ``blockmask`` round trip, kept
  blocks bit-for-bit, dropped blocks zero;
* pair-rate mask invariants — every pair's kept set is contained in the
  max-packed columns (`_packed_pair_k_for`'s static count), kept sets at
  different counts are nested under one key, and monotone rate maps give
  monotone kept counts (the mechanism behind the controllers' monotone
  non-increasing rates, Prop. 2);
* per-layer ``[L, Q, Q]`` tensors quantise to a static maximum that
  dominates every layer's every pair.
"""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.compression import get_compressor
from repro.dist.gnn_parallel import _pair_keep, _packed_pair_k_for
from repro.kernels.ops import wire_pack, wire_unpack
from repro.kernels.varco_pack import (LANE, block_mask_indices,
                                      block_mask_indices_k,
                                      block_mask_indices_pos,
                                      worker_block_maps)

RATE_CHOICES = [1.0, 1.5, 2.0, 3.0, 4.0, 8.0, 16.0, 32.0]


# ---------------------------------------------------------------------------
# pack/unpack round trip at arbitrary (rate, Q, F)
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(nb=st.integers(1, 8), rate=st.floats(1.0, 32.0),
       q=st.sampled_from([1, 2, 4]), n=st.integers(1, 12),
       seed=st.integers(0, 2 ** 16))
def test_pack_unpack_roundtrip(nb, rate, q, n, seed):
    """wire_pack → wire_unpack reconstructs the kept lane-blocks exactly
    and zero-fills the dropped ones, for every worker's key stream."""
    f = nb * LANE
    key = jax.random.key(seed)
    x = jax.random.normal(jax.random.fold_in(key, 999), (n, f), jnp.float32)
    k = max(int(nb / max(rate, 1.0)), 1)
    kept_all, inv_all = worker_block_maps(key, q, nb, k)
    for w in range(q):
        kept, inv = kept_all[w], inv_all[w]
        packed = wire_pack(x, kept, inv)
        assert packed.shape == (n, k * LANE)
        un = np.asarray(wire_unpack(packed, kept, inv))
        blocks = un.reshape(n, nb, LANE)
        x_blocks = np.asarray(x).reshape(n, nb, LANE)
        kept_set = set(np.asarray(kept).tolist())
        for b in range(nb):
            if b in kept_set:
                np.testing.assert_array_equal(blocks[:, b], x_blocks[:, b])
            else:
                assert not blocks[:, b].any()


@settings(max_examples=15, deadline=None)
@given(nb=st.integers(1, 8), rate=st.floats(1.0, 32.0),
       n=st.integers(1, 8), seed=st.integers(0, 2 ** 16))
def test_roundtrip_matches_blockmask_compressor(nb, rate, n, seed):
    """The packed wire's round trip equals the dense ``blockmask``
    compressor bitwise under the same key — the structural fact behind
    packed ≡ dense parity at every rate."""
    f = nb * LANE
    key = jax.random.key(seed)
    x = jax.random.normal(jax.random.fold_in(key, 1), (n, f), jnp.float32)
    kept, inv = block_mask_indices(key, nb, rate)
    rt = wire_unpack(wire_pack(x, kept, inv), kept, inv)
    dense, _ = get_compressor("blockmask")(key, x, jnp.asarray(rate))
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(dense))


# ---------------------------------------------------------------------------
# pair-rate mask invariants
# ---------------------------------------------------------------------------


def _rand_map(rng, shape):
    rm = rng.choice(RATE_CHOICES, size=shape).astype(np.float32)
    it = rm.reshape(-1, shape[-1], shape[-1])
    for sl in it:
        np.fill_diagonal(sl, 1.0)
    return rm


@settings(max_examples=20, deadline=None)
@given(nb=st.integers(1, 8), q=st.sampled_from([2, 3, 4]),
       seed=st.integers(0, 2 ** 16))
def test_pair_keep_within_max_packed_columns(nb, q, seed):
    """Every pair's kept count fits inside the static max-packed buffer:
    1 <= k_pair <= k_max, with k_max = the map's realised maximum."""
    rng = np.random.default_rng(seed)
    rm = _rand_map(rng, (q, q))
    k_true = np.maximum(np.floor(nb / rm), 1.0)
    off = ~np.eye(q, dtype=bool)
    k_max = int(k_true[off].max())
    k = np.asarray(_pair_keep(nb, jnp.asarray(rm), k_max))
    assert k.min() >= 1
    assert k[off].max() <= k_max
    np.testing.assert_array_equal(k[off], k_true[off].astype(np.int32))


@settings(max_examples=20, deadline=None)
@given(nb=st.integers(1, 8), q=st.sampled_from([2, 3, 4]),
       seed=st.integers(0, 2 ** 16), bump=st.sampled_from([1.5, 2.0, 4.0]))
def test_monotone_rate_maps_give_monotone_keep_counts(nb, q, seed, bump):
    """r1 <= r2 elementwise ⇒ kept counts k(r1) >= k(r2) elementwise —
    monotone non-increasing rates induce monotone non-decreasing kept
    sets, which is what keeps Prop. 2 applicable per pair and per layer."""
    rng = np.random.default_rng(seed)
    r1 = _rand_map(rng, (q, q))
    r2 = np.where(np.eye(q, dtype=bool), 1.0, r1 * bump).astype(np.float32)
    k_max = nb
    k1 = np.asarray(_pair_keep(nb, jnp.asarray(r1), k_max))
    k2 = np.asarray(_pair_keep(nb, jnp.asarray(r2), k_max))
    assert (k1 >= k2).all()


@settings(max_examples=20, deadline=None)
@given(nb=st.integers(1, 8), seed=st.integers(0, 2 ** 16))
def test_kept_sets_nested_under_one_key(nb, seed):
    """Kept sets at counts k' <= k are nested under one key (both are
    "permutation position < count"), and the positions match the kept
    selection — the carve-out mechanism of the per-pair/per-layer maps."""
    key = jax.random.key(seed)
    sets = []
    for k in range(1, nb + 1):
        kept, inv, pos = block_mask_indices_pos(key, nb, k)
        kept_k, _ = block_mask_indices_k(key, nb, k)
        np.testing.assert_array_equal(np.asarray(kept), np.asarray(kept_k))
        # pos-based rule reproduces the kept set exactly
        by_pos = np.nonzero(np.asarray(pos) < k)[0]
        np.testing.assert_array_equal(np.sort(np.asarray(kept)), by_pos)
        sets.append(set(np.asarray(kept).tolist()))
    for small, big in zip(sets[:-1], sets[1:]):
        assert small <= big


@settings(max_examples=15, deadline=None)
@given(q=st.sampled_from([2, 3, 4]), n_layers=st.sampled_from([1, 2, 3]),
       seed=st.integers(0, 2 ** 16))
def test_packed_pair_k_dominates_every_layer(q, n_layers, seed):
    """The static kept-block maximum of `_packed_pair_k_for` dominates
    every layer's every pair at every exchanged width — so one packed
    buffer per width serves the whole [L, Q, Q] tensor."""
    from repro.dist.gnn_parallel import DistMeta

    rng = np.random.default_rng(seed)
    shape = (n_layers, q, q) if n_layers > 1 else (q, q)
    rm = _rand_map(rng, shape)
    meta = DistMeta(q=q, part_size=8, halo_size=4, num_nodes=8 * q,
                    feat_dim=256, num_classes=4, halo_demand=q,
                    cross_edges=q, n_train=1, n_val=1, n_test=1,
                    layer_dims=(256, 512), wire="dense")
    kb = dict(_packed_pair_k_for(meta, rm))
    off = ~np.eye(q, dtype=bool)
    for nb, k_static in kb.items():
        k = np.maximum(np.floor(nb / rm.reshape(-1, q, q)), 1.0)
        assert k_static >= int(k[:, off].max())
        assert 1 <= k_static <= nb


# ---------------------------------------------------------------------------
# quantised wire codec (DESIGN.md §3.8)
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(width=st.sampled_from([2, 4, 8]), nb=st.integers(1, 6),
       rate=st.floats(1.0, 32.0), n=st.integers(1, 8),
       seed=st.integers(0, 2 ** 16))
def test_quant_dequant_error_within_analytic_bound(width, nb, rate, n, seed):
    """Quantise→dequantise of a packed payload stays within the advertised
    per-element bound ``amax_block / (2^(w−1) − 1)`` for arbitrary
    ``(width, rate, Q, F)`` draws (deterministic rounding is tighter:
    half that), and ``width ≥ 32`` is an exact passthrough."""
    from repro.kernels.ops import quant_dequant
    from repro.kernels.varco_pack import block_mask_indices

    f = nb * LANE
    key = jax.random.key(seed)
    # scale-diverse rows (blocks spanning orders of magnitude) so the
    # per-block scales are genuinely heterogeneous
    mag = 10.0 ** jax.random.uniform(jax.random.fold_in(key, 1), (n, 1),
                                     minval=-2.0, maxval=2.0)
    x = jax.random.normal(jax.random.fold_in(key, 2), (n, f)) * mag
    kept, inv = block_mask_indices(key, nb, rate)
    packed = wire_pack(x, kept, inv)                 # [n, k*LANE]
    k = packed.shape[1] // LANE
    dq = np.asarray(quant_dequant(packed, width))
    pb = np.asarray(packed).reshape(n, k, LANE)
    qmax = 2.0 ** (width - 1) - 1.0
    bound = np.abs(pb).max(-1) / qmax                # [n, k]
    err = np.abs(dq.reshape(n, k, LANE) - pb)
    assert np.all(err <= 0.5 * bound[..., None] + 1e-6 * (1 + bound[..., None]))
    # fp32 "width" is bit-exact passthrough
    np.testing.assert_array_equal(
        np.asarray(quant_dequant(packed, 32)), np.asarray(packed))


# ---------------------------------------------------------------------------
# out-of-core streaming pipeline ≡ in-memory (ISSUE 7)
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(n=st.integers(60, 220), q=st.sampled_from([2, 3, 4]),
       scheme=st.sampled_from(["random", "metis-like"]),
       chunk_nodes=st.integers(7, 97), chunk_edges=st.integers(40, 900),
       seed=st.integers(0, 5))
def test_stream_partition_equals_in_memory_any_chunking(n, q, scheme,
                                                        chunk_nodes,
                                                        chunk_edges, seed):
    """The chunked partitioner is an exact reduction: for ANY chunk
    granularity (dividing the edge count or not), the owner vector and
    the edge-cut statistics equal ``partition_graph``'s in-memory
    results bitwise."""
    import tempfile

    from repro.graph import (edge_cut_stats, stream_edge_cut,
                             stream_partition, tiny_graph,
                             write_graph_store)
    from repro.graph.partition import PARTITIONERS

    g = tiny_graph(n=n, seed=seed)
    with tempfile.TemporaryDirectory() as td:
        store = write_graph_store(g, td + "/store",
                                  chunk_nodes=chunk_nodes,
                                  chunk_edges=chunk_edges)
        owner_s = stream_partition(store, q, scheme=scheme, seed=seed)
        owner_m = PARTITIONERS[scheme](g, q, seed=seed)
        np.testing.assert_array_equal(owner_s, owner_m)
        assert stream_edge_cut(store, owner_s) == \
            edge_cut_stats(g, owner_m)


@settings(max_examples=6, deadline=None)
@given(n=st.integers(60, 200), q=st.sampled_from([2, 3, 4]),
       chunk_nodes=st.integers(9, 77), seed=st.integers(0, 4),
       scheme=st.sampled_from(["random", "metis-like"]))
def test_shard_roundtrip_bitwise_vs_in_memory(n, q, chunk_nodes, seed,
                                              scheme):
    """write shards → load → rebuild ``HaloSpec``: bitwise-exact against
    ``build_halo_spec``/``build_partitioned``/``attach_p2p`` on the
    in-memory graph — every stacked array, every scalar fact, and both
    the manifest-carried and the rebuilt spec."""
    import json as _json
    import tempfile

    from repro.dist.halo import (HaloSpec, build_halo_spec, ell_arrays,
                                 halo_arrays)
    from repro.graph import (build_partitioned, load_shards, tiny_graph,
                             write_graph_store, write_shards)
    from repro.graph.partition import PARTITIONERS

    g = tiny_graph(n=n, seed=seed)
    owner = PARTITIONERS[scheme](g, q, seed=seed)
    pg = build_partitioned(g, owner, q)
    spec = build_halo_spec(pg)
    with tempfile.TemporaryDirectory() as td:
        store = write_graph_store(g, td + "/store",
                                  chunk_nodes=chunk_nodes)
        ss = load_shards(write_shards(store, owner, td + "/shards"))
    # spec: manifest copy, json round trip, and rebuild from loaded arrays
    assert ss.halo_spec == spec
    assert HaloSpec.from_dict(
        _json.loads(_json.dumps(spec.to_dict()))) == spec
    assert build_halo_spec(ss) == spec
    # scalar facts
    for k in ("q", "part_size", "halo_size", "num_nodes", "feat_dim",
              "num_classes", "halo_demand", "cross_edges"):
        assert getattr(ss, k) == getattr(pg, k), k
    assert (ss.n_train, ss.n_val, ss.n_test) == \
        (int(g.train_mask.sum()), int(g.val_mask.sum()),
         int(g.test_mask.sum()))
    # every stacked runtime array, bitwise
    ref = {k: getattr(pg, k) for k in
           ("features", "labels", "train_mask", "val_mask", "test_mask",
            "node_valid", "local_dst", "local_src", "local_w",
            "local_w_iso", "remote_dst", "remote_src", "remote_w",
            "send_idx", "send_valid")}
    ref.update(halo_arrays(pg, spec))
    ref.update(ell_arrays(pg, spec))
    for k, v in ref.items():
        np.testing.assert_array_equal(ss.arrays[k], v, err_msg=k)


@settings(max_examples=10, deadline=None)
@given(width=st.sampled_from([2, 4, 8]), seed=st.integers(0, 2 ** 16))
def test_stochastic_rounding_unbiased(width, seed):
    """``floor(v + u)`` rounding is unbiased: the mean over M independent
    rounding keys approaches x — elementwise within 6σ of the rounding
    noise (std ≤ scale/2 per draw), and the pooled signed error within
    6σ of its own (much tighter) standard error."""
    from repro.kernels.ops import quant_dequant

    n, nb, m = 4, 2, 256
    f = nb * LANE
    key = jax.random.key(seed)
    x = jax.random.normal(jax.random.fold_in(key, 0), (n, f))
    keys = jax.random.split(jax.random.fold_in(key, 1), m)
    dq = jax.vmap(lambda k: quant_dequant(x, width, key=k))(keys)
    mean = np.asarray(jnp.mean(dq, axis=0)).reshape(n, nb, LANE)
    xb = np.asarray(x).reshape(n, nb, LANE)
    qmax = 2.0 ** (width - 1) - 1.0
    scale = np.maximum(np.abs(xb).max(-1), 1e-30) / qmax   # [n, nb]
    sigma = scale[..., None] * 0.5 / np.sqrt(m)
    assert np.all(np.abs(mean - xb) <= 6.0 * sigma + 1e-7)
    pooled = ((mean - xb) / scale[..., None]).mean()
    assert abs(pooled) <= 6.0 * 0.5 / np.sqrt(m * n * f)


# ---------------------------------------------------------------------------
# sub-byte bit-pack codec round trip (DESIGN.md §3.8 byte layout)
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(width=st.sampled_from([2, 4, 8]), n=st.integers(1, 6),
       m=st.integers(1, 300), seed=st.integers(0, 2 ** 16))
def test_bitpack_roundtrip_reference(width, n, m, seed):
    """``unpack_bits(pack_bits(x, w), w, m) == x`` over the FULL signed
    field range for every width, including tail lane counts ``m`` that
    don't divide ``8/w`` (zero-padded last byte), and the byte layout is
    the documented little-endian grouping."""
    from repro.kernels.ops import pack_bits, unpack_bits

    rng = np.random.default_rng(seed)
    lo, hi = -(2 ** (width - 1)), 2 ** (width - 1) - 1
    x = rng.integers(lo, hi + 1, size=(n, m)).astype(np.int8)
    packed = np.asarray(pack_bits(jnp.asarray(x), width))
    vpb = 8 // width
    assert packed.dtype == np.uint8
    assert packed.shape == (n, -(-m // vpb))
    un = np.asarray(unpack_bits(jnp.asarray(packed), width, m))
    np.testing.assert_array_equal(un, x)
    # documented layout: lane i -> byte i//vpb, bit offset (i%vpb)*w,
    # low-w bits of the two's complement
    i = int(rng.integers(0, m))
    field = (int(packed[0, i // vpb]) >> ((i % vpb) * width)) \
        & (2 ** width - 1)
    assert field == int(x[0, i]) & (2 ** width - 1)
    # tail lanes beyond m decode to the zero pad
    full = np.asarray(unpack_bits(jnp.asarray(packed), width))
    assert full.shape[-1] == packed.shape[-1] * vpb
    assert not full[:, m:].any()


@settings(max_examples=20, deadline=None)
@given(width=st.sampled_from([2, 4, 8]), n=st.integers(1, 8),
       k=st.integers(1, 4), seed=st.integers(0, 2 ** 16))
def test_bitpack_roundtrip_kernel_helpers(width, n, k, seed):
    """The in-kernel placement-matmul pack (`_bitpack_fields`) yields
    bitwise the reference bytes, and `_bitunpack_fields` inverts them —
    the fused ``varco_pack_quant`` / ``varco_unpack_quant`` codec path."""
    from repro.kernels.ref import pack_bits_reference
    from repro.kernels.varco_pack import _bitpack_fields, _bitunpack_fields

    rng = np.random.default_rng(seed)
    lo, hi = -(2 ** (width - 1)), 2 ** (width - 1) - 1
    x = rng.integers(lo, hi + 1, size=(n, k * LANE)).astype(np.int8)
    packed = np.asarray(_bitpack_fields(jnp.asarray(x), width))
    np.testing.assert_array_equal(
        packed, np.asarray(pack_bits_reference(jnp.asarray(x), width)))
    un = np.asarray(_bitunpack_fields(jnp.asarray(packed.astype(np.uint8)),
                                      width))
    np.testing.assert_array_equal(un, x)
