"""Pallas kernel vs ref.py oracle allclose, interpret mode, shape/dtype
sweeps (deliverable c)."""

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ell_spmm import ell_spmm
from repro.kernels.varco_pack import (block_mask_indices, varco_pack,
                                      varco_unpack)
from repro.kernels import ref

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,s,d", [
    (1, 2, 2, 128, 64),      # MHA
    (2, 4, 2, 256, 64),      # GQA 2:1
    (1, 8, 1, 128, 128),     # MQA
    (1, 2, 2, 384, 256),     # gemma-sized heads, ragged seq/block
])
def test_flash_matches_reference(b, h, kv, s, d, dtype):
    q = jnp.asarray(RNG.normal(0, 1, (b, h, s, d)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (b, kv, s, d)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (b, kv, s, d)), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    expect = ref.mha_reference(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [64, 128, 200])
def test_flash_sliding_window(window):
    q = jnp.asarray(RNG.normal(0, 1, (1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (1, 2, 256, 64)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True)
    expect = ref.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_noncausal():
    q = jnp.asarray(RNG.normal(0, 1, (1, 2, 128, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (1, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (1, 2, 128, 64)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    expect = ref.mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_chunked_sdpa_matches_dense():
    """The model's jnp flash path equals dense sdpa (transformer internals)."""
    from repro.models.transformer import chunked_sdpa
    from repro.models.layers import sdpa, _attn_mask
    b, s, h, kv, d = 2, 2048, 4, 2, 64
    q = jnp.asarray(RNG.normal(0, 1, (b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, s, kv, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, kv, d)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    out = chunked_sdpa(q, k, v, window=0)
    expect = sdpa(q, k, v, _attn_mask(pos, pos, 0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# varco pack / unpack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,f,rate", [(256, 1024, 4.0), (512, 512, 2.0),
                                      (128, 2048, 16.0), (256, 256, 1.0)])
def test_pack_unpack_roundtrip(n, f, rate, dtype):
    x = jnp.asarray(RNG.normal(0, 1, (n, f)), dtype)
    kept, inv = block_mask_indices(jax.random.key(3), f // 128, rate)
    packed = varco_pack(x, kept, interpret=True)
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(ref.pack_reference(x, kept)))
    xt = varco_unpack(packed, inv, interpret=True)
    np.testing.assert_array_equal(np.asarray(xt),
                                  np.asarray(ref.unpack_reference(packed,
                                                                  inv)))
    # round trip == block-mask multiply
    mask = np.zeros(f // 128, bool)
    mask[np.asarray(kept)] = True
    expect = np.asarray(x).reshape(n, f // 128, 128) * mask[None, :, None]
    np.testing.assert_array_equal(np.asarray(xt),
                                  expect.reshape(n, f).astype(expect.dtype))


@settings(max_examples=15, deadline=None)
@given(nb=st.integers(1, 32), rate=st.floats(1.0, 32.0),
       seed=st.integers(0, 100))
def test_block_mask_indices_properties(nb, rate, seed):
    kept, inv = jax.jit(block_mask_indices,
                        static_argnums=(1, 2))(jax.random.key(seed), nb, rate)
    kept = np.asarray(kept)
    inv = np.asarray(inv)
    k = max(int(nb / max(rate, 1.0)), 1)
    assert len(kept) == k
    assert len(np.unique(kept)) == k                     # no duplicates
    assert (np.sort(kept) == kept).all()
    # inverse map consistent
    for col, blk in enumerate(kept):
        assert inv[blk] == col
    assert (inv[np.setdiff1d(np.arange(nb), kept)] == -1).all()


def test_kernel_roundtrip_satisfies_definition1():
    """Kernel-path compression obeys the same Def.1 error bound."""
    x = jnp.asarray(RNG.normal(0, 1, (256, 1024)), jnp.float32)
    errs = []
    for i in range(8):
        xt, bits = ops.compress_roundtrip(jax.random.key(i), x, 4.0,
                                          interpret=True)
        errs.append(float(jnp.sum((xt - x) ** 2) / jnp.sum(x ** 2)))
        assert float(bits) == 256 * 256 * 32        # exactly 1/4 of blocks
    assert abs(np.mean(errs) - 0.75) < 0.15         # eps^2 = 1 - 1/r


# ---------------------------------------------------------------------------
# ell spmm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_src,n_dst,k,f,sc", [
    (2048, 256, 16, 256, 1024),
    (1024, 128, 8, 128, 256),     # multiple source chunks
    (512, 128, 32, 384, 512),
])
def test_ell_spmm_matches_reference(n_src, n_dst, k, f, sc, dtype):
    x = jnp.asarray(RNG.normal(0, 1, (n_src, f)), dtype)
    nbr = jnp.asarray(RNG.integers(0, n_src, (n_dst, k)), jnp.int32)
    w = jnp.asarray(RNG.normal(0, 1, (n_dst, k)), jnp.float32)
    out = ell_spmm(x, nbr, w, src_chunk=sc, interpret=True)
    expect = ref.ell_spmm_reference(x, nbr, w)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_ell_spmm_padded_degrees_zero_weight():
    """Pad entries (w == 0) contribute nothing wherever they point."""
    x = jnp.asarray(RNG.normal(0, 1, (256, 128)), jnp.float32)
    nbr = jnp.asarray(RNG.integers(0, 256, (128, 4)), jnp.int32)
    w = jnp.asarray(RNG.normal(0, 1, (128, 4)), jnp.float32)
    w = w.at[:, 2:].set(0.0)
    out = ell_spmm(x, nbr, w, interpret=True)
    expect = ref.ell_spmm_reference(x, nbr[:, :2], w[:, :2])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# ell_aggregate: the differentiable runtime path (custom VJP)
# ---------------------------------------------------------------------------


def _random_ell(n_dst, n_src, k, seed, rev_k=None):
    from repro.dist.halo import build_reverse_ell
    rng = np.random.default_rng(seed)
    nbr = rng.integers(0, n_src, (n_dst, k)).astype(np.int32)
    valid = rng.random((n_dst, k)) < 0.7
    w = np.where(valid, rng.normal(0, 1, (n_dst, k)), 0.0).astype(np.float32)
    rnbr, rslot = build_reverse_ell(nbr, valid, n_src, rev_k=rev_k)
    return (jnp.asarray(nbr), jnp.asarray(w), jnp.asarray(rnbr),
            jnp.asarray(rslot))


#: shapes off the old Pallas grid (128-row node tiles, 1024-row source
#: chunks) at the benchmark's ELL width and hidden width
OFF_GRID = (300, 1100, 14, 256)


@pytest.mark.parametrize("n_dst,n_src,k,f", [(64, 48, 6, 128),
                                             (128, 128, 3, 96),
                                             (33, 17, 9, 64),
                                             OFF_GRID])
def test_ell_aggregate_forward_matches_reference(n_dst, n_src, k, f):
    nbr, w, rnbr, rslot = _random_ell(n_dst, n_src, k, seed=f)
    x = jnp.asarray(RNG.normal(0, 1, (n_src, f)), jnp.float32)
    out = ops.ell_aggregate(x, nbr, w, rnbr, rslot)
    expect = ref.ell_spmm_reference(x, nbr, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_dst,n_src,k,f", [(64, 48, 6, 128),
                                             (33, 17, 9, 64),
                                             OFF_GRID])
def test_ell_aggregate_gradient_matches_reference(n_dst, n_src, k, f):
    """Custom VJP (reversed-list transpose) vs autodiff of the jnp oracle —
    both d/dx and d/dw, under an arbitrary downstream cotangent."""
    nbr, w, rnbr, rslot = _random_ell(n_dst, n_src, k, seed=7 * f)
    x = jnp.asarray(RNG.normal(0, 1, (n_src, f)), jnp.float32)
    cot = jnp.asarray(RNG.normal(0, 1, (n_dst, f)), jnp.float32)

    def loss_kernel(x_, w_):
        return jnp.sum(ops.ell_aggregate(x_, nbr, w_, rnbr, rslot) * cot)

    def loss_ref(x_, w_):
        return jnp.sum(ref.ell_spmm_reference(x_, nbr, w_) * cot)

    gx, gw = jax.grad(loss_kernel, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r),
                               rtol=1e-5, atol=1e-5)


def test_ell_aggregate_gradient_under_vmap():
    """The runtime vmaps ell_aggregate over partitions — gradients must
    batch identically to the per-slice VJP."""
    q, n, k, f = 3, 32, 4, 64
    # common reverse width so the per-partition lists stack (as ell_arrays
    # pads in the runtime); n*k bounds any source's reverse degree
    packs = [_random_ell(n, n, k, seed=i, rev_k=n * k // 2) for i in range(q)]
    nbr = jnp.stack([p[0] for p in packs])
    w = jnp.stack([p[1] for p in packs])
    rnbr = jnp.stack([p[2] for p in packs])
    rslot = jnp.stack([p[3] for p in packs])
    x = jnp.asarray(RNG.normal(0, 1, (q, n, f)), jnp.float32)

    def loss_v(x_):
        out = jax.vmap(ops.ell_aggregate)(x_, nbr, w, rnbr, rslot)
        return jnp.sum(out ** 2)

    g_v = jax.grad(loss_v)(x)
    for p in range(q):
        def loss_1(x_):
            return jnp.sum(ops.ell_aggregate(x_, nbr[p], w[p], rnbr[p],
                                             rslot[p]) ** 2)
        g_1 = jax.grad(loss_1)(x[p])
        np.testing.assert_allclose(np.asarray(g_v[p]), np.asarray(g_1),
                                   rtol=1e-6, atol=1e-6)


def test_ell_aggregate_transpose_is_exact():
    """The VJP's x-cotangent is the reversed-list SpMM: applying forward
    then transpose equals the dense operator A^T A x."""
    nbr, w, rnbr, rslot = _random_ell(24, 18, 5, seed=11)
    n_src, f = 18, 32
    x = jnp.asarray(RNG.normal(0, 1, (n_src, f)), jnp.float32)
    y, vjp = jax.vjp(lambda x_: ops.ell_aggregate(x_, nbr, w, rnbr, rslot), x)
    (xt,) = vjp(y)
    a = np.zeros((24, n_src), np.float32)
    for i in range(24):
        for kk in range(5):
            a[i, int(nbr[i, kk])] += float(w[i, kk])
    np.testing.assert_allclose(np.asarray(xt), a.T @ a @ np.asarray(x),
                               rtol=1e-4, atol=1e-4)


def _jaxpr_vars(jaxpr):
    """(primitive name, output avals) of every equation of ``jaxpr`` and of
    the jaxprs nested in its equations' parameters."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, [v.aval for v in eqn.outvars]
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _jaxpr_vars(sub)


def _largest_and_kernels(fn, *args):
    found = list(_jaxpr_vars(jax.make_jaxpr(fn)(*args).jaxpr))
    largest = max(int(np.prod(a.shape)) for _, avals in found for a in avals
                  if hasattr(a, "shape"))
    return largest, sum(name == "pallas_call" for name, _ in found)


def test_ell_aggregate_gathers_rows_without_kernel_or_nkf(monkeypatch):
    """The training path's local aggregation, forward and VJP, holds no
    Pallas call and no intermediate as large as the ``[N_dst, K, F]``
    gather: nothing larger than the largest ``[N, F]`` operand.  The
    backend is steered to ``"tpu"`` so that a TPU-only branch would show."""
    n_dst, n_src, k, f = OFF_GRID
    nbr, w, rnbr, rslot = _random_ell(n_dst, n_src, k, seed=3)
    x = jnp.asarray(RNG.normal(0, 1, (n_src, f)), jnp.float32)
    g = jnp.asarray(RNG.normal(0, 1, (n_dst, f)), jnp.float32)
    bound = max(n_dst, n_src) * f

    # the checks can see what they guard against
    largest, _ = _largest_and_kernels(ref.ell_spmm_reference, x, nbr, w)
    assert largest >= n_dst * k * f
    _, kernels = _largest_and_kernels(
        lambda *a: ell_spmm(*a, interpret=False), x[:1024], nbr[:256], w[:256])
    assert kernels == 1

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def fwd(x_, w_):
        return ops.ell_aggregate(x_, nbr, w_, rnbr, rslot)

    def bwd(x_, w_, g_):
        return jax.vjp(fwd, x_, w_)[1](g_)

    for fn, args in ((fwd, (x, w)), (bwd, (x, w, g))):
        largest, kernels = _largest_and_kernels(fn, *args)
        assert kernels == 0, fn.__name__
        assert largest <= bound, (fn.__name__, largest, bound)


# ---------------------------------------------------------------------------
# ssd chunked scan vs sequential oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,chunk", [(64, 16), (128, 32), (96, 96)])
@pytest.mark.parametrize("g", [1, 2])
def test_ssd_chunked_matches_sequential(t, chunk, g):
    from repro.models.mamba2 import ssd_chunked
    b, h, p, n = 2, 4, 16, 8
    x = jnp.asarray(RNG.normal(0, 1, (b, t, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, t, h)), jnp.float32)
    a_log = jnp.asarray(RNG.uniform(-1, 1, (h,)), jnp.float32)
    bb = jnp.asarray(RNG.normal(0, 1, (b, t, g, n)), jnp.float32)
    cc = jnp.asarray(RNG.normal(0, 1, (b, t, g, n)), jnp.float32)
    d = jnp.asarray(RNG.normal(0, 1, (h,)), jnp.float32)
    y1, _ = ssd_chunked(x, dt, a_log, bb, cc, d, chunk=chunk)
    y2 = ref.ssd_reference(x, dt, a_log, bb, cc, d)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# ssd_chunk Pallas kernel (intra-chunk quadratic form) vs jnp oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q,h,p,n", [(64, 4, 32, 16), (128, 2, 64, 128),
                                     (32, 8, 16, 32)])
def test_ssd_chunk_kernel_matches_oracle(q, h, p, n):
    from repro.kernels.ssd_chunk import ssd_chunk
    b_, nc = 2, 3
    x = jnp.asarray(RNG.normal(0, 1, (b_, nc, q, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b_, nc, q, h)), jnp.float32)
    a = -jnp.exp(jnp.asarray(RNG.uniform(-1, 1, (h,)), jnp.float32))
    cum = jnp.cumsum(dt * a, axis=2)
    bb = jnp.asarray(RNG.normal(0, 1, (b_, nc, q, h, n)), jnp.float32)
    cc = jnp.asarray(RNG.normal(0, 1, (b_, nc, q, h, n)), jnp.float32)
    y, s = ssd_chunk(x, dt, cum, bb, cc, interpret=True)

    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]
    qi = np.arange(q)
    causal = jnp.asarray(qi[:, None] >= qi[None, :])
    decay = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    scores = jnp.einsum("bnqhk,bnshk->bnqsh", cc, bb)
    m = scores * decay * dt[:, :, None, :, :]
    y_ref = jnp.einsum("bnqsh,bnshp->bnqhp", m, x)
    d2e = jnp.exp(cum[:, :, -1:, :] - cum)
    s_ref = jnp.einsum("bnqh,bnqhk,bnqhp->bnhpk", d2e * dt, bb, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=2e-5,
                               atol=2e-5)
