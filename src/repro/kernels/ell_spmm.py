"""ELLPACK SpMM Pallas TPU kernel — GNN neighbour aggregation.

The GNN hot spot is ``out[i] = Σ_k w[i,k] · x[nbr[i,k]]`` (eq. (2)'s ``S x``
with degree-padded ELL neighbour lists).  CSR row-gather is replaced by a
**source-chunked** formulation so arbitrary-size node sets stream through
VMEM:

  grid (node_tiles, feature_blocks, source_chunks); the kernel holds a
  ``[sc, bf]`` source-chunk slab of ``x`` in VMEM, builds the dense
  ``[tn, sc]`` weight tile ``A[t, s] = Σ_k w[t,k]·[nbr[t,k] − lo == s]``
  of the neighbours that fall inside the chunk (one iota compare per
  ``k``, so no ``[tn, K, sc]`` intermediate ever exists), and accumulates
  ``A @ x_chunk`` on the MXU in a VMEM scratch, writing out on the last
  chunk.  There is no data-dependent VMEM gather, which Mosaic cannot
  lower; the price is work quadratic in the node count (every node tile
  sweeps every source chunk).

HBM traffic is one pass over ``x`` per node tile.  Validated against
``ref.ell_spmm_reference`` in interpret mode over shape sweeps incl.
ragged/padded degrees.

The kernel is forward-only, and is the :func:`repro.kernels.ops.aggregate`
surface alone: no training path runs it.  The runtime's differentiable
entry point, :func:`repro.kernels.ops.ell_aggregate`, runs the same SpMM
on every backend as K row gathers of ``x`` with a per-slot weighted f32
accumulate, work linear in ``N_dst·K·F``, and its custom VJP runs the
transpose as the same gathers over the reversed neighbour lists
(``repro.dist.halo.build_reverse_ell``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ell_kernel(nbr_ref, w_ref, x_ref, out_ref, acc_scr, *,
                sc: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    nbr = nbr_ref[...]                       # [tn, K] int32 (global ids)
    w = w_ref[...].astype(jnp.float32)       # [tn, K]
    x = x_ref[...].astype(jnp.float32)       # [sc, bf] source chunk slab

    local = nbr - ci * sc                    # out-of-chunk ids match no s
    s_ids = jax.lax.broadcasted_iota(jnp.int32, (nbr.shape[0], sc), 1)
    a = jnp.zeros((nbr.shape[0], sc), jnp.float32)
    for k in range(nbr.shape[1]):            # static: K is a block dim
        a = a + jnp.where(s_ids == local[:, k:k + 1], w[:, k:k + 1], 0.0)
    acc_scr[...] += jnp.dot(a, x, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(ci == n_chunks - 1)
    def _finalize():
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


def ell_spmm(x: jax.Array, nbr: jax.Array, w: jax.Array, *,
             tile_n: int = 128, block_f: int = 128, src_chunk: int = 1024,
             interpret: bool = False) -> jax.Array:
    """out[i] = Σ_k w[i,k] x[nbr[i,k]].

    x: [N_src, F]; nbr: [N_dst, K] int32 (pad entries may point anywhere
    with w == 0); w: [N_dst, K].  Returns [N_dst, F].
    """
    n_src, f = x.shape
    n_dst, k = nbr.shape
    tn = min(tile_n, n_dst)
    bf = min(block_f, f)
    sc = min(src_chunk, n_src)
    assert n_dst % tn == 0 and f % bf == 0 and n_src % sc == 0
    n_chunks = n_src // sc

    kernel = functools.partial(_ell_kernel, sc=sc, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=(n_dst // tn, f // bf, n_chunks),
        in_specs=[
            pl.BlockSpec((tn, k), lambda i, j, c: (i, 0)),
            pl.BlockSpec((tn, k), lambda i, j, c: (i, 0)),
            pl.BlockSpec((sc, bf), lambda i, j, c: (c, j)),
        ],
        out_specs=pl.BlockSpec((tn, bf), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_dst, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((tn, bf), jnp.float32)],
        interpret=interpret,
    )(nbr, w, x)
