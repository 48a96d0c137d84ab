"""VARCO compression pack/unpack Pallas TPU kernels.

The paper's compression (Definition 1 + Appendix) communicates a random
subset of activation elements chosen by a shared PRNG.  Element-granular
gather/scatter is hostile to the TPU vector unit, so the TPU-native
realisation subsamples **128-lane feature blocks** (the VPU lane width):
the shared key selects ``K = (F/128)/r`` blocks; ``pack`` gathers them into
a dense ``[N, K·128]`` wire buffer and ``unpack`` scatters them back,
zero-filling dropped blocks (exactly the paper's decoder).  Block-granular
random subsetting satisfies Definition 1 with the same ε(r) for
exchangeable coordinates; see DESIGN.md §3.

Mechanics: the kept-block indices ride in scalar-prefetch memory (SMEM) so
the BlockSpec ``index_map`` can route HBM→VMEM DMAs directly — the gather
costs zero VPU work; it is pure DMA steering.  ``unpack`` iterates all
output blocks, copying from the packed buffer where the inverse map is
valid and zeroing otherwise (``inv`` also in SMEM).

These kernels are the TPU realisation of the runtime's **packed wire
format** (DESIGN.md §3.3): :func:`repro.core.collectives.packed_all_gather`
packs each worker's boundary block before the all-gather so only the
``[B, K·128]`` payload crosses the wire, and unpacks on receipt.  The
differentiable entry points are :func:`repro.kernels.ops.wire_pack` /
:func:`repro.kernels.ops.wire_unpack`, which dispatch to these Pallas
kernels on TPU and to the ``ref.py`` jnp oracles elsewhere (the CPU
fallback rule), with custom VJPs (pack and unpack are each other's
transpose).  Correctness vs ``ref.pack_reference`` / ``ref.unpack_reference``
is pinned by ``tests/test_kernels.py``; the runtime integration — packed vs
dense parity at every rate — by ``tests/test_packed_wire.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _pack_kernel(idx_ref, x_ref, out_ref):
    del idx_ref  # consumed by the index_map
    out_ref[...] = x_ref[...]


def varco_pack(x: jax.Array, block_idx: jax.Array, *, tile_n: int = 256,
               interpret: bool = False) -> jax.Array:
    """Gather kept lane-blocks: x [N, F], block_idx [K] -> [N, K*128]."""
    n, f = x.shape
    assert f % LANE == 0, f
    k = block_idx.shape[0]
    tn = min(tile_n, n)
    assert n % tn == 0, (n, tn)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // tn, k),
        in_specs=[
            pl.BlockSpec((tn, LANE), lambda i, j, idx: (i, idx[j])),
        ],
        out_specs=pl.BlockSpec((tn, LANE), lambda i, j, idx: (i, j)),
    )
    return pl.pallas_call(
        _pack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, k * LANE), x.dtype),
        interpret=interpret,
    )(block_idx, x)


def _lane_maps(m: int, width: int):
    """Lane → byte maps of the sub-byte layout for ``m`` lanes: each lane's
    byte column ``lane // vpb`` and bit offset ``(lane % vpb) * width``
    (``vpb = 8 / width``), as ``[1, m]`` int32 rows (shifts and masks:
    ``vpb`` is a power of two, and Mosaic has no vector integer divide)."""
    vpb = 8 // width
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    return lane >> (vpb.bit_length() - 1), (lane & (vpb - 1)) * width


def _bitpack_fields(levels: jax.Array, width: int, col0=0,
                    n_cols: int | None = None) -> jax.Array:
    """int levels ``[tn, m]`` -> byte values ``[tn, n_cols]`` f32, lane
    ``i``'s low ``width`` bits landing in byte column ``col0 + i // vpb``
    at bit offset ``(i % vpb) * width`` (``col0`` may be traced).

    The lane → byte compaction is a matmul against the placement matrix
    ``P[i, c] = 2^(bit offset of i)·[c == col0 + i // vpb]``: byte values
    stay below 256 and ``P`` holds powers of two, so the product is exact
    at any MXU precision.  (Mosaic lowers no strided lane slice, so the
    compaction cannot be a slice-and-OR.)
    """
    m = levels.shape[-1]
    n_cols = m * width // 8 if n_cols is None else n_cols
    col, shift = _lane_maps(m, width)
    u = levels.astype(jnp.int32) & (2 ** width - 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (m, n_cols), 1)
    place = jnp.where(cols == col.reshape(m, 1) + col0,
                      (1 << shift).reshape(m, 1).astype(jnp.float32), 0.0)
    return jnp.dot(u.astype(jnp.float32), place,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _bitunpack_fields(packed: jax.Array, width: int, col0=0,
                      m: int | None = None) -> jax.Array:
    """Inverse of :func:`_bitpack_fields`: bytes ``[tn, B]`` -> the ``m``
    sign-extended int32 levels stored from byte column ``col0`` on
    (``col0`` may be traced).  Each lane's byte is fetched by a one-hot
    matmul (exact: byte values below 256), then shifted and masked."""
    m = packed.shape[-1] * 8 // width if m is None else m
    col, shift = _lane_maps(m, width)
    rows = jax.lax.broadcasted_iota(jnp.int32, (packed.shape[-1], m), 0)
    pick = jnp.where(rows == col + col0, 1.0, 0.0)
    byte = jnp.dot(packed.astype(jnp.int32).astype(jnp.float32), pick,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    v = (byte >> shift) & (2 ** width - 1)
    return jnp.where(v >= 2 ** (width - 1), v - 2 ** width, v)


def _pack_quant_kernel(idx_ref, x_ref, out_ref, scale_ref, bytes_scr,
                       scale_scr, *, qmax, width, bpb):
    del idx_ref  # consumed by the index_map
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        bytes_scr[...] = jnp.zeros_like(bytes_scr)
        scale_scr[...] = jnp.zeros_like(scale_scr)

    xb = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    levels = jnp.clip(jnp.rint(xb / scale), -qmax, qmax)
    # kept block j owns byte columns [j*bpb, (j+1)*bpb) and scale column
    # j of the row tile's resident outputs
    bytes_scr[...] += _bitpack_fields(levels, width, col0=j * bpb,
                                      n_cols=bytes_scr.shape[-1])
    kcol = jax.lax.broadcasted_iota(jnp.int32, scale_scr.shape, 1)
    scale_scr[...] += jnp.where(kcol == j, scale, 0.0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        out_ref[...] = bytes_scr[...].astype(jnp.int32).astype(jnp.uint8)
        scale_ref[...] = scale_scr[...]


def varco_pack_quant(x: jax.Array, block_idx: jax.Array, *, width: int,
                     tile_n: int = 256, interpret: bool = False
                     ) -> tuple[jax.Array, jax.Array]:
    """Fused gather + low-bit quantise + bit-pack: one kernel launch.

    x [N, F], block_idx [K] -> (payload uint8 [N, K*128*width/8],
    scales f32 [N, K]).  Each kept lane-block is DMA-routed into VMEM
    exactly as in :func:`varco_pack`, and *in the same tile visit* the
    kernel computes the per-row block amax, the symmetric scale
    ``amax / qmax`` with ``qmax = 2^(width-1) - 1``, the rounded-clipped
    levels, AND the sub-byte bit-pack (``8/width`` lanes per byte,
    little-endian — ``width == 8`` stores bitwise the int8 lanes the
    pre-packing wire shipped) — the fp32 intermediate never reaches HBM.
    The payload row and the scales of a row tile stay resident in VMEM
    across the kept blocks (full-width blocks meet the (8, 128) tiling
    rule at any ``K``) and are stored once.  ``width`` ∈ {2, 4, 8};
    storage matches the ledger's ``LANE·width`` payload charge exactly.
    Oracle: :func:`repro.kernels.ref.pack_quant_reference`; decode with
    :func:`varco_unpack_quant` / ``ref.unpack_quant_reference``.
    """
    n, f = x.shape
    assert f % LANE == 0, f
    assert width in (2, 4, 8), width
    k = block_idx.shape[0]
    tn = min(tile_n, n)
    assert n % tn == 0, (n, tn)
    qmax = float(2 ** (width - 1) - 1)
    bpb = LANE * width // 8                 # payload bytes per lane-block

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // tn, k),
        in_specs=[
            pl.BlockSpec((tn, LANE), lambda i, j, idx: (i, idx[j])),
        ],
        out_specs=[
            pl.BlockSpec((tn, k * bpb), lambda i, j, idx: (i, 0)),
            pl.BlockSpec((tn, k), lambda i, j, idx: (i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((tn, k * bpb), jnp.float32),
                        pltpu.VMEM((tn, k), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_pack_quant_kernel, qmax=qmax, width=width,
                          bpb=bpb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, k * bpb), jnp.uint8),
                   jax.ShapeDtypeStruct((n, k), jnp.float32)],
        interpret=interpret,
    )(block_idx, x)


def _unpack_quant_kernel(inv_ref, packed_ref, scale_ref, out_ref, *, width,
                         bpb):
    j = pl.program_id(1)
    p = inv_ref[j]

    @pl.when(p >= 0)
    def _decode():
        levels = _bitunpack_fields(packed_ref[...], width, col0=p * bpb,
                                   m=LANE)
        sc = scale_ref[...]
        kcol = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        scale = jnp.sum(jnp.where(kcol == p, sc, 0.0), axis=-1,
                        keepdims=True)
        out_ref[...] = levels.astype(jnp.float32) * scale

    @pl.when(p < 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)


def varco_unpack_quant(payload: jax.Array, scales: jax.Array,
                       inv_idx: jax.Array, *, width: int, tile_n: int = 256,
                       interpret: bool = False) -> jax.Array:
    """Fused receive-side decode: bit-unpack + dequantise + scatter.

    payload uint8 [N, K*128*width/8], scales f32 [N, K], inv_idx [F/128]
    (packed block column of each output block, -1 if dropped) -> f32
    [N, F].  One launch does what unpack-then-dequant did in two: a row
    tile's whole payload row and scales sit in VMEM (full-width blocks,
    per the (8, 128) tiling rule), each live output block decodes its
    packed column — sign-extends the ``width``-bit fields and multiplies
    by the block scale — and dropped blocks are zero-filled (the paper's
    decoder).  Oracle: ``ref.unpack_quant_reference`` +
    ``ref.unpack_reference``.
    """
    n, kb = payload.shape
    assert width in (2, 4, 8), width
    bpb = LANE * width // 8
    assert kb % bpb == 0, (kb, bpb)
    k = scales.shape[-1]
    nf = inv_idx.shape[0]
    tn = min(tile_n, n)
    assert n % tn == 0, (n, tn)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // tn, nf),
        in_specs=[
            pl.BlockSpec((tn, kb), lambda i, j, inv: (i, 0)),
            pl.BlockSpec((tn, k), lambda i, j, inv: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tn, LANE), lambda i, j, inv: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_unpack_quant_kernel, width=width, bpb=bpb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, nf * LANE), jnp.float32),
        interpret=interpret,
    )(inv_idx, payload, scales)


def _unpack_kernel(inv_ref, packed_ref, out_ref):
    j = pl.program_id(1)
    live = inv_ref[j] >= 0

    @pl.when(live)
    def _copy():
        out_ref[...] = packed_ref[...]

    @pl.when(jnp.logical_not(live))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)


def varco_unpack(packed: jax.Array, inv_idx: jax.Array, *, tile_n: int = 256,
                 interpret: bool = False) -> jax.Array:
    """Scatter kept blocks back, zero-filling dropped ones.

    packed: [N, K*128]; inv_idx: [F/128] with inv_idx[b] = packed block
    column of output block b, or -1 if dropped.  Returns [N, F].
    """
    n, kf = packed.shape
    k = kf // LANE
    nf = inv_idx.shape[0]
    tn = min(tile_n, n)
    assert n % tn == 0, (n, tn)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // tn, nf),
        in_specs=[
            pl.BlockSpec((tn, LANE),
                         lambda i, j, inv: (i, jnp.maximum(inv[j], 0))),
        ],
        out_specs=pl.BlockSpec((tn, LANE), lambda i, j, inv: (i, j)),
    )
    return pl.pallas_call(
        _unpack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, nf * LANE), packed.dtype),
        interpret=interpret,
    )(inv_idx, packed)


def block_mask_indices(key: jax.Array, n_blocks: int, rate: float
                       ) -> tuple[jax.Array, jax.Array]:
    """Shared-PRNG selection of ``K = max(floor(n_blocks/rate), 1)`` kept
    lane-blocks (floor, clamped to one block — never zero payload).

    Returns (block_idx [K] sorted, inv_idx [n_blocks]).  Both ends derive
    these from the same key — no index metadata on the wire (paper App. A).
    """
    k = max(int(n_blocks / max(rate, 1.0)), 1)
    return block_mask_indices_k(key, n_blocks, k)


def block_mask_indices_k(key: jax.Array, n_blocks: int, k: int
                         ) -> tuple[jax.Array, jax.Array]:
    """:func:`block_mask_indices` with the kept-block count ``k`` given
    directly — the runtime quantises the (possibly annealing) rate to ``k``
    outside jit so the rate itself can stay a traced operand."""
    perm = jax.random.permutation(key, n_blocks)
    kept = jnp.sort(perm[:k])
    inv = jnp.full((n_blocks,), -1, jnp.int32)
    inv = inv.at[kept].set(jnp.arange(k, dtype=jnp.int32))
    return kept.astype(jnp.int32), inv


def block_mask_indices_pos(key: jax.Array, n_blocks: int, k: int
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`block_mask_indices_k` plus the permutation *positions*.

    Returns ``(kept [k], inv [n_blocks], pos [n_blocks])`` where ``pos[b]``
    is block ``b``'s slot in the shared permutation.  The kept sets at two
    counts ``k' <= k`` are *nested* under one key (both are "permutation
    slot < count"), so a buffer packed at ``k`` realises any smaller
    per-pair count ``k'`` by zeroing the packed columns whose block has
    ``pos >= k'`` — the per-pair rate-map mechanism of
    ``repro.dist.ratectl`` (DESIGN.md §3.6).  ``pos`` matches the dense
    ``blockmask`` compressor's keep rule bitwise.
    """
    perm = jax.random.permutation(key, n_blocks)
    pos = jnp.zeros((n_blocks,), jnp.int32).at[perm].set(
        jnp.arange(n_blocks, dtype=jnp.int32))
    kept = jnp.sort(perm[:k])
    inv = jnp.full((n_blocks,), -1, jnp.int32)
    inv = inv.at[kept].set(jnp.arange(k, dtype=jnp.int32))
    return kept.astype(jnp.int32), inv, pos


def worker_block_maps(key: jax.Array, q: int, n_blocks: int, k: int
                      ) -> tuple[jax.Array, jax.Array]:
    """Every worker's ``(kept, inv)`` pair for one exchange: worker ``i``
    draws from ``fold_in(key, i)``.  This is THE key-stream rule all wire
    paths share — emulated and shard_map, packed and p2p — so the
    bitwise-parity guarantees are structural, not four copies that must be
    kept in sync.  Returns ``(kept_all [Q, k], inv_all [Q, n_blocks])``.
    """
    keys = jax.vmap(jax.random.fold_in, (None, 0))(key, jnp.arange(q))
    return jax.vmap(lambda kk: block_mask_indices_k(kk, n_blocks, k))(keys)


def worker_block_maps_pos(key: jax.Array, q: int, n_blocks: int, k: int
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`worker_block_maps` plus every worker's permutation positions
    (:func:`block_mask_indices_pos`): ``(kept_all [Q, k], inv_all
    [Q, n_blocks], pos_all [Q, n_blocks])``.  Same ``fold_in(key, worker)``
    streams, so the scalar-rate wires and the per-pair rate-map wires draw
    identical kept sets for identical keys."""
    keys = jax.vmap(jax.random.fold_in, (None, 0))(key, jnp.arange(q))
    return jax.vmap(lambda kk: block_mask_indices_pos(kk, n_blocks, k))(keys)
