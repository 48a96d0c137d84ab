"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU so the same call sites run on this
CPU container (kernel bodies execute in Python) and compile to real Mosaic
kernels on TPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .ell_spmm import ell_spmm
from .flash_attention import flash_attention
from .varco_pack import (LANE, block_mask_indices, varco_pack,
                         varco_pack_quant, varco_unpack, varco_unpack_quant)

#: wire bit-widths the quantised codecs speak — 32 is the fp32
#: passthrough, the rest are symmetric per-lane-block int formats
#: (qmax = 2^(w-1) - 1) bit-packed to true sub-byte storage: 8/w lanes
#: per byte, so the buffers carry exactly the ledger's w bits per lane.
WIRE_WIDTHS = (2, 4, 8, 32)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def mha(q, k, v, *, causal: bool = True, window: int = 0,
        interpret: bool | None = None):
    """Flash attention. q [B,H,S,D], k/v [B,KV,S,D]."""
    it = _default_interpret() if interpret is None else interpret
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=it)


@partial(jax.jit, static_argnames=("interpret",))
def compress_pack(x, block_idx, *, interpret: bool | None = None):
    it = _default_interpret() if interpret is None else interpret
    return varco_pack(x, block_idx, interpret=it)


@partial(jax.jit, static_argnames=("interpret",))
def compress_unpack(packed, inv_idx, *, interpret: bool | None = None):
    it = _default_interpret() if interpret is None else interpret
    return varco_unpack(packed, inv_idx, interpret=it)


@partial(jax.jit, static_argnames=("rate", "n_blocks"))
def compression_indices(key, n_blocks: int, rate: float):
    return block_mask_indices(key, n_blocks, rate)


def compress_roundtrip(key, x, rate: float, *, interpret: bool | None = None):
    """Full VARCO compress→wire→decompress round trip via the kernels."""
    n_blocks = x.shape[-1] // 128
    kept, inv = block_mask_indices(key, n_blocks, rate)
    packed = compress_pack(x, kept, interpret=interpret)
    wire_bits = packed.size * jnp.finfo(packed.dtype).bits
    return compress_unpack(packed, inv, interpret=interpret), wire_bits


# ---------------------------------------------------------------------------
# Differentiable wire ops (the packed halo-exchange payload path)
# ---------------------------------------------------------------------------
#
# ``wire_pack`` / ``wire_unpack`` are what the distributed runtime puts on
# the wire (DESIGN.md §3.3): Pallas kernels on TPU, the jnp ``ref`` oracles
# on every other backend — interpret-mode Pallas executes kernel bodies in
# Python, far too slow for a train loop, while the oracles are ordinary XLA
# gathers.  Gradients flow through the wire (Algorithm 1 back-propagates
# across machines), so both ops carry custom VJPs: pack and unpack are each
# other's transpose under the same (kept, inv) index pair.


def _padded_rows(n: int, tile: int = 256) -> int:
    """Row count the Pallas kernels accept: their ``tile_n`` grid needs
    ``n % min(tile, n) == 0``, but the runtime feeds arbitrary boundary
    counts (B = halo_size).  Pad small inputs to the f32 sublane (8), large
    ones to a whole tile."""
    if n <= tile:
        return -(-n // 8) * 8
    return -(-n // tile) * tile


def _pad_call(kernel, x, idx):
    n = x.shape[0]
    pad = _padded_rows(n) - n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = kernel(x, idx)
    return out[:n] if pad else out


def _pack_impl(x, kept):
    if jax.default_backend() == "tpu":
        return _pad_call(varco_pack, x, kept)
    return ref.pack_reference(x, kept)


def _unpack_impl(packed, inv):
    if jax.default_backend() == "tpu":
        return _pad_call(varco_unpack, packed, inv)
    return ref.unpack_reference(packed, inv)


@jax.custom_vjp
def wire_pack(x, kept, inv):
    """Gather kept lane-blocks for the wire: ``[N, F] -> [N, K*128]``.

    ``kept``/``inv`` must be the matched pair from
    :func:`repro.kernels.varco_pack.block_mask_indices`; ``inv`` is carried
    for the backward scatter.
    """
    del inv
    return _pack_impl(x, kept)


def _wire_pack_fwd(x, kept, inv):
    return _pack_impl(x, kept), (kept, inv)


def _wire_pack_bwd(res, g):
    _, inv = res
    return _unpack_impl(g, inv), None, None


wire_pack.defvjp(_wire_pack_fwd, _wire_pack_bwd)


@jax.custom_vjp
def wire_unpack(packed, kept, inv):
    """Scatter a received wire payload back: ``[N, K*128] -> [N, F]``,
    zero-filling dropped blocks (the paper's decoder)."""
    del kept
    return _unpack_impl(packed, inv)


def _wire_unpack_fwd(packed, kept, inv):
    return _unpack_impl(packed, inv), (kept, inv)


def _wire_unpack_bwd(res, g):
    kept, _ = res
    return _pack_impl(g, kept), None, None


wire_unpack.defvjp(_wire_unpack_fwd, _wire_unpack_bwd)


# ---------------------------------------------------------------------------
# Quantised wire codecs (DESIGN.md §3.8)
# ---------------------------------------------------------------------------
#
# ``quant_dequant`` is the value-level model of the low-bit wire: what a
# receiver reconstructs from an int-``width`` payload plus the per-block
# fp32 scales.  The *width* operand may be a traced array (per-pair
# widths change every step under the controllers), which works because
# the arithmetic — qmax = 2^(w-1)-1, scale = amax/qmax, round, clip —
# is ordinary elementwise math; only the storage dtype needs a static
# width, and that lives in the fused Pallas kernel
# (``varco_pack_quant``) / ``pack_quant`` below.


def quant_levels(x, width, *, key=None):
    """Per-lane-block symmetric quantisation *levels* plus scales.

    ``x [..., nb*LANE]`` -> ``(levels int8 [..., nb*LANE], scales f32
    [..., nb])``.  ``width`` may be a traced scalar or array
    broadcastable against the per-block scale array (per-pair widths
    change every step under the controllers); the *storage* stays int8
    here — :func:`pack_bits` squeezes the levels to true sub-byte bytes
    at the step's static storage width.  Deterministic round-to-nearest
    by default; pass ``key`` for stochastic rounding ``floor(v + u)``
    (same uniform stream :func:`quant_dequant` draws, so the two agree
    bitwise).  ``width >= 32`` yields wrapped garbage levels — callers
    on the fp32 passthrough discard them (as :func:`quant_dequant`'s
    ``where`` does).
    """
    lead = x.shape[:-1]
    nb = x.shape[-1] // LANE
    xb = x.reshape(*lead, nb, LANE)
    w = jnp.asarray(width, jnp.float32)
    qmax = 2.0 ** (w - 1.0) - 1.0
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    v = xb / scale[..., None]
    if key is None:
        qv = jnp.rint(v)
    else:
        qv = jnp.floor(v + jax.random.uniform(key, xb.shape))
    qv = jnp.clip(qv, -qmax[..., None], qmax[..., None])
    return qv.astype(jnp.int8).reshape(x.shape), scale


def quant_dequant(x, width, *, key=None):
    """Symmetric per-lane-block quantise→dequantise at ``width`` bits.

    ``x [..., nb*LANE]``; ``width`` — scalar or array broadcastable
    against the per-block scale array ``[..., nb]`` (e.g. per-pair
    widths ``w[:, :, None, None]`` against hops ``[Q, D, H, nb]``).
    ``width >= 32`` is an exact fp32 passthrough.  Deterministic
    round-to-nearest by default (the parity-checked wire behaviour,
    identical on both backends); pass ``key`` for stochastic rounding
    ``floor(v + u)``, ``u ~ U[0, 1)`` — unbiased in expectation.
    Per-element error ≤ ``amax_block / (2^(width-1) - 1)``.

    Built on :func:`quant_levels` — for sub-32 widths the int8 levels
    round-trip exactly (|level| ≤ 127), so the decode here is bitwise
    what a receiver reconstructs from the bit-packed wire bytes.
    """
    lead = x.shape[:-1]
    nb = x.shape[-1] // LANE
    xb = x.reshape(*lead, nb, LANE)
    w = jnp.asarray(width, jnp.float32)
    levels, scale = quant_levels(x, width, key=key)
    lb = levels.astype(jnp.float32).reshape(*lead, nb, LANE)
    dq = lb * scale[..., None]
    out = jnp.where(jnp.broadcast_to(w >= 32.0, scale.shape)[..., None],
                    xb, dq)
    return out.reshape(x.shape)


def pack_bits(levels, width: int):
    """Bit-pack int-``width`` levels to bytes: ``[..., M] -> [...,
    ceil(M·width/8)]`` uint8, ``8/width`` lanes per byte little-endian
    (``width == 8`` is the identity reinterpret).  jnp on every backend
    — the fused Pallas kernels pack in-register (``varco_pack_quant``);
    this is the standalone codec the transport layers and tests use."""
    return ref.pack_bits_reference(levels, width)


def unpack_bits(packed, width: int, m: int | None = None):
    """Inverse of :func:`pack_bits`: sign-extend each ``width``-bit
    field back to int8 levels (``m`` trims tail-byte zero-pad lanes)."""
    return ref.unpack_bits_reference(packed, width, m)


def dequant_bits(payload, scales, width: int):
    """Value-level decode of a sub-byte wire buffer: payload uint8
    ``[..., K·LANE·width/8]`` × scales f32 ``[..., K]`` -> f32
    ``[..., K·LANE]``.  Bitwise the ``levels · scale`` dequantise of
    :func:`quant_dequant` — what every receiver reconstructs from the
    bytes that actually crossed the wire."""
    k = scales.shape[-1]
    levels = ref.unpack_bits_reference(payload, width, k * LANE)
    lb = levels.astype(jnp.float32).reshape(*scales.shape, LANE)
    return (lb * scales[..., None]).reshape(*payload.shape[:-1], k * LANE)


def wire_quant(x, width, *, key=None):
    """Straight-through :func:`quant_dequant`: the forward sees the
    quantised wire values, the backward passes gradients through
    unchanged (the STE the ratectl error-feedback loop assumes)."""
    return x + jax.lax.stop_gradient(quant_dequant(x, width, key=key) - x)


#: fold_in salt separating the stochastic-rounding key stream from the
#: mask-selection streams that share the per-exchange key (DESIGN.md §3.8)
ROUND_SALT = 0x5EED


def default_wire_rounding() -> str:
    """Default rounding mode of the quantised wire on this backend:
    ``"stochastic"`` on TPU (unbiased ``floor(v + u)`` — the hardware
    target, where the paper's convergence argument wants an unbiased
    codec), ``"rint"`` elsewhere (deterministic round-to-nearest — the
    parity-checked CPU behaviour every golden trace is pinned under).
    Callers may always opt into either mode explicitly; this is only the
    ``rounding=None`` resolution used by ``make_auto_train_step``."""
    return "stochastic" if jax.default_backend() == "tpu" else "rint"


def round_key(key, sender, hop=None):
    """Per-(pair) stochastic-rounding key schedule: the shared
    per-exchange key (already ``fold_in(step key, call)``) is salted away
    from the mask streams, then folded with the *sender* index and — on
    the p2p wire — the ring-hop index, so every ordered pair draws its
    own uniforms and the emulated backend (vmapping over senders) and the
    shard_map backend (each worker its own ``sender``) consume identical
    streams.  The (seed, step, pair) derivation: seed and step live in
    the exchange key, the pair in the folds here."""
    k = jax.random.fold_in(jax.random.fold_in(key, ROUND_SALT), sender)
    return k if hop is None else jax.random.fold_in(k, hop)


def per_block_wire_bits(width):
    """On-wire bits of ONE kept lane-block per row at ``width``: the
    ``LANE·width`` payload plus the fp32 scale — the accounting
    convention the int8 dense compressor established (scales charged
    fully).  ``width >= 32`` means fp32 on the wire: no scale travels,
    so the charge stays exactly ``LANE·32`` and pre-quantisation ledgers
    are reproduced bit-for-bit.  ``width`` may be a traced array."""
    w = jnp.asarray(width, jnp.float32)
    return jnp.where(w >= 32.0, LANE * 32.0, LANE * w + 32.0)


def _pack_quant_impl(x, kept, width: int):
    if jax.default_backend() == "tpu":
        n = x.shape[0]
        pad = _padded_rows(n) - n
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        packed, scales = varco_pack_quant(x, kept, width=width)
        return (packed[:n], scales[:n]) if pad else (packed, scales)
    return ref.pack_quant_reference(x, kept, width)


@partial(jax.jit, static_argnames=("width", "interpret"))
def pack_quant(x, kept, *, width: int, interpret: bool | None = None):
    """Fused pack+quantise+bit-pack entry point: ``[N, F] -> (payload
    uint8 [N, K*128*width/8], scales f32 [N, K])`` in one kernel launch
    (Pallas on TPU, the ``ref`` oracle elsewhere).  The payload carries
    the ledger's exact ``LANE·width`` bits per kept block — ``8/width``
    lanes per byte, ``width == 8`` bitwise the former int8 storage.
    Decode with :func:`unpack_quant` (fused) or
    :func:`dequant_bits` (+ ``wire_unpack`` for the scatter)."""
    if interpret is not None and interpret:
        n = x.shape[0]
        pad = _padded_rows(n) - n
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        packed, scales = varco_pack_quant(x, kept, width=width,
                                          interpret=True)
        return (packed[:n], scales[:n]) if pad else (packed, scales)
    return _pack_quant_impl(x, kept, width)


def _unpack_quant_impl(payload, scales, inv, width: int):
    if jax.default_backend() == "tpu":
        n = payload.shape[0]
        pad = _padded_rows(n) - n
        if pad:
            payload = jnp.pad(payload, ((0, pad), (0, 0)))
            scales = jnp.pad(scales, ((0, pad), (0, 0)))
        out = varco_unpack_quant(payload, scales, inv, width=width)
        return out[:n] if pad else out
    return ref.unpack_reference(
        ref.unpack_quant_reference(payload, scales, width), inv)


@partial(jax.jit, static_argnames=("width", "interpret"))
def unpack_quant(payload, scales, inv, *, width: int,
                 interpret: bool | None = None):
    """Fused receive-side decode: bit-unpack + dequantise + scatter in
    one launch — ``(payload uint8 [N, K*128*width/8], scales f32
    [N, K], inv [F/128]) -> f32 [N, F]`` with dropped blocks
    zero-filled (Pallas ``varco_unpack_quant`` on TPU, the ``ref``
    oracles elsewhere)."""
    if interpret is not None and interpret:
        n = payload.shape[0]
        pad = _padded_rows(n) - n
        if pad:
            payload = jnp.pad(payload, ((0, pad), (0, 0)))
            scales = jnp.pad(scales, ((0, pad), (0, 0)))
        out = varco_unpack_quant(payload, scales, inv, width=width,
                                 interpret=True)
        return out[:n] if pad else out
    return _unpack_quant_impl(payload, scales, inv, width)


@partial(jax.jit, static_argnames=("interpret",))
def aggregate(x, nbr, w, *, interpret: bool | None = None):
    """Forward-only ELL neighbour aggregation (kernel correctness surface).
    The runtime's differentiable entry point is :func:`ell_aggregate`."""
    it = _default_interpret() if interpret is None else interpret
    return ell_spmm(x, nbr, w, interpret=it)


# ---------------------------------------------------------------------------
# Differentiable ELL aggregation (the p2p wire's local-edge hot path)
# ---------------------------------------------------------------------------
#
# ``ell_aggregate`` is what ``repro.dist.gnn_parallel`` runs over each
# partition's local edges on the p2p wire, on every backend alike: K row
# gathers of ``x``, one per ELL slot, each weighted and accumulated in f32
# (``_ell_gather``).  Its work is linear in ``N_dst·K·F``; the Pallas
# ``ell_spmm`` kernel sweeps every source chunk for every node tile, work
# quadratic in the node count, and stays only as the forward-only
# :func:`aggregate` surface.  The custom VJP keeps gradients on the same
# gathers: the transpose of an ELL SpMM is the ELL SpMM over the *reversed*
# neighbour lists (``repro.dist.halo.build_reverse_ell``), whose weights
# are gathered from the forward weights via the ``rslot`` flat map.


def _ell_gather(x, nbr, w):
    """ELL SpMM as K row gathers with a per-slot weighted f32 accumulate,
    slots summed in ascending ``k`` like ``ref.ell_spmm_reference``'s
    einsum, without its ``[N, K, F]`` gather.  One loop iteration per
    slot: unrolled slots keep their gathered rows live together, which
    raises the step's peak device memory."""
    def slot(k, acc):
        return acc + w[:, k].astype(jnp.float32)[:, None] * \
            x[nbr[:, k]].astype(jnp.float32)

    acc = jnp.zeros((nbr.shape[0], x.shape[1]), jnp.float32)
    return jax.lax.fori_loop(0, nbr.shape[1], slot, acc).astype(x.dtype)


@jax.custom_vjp
def ell_aggregate(x, nbr, w, rnbr, rslot):
    """Differentiable ELL aggregation: ``out[i] = Σ_k w[i,k] x[nbr[i,k]]``.

    ``x [N_src, F]``; ``nbr``/``w [N_dst, K]`` (pad entries carry ``w ==
    0``); ``rnbr``/``rslot [N_src, RK]`` are the static reversed lists from
    :func:`repro.dist.halo.build_reverse_ell` — ``rslot`` gathers the
    matching forward weight (``-1`` pad), so the x-cotangent is the
    reversed-list ELL SpMM (the exact transpose of the forward).
    """
    del rnbr, rslot
    return _ell_gather(x, nbr, w)


def _ell_aggregate_fwd(x, nbr, w, rnbr, rslot):
    return _ell_gather(x, nbr, w), (x, nbr, w, rnbr, rslot)


def _ell_aggregate_bwd(res, g):
    x, nbr, w, rnbr, rslot = res
    rw = jnp.where(rslot >= 0, w.reshape(-1)[jnp.maximum(rslot, 0)], 0.0)
    dx = _ell_gather(g, rnbr, rw).astype(x.dtype)

    # dw[i, k] = <g[i], x[nbr[i, k]]> — K-sliced like _ell_gather, never the
    # [N, K, F] gather.  (In the train loop graph weights are not
    # differentiated, so XLA DCEs this branch entirely.)
    gf = g.astype(jnp.float32)

    def body(k, acc):
        return acc.at[:, k].set(
            jnp.sum(gf * x[nbr[:, k]].astype(jnp.float32), axis=-1))

    dw = jax.lax.fori_loop(0, nbr.shape[1], body,
                           jnp.zeros(nbr.shape, jnp.float32)).astype(w.dtype)
    return dx, None, dw, None, None


ell_aggregate.defvjp(_ell_aggregate_fwd, _ell_aggregate_bwd)


# re-exported oracles (benchmarks compare against these)
mha_reference = ref.mha_reference
pack_reference = ref.pack_reference
unpack_reference = ref.unpack_reference
pack_quant_reference = ref.pack_quant_reference
quant_dequant_reference = ref.quant_dequant_reference
pack_bits_reference = ref.pack_bits_reference
unpack_bits_reference = ref.unpack_bits_reference
unpack_quant_reference = ref.unpack_quant_reference
ell_spmm_reference = ref.ell_spmm_reference
ssd_reference = ref.ssd_reference
