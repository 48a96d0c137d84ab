"""Partition-parallel GNN runtime (paper Algorithm 1, DESIGN.md §3).

Each of the ``Q`` workers owns one graph partition in the padded ``[Q, ...]``
layout produced by :class:`repro.graph.partition.PartitionedGraph`.  A layer's
aggregation ``S x`` decomposes into

* a **local** scatter over edges whose endpoints are both owned, plus
* a **remote** scatter over cross edges whose source activations arrive via
  the *halo exchange*: every worker publishes its boundary nodes, the blocks
  are (optionally compressed, then) all-gathered, and the flattened
  ``[Q·B, F]`` halo buffer supplies the remote neighbour terms.

The same aggregation oracle (``nn.gnn.AggregateFn``) is built two ways:

* ``_make_aggregate_emulated`` — single-device emulation over the stacked
  ``[Q, ...]`` arrays (vmap over partitions, the all-gather is a reshape).
  This is the default test/CPU path.
* ``_make_aggregate_shard`` — the real collective path for ``shard_map``
  over a ``workers`` mesh axis, using
  :func:`repro.core.collectives.compressed_all_gather`.

Both draw per-worker compression masks from ``fold_in(key, worker_index)``
of a per-exchange key, so the emulated and shard_map runs are *bitwise
identical* (tests/test_multidevice.py pins this).

Wire formats (``DistMeta.wire``, DESIGN.md §3.3/§3.5): ``"dense"``
all-gathers the masked ``[B, F]`` boundary block — compression shrinks the
ledger, not the buffer; ``"packed"`` ships only the kept lane-blocks
(``[B, K·128]``, via :func:`repro.core.collectives.packed_all_gather` / the
varco_pack kernels), so the wire volume itself drops with the rate;
``"p2p"`` replaces the all-gather entirely with a neighbor-only
``ppermute`` ring (:func:`repro.core.collectives.neighbor_exchange`) that
ships each peer only the per-pair halo rows it references
(``repro.dist.halo``), and runs the local-edge aggregation as ELL row
gathers (:func:`repro.kernels.ops.ell_aggregate`) while the hops are in
flight — transport equals the analytic point-to-point
charge at every rate.  All formats draw the same per-worker masks, so
packed / p2p and dense-``blockmask`` runs deliver identical remote values;
wire buffer shapes are set by the static kept-block counts, which each
step quantises from the schedule outside jit (bounded recompiles — see
:func:`make_train_step`).  The p2p wire needs the halo/ELL index arrays of
:func:`repro.dist.halo.attach_p2p` merged into the graph pytree.

Both non-dense wires additionally accept a per-pair ``[Q, Q]`` **rate
map** (DESIGN.md §3.6) in place of the scalar rate — the operand the
closed-loop controllers of ``repro.dist.ratectl`` plan each step: one
static kept-block count per width (the map's maximum) keeps recompiles
bounded, nested permutation masks carve out each pair's own kept set, and
the ledger grows per-pair transport / compression-error / staleness
columns (see :func:`_make_aggregate_emulated`).

Ledger accounting (paper Fig. 5 axis): every exchange charges two numbers,
``[analytic, transport]``.  Analytic is ``halo_demand × F × 32 / rate``
bits — the activations a point-to-point implementation would ship.
Transport is what the active wire format actually ships per needed boundary
row: the full ``F`` columns on the dense wire (zeros travel too), the
``K·128`` packed columns on the packed wire (DESIGN.md §3.2–3.3).  A train
step charges twice the forward traffic (activations forward + their
cotangents backward).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.collectives import (compressed_all_gather,
                                    neighbor_exchange_finish,
                                    neighbor_exchange_start,
                                    packed_all_gather)
from repro.core.compression import Compressor
from repro.core.varco import FULL_COMM, CommPolicy
from repro.dist.sharding import worker_graph_shardings
from repro.graph.partition import PartitionedGraph
from repro.kernels.ops import (WIRE_WIDTHS, dequant_bits, ell_aggregate,
                               pack_bits, per_block_wire_bits, quant_levels,
                               round_key, wire_pack, wire_quant, wire_unpack)
from repro.kernels.varco_pack import (LANE, worker_block_maps,
                                      worker_block_maps_pos)
from repro.nn.gnn import GNNConfig, gnn_forward, masked_loss_and_correct
from repro.train.optim import Optimizer, apply_updates

AXIS = "workers"
WIRES = ("dense", "packed", "p2p")

# shard_map executables kept per kept-block map before LRU eviction (an
# annealing schedule revisits maps; see make_train_step)
COMPILED_CACHE_SIZE = 8


# ---------------------------------------------------------------------------
# Static partition metadata
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DistMeta:
    """Static (hashable) facts about a partitioning, shared by every step.

    ``halo_demand`` is the paper's communication unit: the number of distinct
    (requesting partition, remote node) pairs whose activations must cross
    the wire each exchange.  Split sizes are *global* so per-worker losses
    normalise identically (``psum(local grads) == full gradient``).

    ``wire`` selects the halo-exchange transport (DESIGN.md §3.3/§3.5):
    ``"dense"`` ships the masked ``[B, F]`` block, ``"packed"`` ships only
    the kept ``[B, K·128]`` lane-blocks via the varco_pack kernels, and
    ``"p2p"`` ships each peer only its per-pair halo rows over the
    ``neighbor_exchange`` ppermute ring (graph pytree must carry the
    ``repro.dist.halo.attach_p2p`` arrays).  ``p2p_hop_width`` /
    ``p2p_compact`` are the p2p wire's static buffer facts (``H`` rows per
    ring hop, receiver-side compact-buffer height).

    Example::

        pg = partition_graph(g, q=8, scheme="random")
        meta = DistMeta.build(pg, params, wire="p2p")
        step = make_train_step(cfg, policy, opt, meta)
    """

    q: int
    part_size: int
    halo_size: int
    num_nodes: int
    feat_dim: int
    num_classes: int
    halo_demand: int
    cross_edges: int
    n_train: int
    n_val: int
    n_test: int
    layer_dims: tuple[int, ...]
    wire: str = "dense"
    p2p_hop_width: int = 0
    p2p_compact: int = 0
    # flattened [Q*Q] per-pair halo row counts (receiver-major; diagonal 0),
    # summing to halo_demand — the unit of per-pair rate-map accounting and
    # of the ratectl controllers' water-filling (DESIGN.md §3.6)
    pair_rows: tuple = ()

    def __post_init__(self):
        if self.wire not in WIRES:
            raise ValueError(f"wire must be one of {WIRES}, got {self.wire!r}")
        if self.wire == "packed":
            # halo exchanges happen at each layer's *input* width, which is
            # what layer_dims records (sage exchanges once per layer, poly
            # once per extra tap — same widths)
            for f in {self.feat_dim, *self.layer_dims}:
                if f % LANE:
                    raise ValueError(
                        f"packed wire needs every exchanged feature width "
                        f"divisible by {LANE}, got {f} (exchanged widths: "
                        f"{sorted({self.feat_dim, *self.layer_dims})}); "
                        f"use wire='dense' for off-lane-grid models")

    @staticmethod
    def build(pg: PartitionedGraph, params: dict,
              wire: str = "dense") -> "DistMeta":
        dims = []
        for layer in params["layers"]:
            if "self" in layer:                       # sage
                dims.append(int(layer["self"]["w"].shape[0]))
            else:                                     # poly taps
                dims.append(int(layer["taps"][0]["w"].shape[0]))
        # the per-pair facts cost an O(Q² + edges) host sweep — only the
        # rate-map-capable wires consume them, the dense wire stays free.
        # Shard-backed graphs (repro.graph.stream.ShardSet) carry the spec
        # precomputed in their manifest, so no sweep (and no global graph)
        # is needed at all.
        hop_w = compact = 0
        pair_rows: tuple = ()
        if wire != "dense":
            spec = getattr(pg, "halo_spec", None)
            if spec is None:
                from repro.dist.halo import build_halo_spec
                spec = build_halo_spec(pg)
            pair_rows = spec.pair_rows
            if wire == "p2p":
                hop_w, compact = spec.hop_width, spec.compact_rows
        n_train = getattr(pg, "n_train", None)
        return DistMeta(
            q=pg.q, part_size=pg.part_size, halo_size=pg.halo_size,
            num_nodes=pg.num_nodes, feat_dim=pg.feat_dim,
            num_classes=pg.num_classes, halo_demand=pg.halo_demand,
            cross_edges=pg.cross_edges,
            n_train=int(pg.train_mask.sum()) if n_train is None
            else int(n_train),
            n_val=int(pg.val_mask.sum()) if n_train is None
            else int(pg.n_val),
            n_test=int(pg.test_mask.sum()) if n_train is None
            else int(pg.n_test),
            layer_dims=tuple(dims), wire=wire,
            p2p_hop_width=hop_w, p2p_compact=compact,
            pair_rows=pair_rows)

    def pair_table(self) -> np.ndarray:
        """``[Q, Q]`` per-pair halo row counts (receiver × sender, diagonal
        0; entries sum to ``halo_demand``).  The unit of the per-pair
        rate-map ledger and of ``repro.dist.ratectl``'s allocations.
        Populated by :meth:`build` for the packed and p2p wires;
        hand-constructed or dense-wire metas must fill ``pair_rows``
        before using a ``[Q, Q]`` rate map."""
        if not self.pair_rows:
            raise ValueError(
                "DistMeta.pair_rows is empty — per-pair rate maps need the "
                "pair table; construct the meta via DistMeta.build(...) "
                "with wire='packed' or 'p2p' (dense metas don't carry it)")
        return np.asarray(self.pair_rows, np.int64).reshape(self.q, self.q)

    def ledger_bits(self, feat: int, rate=1.0) -> jnp.ndarray:
        """Analytic wire bits of one halo exchange at feature width ``feat``."""
        return jnp.asarray(self.halo_demand * feat * 32.0, jnp.float32) / \
            jnp.asarray(rate, jnp.float32)

    def packed_width(self, feat: int, rate: float = 1.0) -> int:
        """Columns of the packed wire payload: ``K·128`` with ``K =
        max(floor((feat/128)/rate), 1)`` (matches ``block_mask_indices``).
        ``rate`` must be static; ``feat % 128 == 0``."""
        if feat % LANE:
            raise ValueError(f"packed wire needs feat % {LANE} == 0, "
                             f"got {feat}")
        n_blocks = feat // LANE
        return max(int(n_blocks / max(float(rate), 1.0)), 1) * LANE

    def _wire_width(self, feat: int, rate: float) -> int:
        """On-wire column count of the active format at ``rate``."""
        if self.wire == "packed":
            return self.packed_width(feat, rate)
        if self.wire == "p2p":
            # uncompressed hops ship dense rows (any width); compressing
            # policies pack lane-blocks exactly like the packed wire
            return feat if float(rate) <= 1.0 \
                else self.packed_width(feat, rate)
        return feat

    def transport_bits(self, feat: int, rate: float = 1.0) -> jnp.ndarray:
        """Bits the active wire format actually ships per halo exchange,
        charged per needed boundary row (same point-to-point ``halo_demand``
        unit as :meth:`ledger_bits`): the full ``feat`` columns on the dense
        wire — dropped entries travel as zeros — vs the ``K·128`` packed
        columns.  Equals ``ledger_bits`` at rate 1 on the packed and p2p
        wires; on the p2p wire the charge *is* the physically shipped
        volume (padding aside) — the analytic edge-cut rows — equal to
        ``ledger_bits`` whenever the rate divides the lane-block count."""
        width = self._wire_width(feat, rate)
        return jnp.asarray(self.halo_demand * width * 32.0, jnp.float32)

    def transport_bits_quant(self, feat: int, rate: float = 1.0,
                             width: int = 32) -> jnp.ndarray:
        """:meth:`transport_bits` on a quantised wire (DESIGN.md §3.8):
        per needed boundary row, each of the ``K`` kept lane-blocks
        charges ``128·width`` payload bits plus one fp32 scale.
        ``width >= 32`` reproduces :meth:`transport_bits` exactly (fp32
        ships no scales) — the analytic counterpart the quant smoke pins
        the measured ledger against."""
        if width >= 32:
            return self.transport_bits(feat, rate)
        k = self.packed_width(feat, rate) // LANE
        return jnp.asarray(
            self.halo_demand * k * (LANE * width + 32.0), jnp.float32)

    def collective_bits(self, feat: int, rate: float = 1.0) -> float:
        """Bits the wire format physically moves per exchange, padding
        included — the honest buffer-level volume the benchmarks compare.
        All-gather wires ship every worker's padded ``[B, width]`` block to
        ``Q - 1`` peers; the p2p ring ships ``Q - 1`` padded ``[H, width]``
        hop buffers per worker, each crossing to exactly one peer."""
        width = self._wire_width(feat, rate)
        if self.wire == "p2p":
            return float(self.q * max(self.q - 1, 0) *
                         self.p2p_hop_width * width * 32.0)
        return float(self.q * (self.q - 1) * self.halo_size * width * 32.0)


# ---------------------------------------------------------------------------
# Mesh / placement
# ---------------------------------------------------------------------------


def make_worker_mesh(q: int) -> Mesh:
    """1-D ``workers`` mesh over the first ``q`` local devices.

    Example (8 virtual CPU devices)::

        # XLA_FLAGS=--xla_force_host_platform_device_count=8
        mesh = make_worker_mesh(8)
        step = make_train_step(cfg, policy, opt, meta, mesh=mesh)
    """
    devs = jax.devices()
    if len(devs) < q:
        raise ValueError(f"need {q} devices for a worker mesh, have "
                         f"{len(devs)} (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={q})")
    return Mesh(np.asarray(devs[:q]), (AXIS,))


def shard_graph(graph: dict, mesh: Mesh) -> dict:
    """Place the ``[Q, ...]`` graph pytree over the ``workers`` axis.

    Handles every leaf the runtime knows — including the p2p per-pair halo
    specs and ELL lists merged in by ``repro.dist.halo.attach_p2p`` (all
    stacked ``[Q, ...]``, specs from
    :func:`repro.dist.sharding.worker_graph_shardings`).

    Example::

        graph = shard_graph(attach_p2p(pg.device_arrays(), pg),
                            make_worker_mesh(pg.q))
    """
    shardings = worker_graph_shardings(graph, mesh, AXIS)
    return {k: jax.device_put(v, shardings[k]) for k, v in graph.items()}


# ---------------------------------------------------------------------------
# Aggregation oracles
# ---------------------------------------------------------------------------


def _local_w_for(graph: dict, policy: CommPolicy, rate):
    """Local edge weights for a communicating exchange at rate ``r``.

    VARCO mode blends toward the isolated-subgraph renormalisation: the
    biased mask delivers remote halo mass attenuated by ``1/r`` in
    expectation, so the aggregation realises ``(1/r)·S_full + (1-1/r)·S_iso``
    — local weights interpolate from the global-degree normalisation
    (``r=1``, bitwise the centralized operator) toward the No-Comm operator
    (``r→∞``).  Without the blend, heavy early compression under-scales
    every aggregation instead of degrading gracefully to the
    (well-conditioned) local-only training that the schedule then anneals
    away from.

    Fixed-compression and full-comm runs keep the paper's plain baseline
    semantics (no renormalisation), which the Definition-1 error-envelope
    tests pin down.
    """
    lw = graph["local_w"]
    if policy.mode != "varco":
        return lw
    mix = 1.0 - 1.0 / jnp.maximum(jnp.asarray(rate, jnp.float32), 1.0)
    return lw + mix * (graph["local_w_iso"] - lw)


def _ell_w_for(graph: dict, policy: CommPolicy, rate):
    """:func:`_local_w_for` in ELL layout (the p2p wire's local weights):
    the same VARCO blend toward the isolated-subgraph renormalisation,
    applied elementwise to the degree-padded ``[Q, P, K]`` weight lists
    (pad entries are 0 in both operands, so they stay 0)."""
    w = graph["ell_w"]
    if policy.mode != "varco":
        return w
    mix = 1.0 - 1.0 / jnp.maximum(jnp.asarray(rate, jnp.float32), 1.0)
    return w + mix * (graph["ell_w_iso"] - w)


def _exchange_bits(meta: DistMeta, f: int, rate,
                   wire_width: int | None = None) -> jnp.ndarray:
    """Per-exchange ledger charge ``[analytic, transport]`` (module docs).
    ``wire_width`` is the static on-wire column count — ``K·128`` on the
    packed wire, the full ``f`` (dense buffer) when ``None``."""
    transport = meta.halo_demand * (f if wire_width is None
                                    else wire_width) * 32.0
    return jnp.stack([meta.ledger_bits(f, rate),
                      jnp.asarray(transport, jnp.float32)])


def _keep_of(f: int, rate, packed_k: dict | None) -> int:
    """Static kept-block count for a packed exchange at width ``f``: from
    the quantised ``packed_k`` map when the rate is traced (train steps),
    else derived from a concrete rate directly (tests / eval call sites)."""
    n_blocks = f // LANE
    if packed_k is not None:
        return packed_k[n_blocks]
    return max(int(n_blocks / max(float(rate), 1.0)), 1)


def _exchanged_nbs(meta: DistMeta) -> tuple:
    """Sorted distinct lane-block counts of every exchanged feature width
    (``feat_dim`` plus each layer's input width) — THE shared domain of
    every bounded-recompile static-fact map (`_packed_k_for`,
    `_packed_pair_k_for`, `_packed_pair_w_for`): each quantises its traced
    operand to one static value per entry of this tuple, so the number of
    distinct compiled variants is bounded by the tuple's value ranges, not
    by the operand's."""
    return tuple(sorted({d // LANE for d in (meta.feat_dim,
                                             *meta.layer_dims)}))


def _packed_k_for(meta: DistMeta, rate_f: float) -> tuple:
    """Quantise a concrete rate to the kept-block count of every exchanged
    width (``layer_dims`` = each layer's input width) — the *only* static
    fact the packed wire needs per step, so an annealing schedule triggers
    at most ``Π n_blocks`` recompiles (a handful) instead of one per
    distinct rate value."""
    return tuple((nb, max(int(nb / max(rate_f, 1.0)), 1))
                 for nb in _exchanged_nbs(meta))


# ---------------------------------------------------------------------------
# Per-pair rate maps (DESIGN.md §3.6) — shared plumbing of both backends
# ---------------------------------------------------------------------------
#
# A closed-loop controller (``repro.dist.ratectl``) plans a ``[Q, Q]`` rate
# map (receiver × sender) instead of one scalar.  The wire realises it with
# ONE static kept-block count per exchanged width — the map's *maximum* —
# so recompiles stay bounded exactly like `_packed_k_for`: every sender
# packs once at that count, and each pair's smaller kept set is carved out
# by zeroing packed columns whose block sits at permutation position
# ``>= k_pair`` (kept sets at different counts are nested under one key —
# `block_mask_indices_pos`).  The dense wire keeps the scalar path.


def _pair_keep(nb: int, rate_map, k_max: int) -> jnp.ndarray:
    """Traced per-pair kept-block counts ``[Q, Q]`` at width ``nb·128``:
    the same ``max(floor(nb / r), 1)`` rule as the ``blockmask`` compressor
    and `_keep_of`, clamped to the step's static maximum ``k_max``."""
    r = jnp.maximum(jnp.asarray(rate_map, jnp.float32), 1.0)
    k = jnp.maximum(jnp.floor(nb / r), 1.0)
    return jnp.minimum(k, float(k_max)).astype(jnp.int32)


def _packed_pair_k_for(meta: DistMeta, rate_map) -> tuple:
    """Quantise a concrete rate map to the static max kept-block count of
    every exchanged width — `_packed_k_for`'s bounded-recompile contract
    for rate maps (at most ``Π (width/128)`` distinct tuples).

    Accepts the per-pair ``[Q, Q]`` map or the per-layer ``[L, Q, Q]``
    tensor (DESIGN.md §3.7): the static count is the maximum over every
    layer's off-diagonal entries, so one packed buffer per width serves
    all layers and each layer's smaller kept set is carved out by the
    nested column masks."""
    rm = np.maximum(np.asarray(rate_map, np.float64), 1.0)
    q = meta.q
    rm = rm.reshape(-1, q, q)          # [L, Q, Q] (L == 1 for pair maps)
    off = ~np.eye(q, dtype=bool) if q > 1 else np.zeros((1, 1), bool)
    out = []
    for nb in _exchanged_nbs(meta):
        k = np.maximum(np.floor(nb / rm), 1.0)
        kmax = int(k[:, off].max()) if q > 1 else 1
        out.append((nb, min(max(kmax, 1), nb)))
    return tuple(out)


def _snap_width(v) -> int:
    """Snap a planned bit-width to the nearest supported storage width from
    above: {2, 4, 8} quantised wire widths, else 32 (exact fp32).  Snapping
    *up* keeps realised error at or below the planner's estimate — the
    width analogue of `_pair_keep`'s floor-to-k rule."""
    v = float(v)
    for w in WIRE_WIDTHS[:-1]:
        if v <= w:
            return w
    return 32


def _packed_pair_w_for(meta: DistMeta, width_map) -> tuple:
    """Quantise a concrete width map to the sorted tuple of distinct
    sub-32 storage widths it realises off-diagonal — `_packed_pair_k_for`'s
    bounded-recompile contract for the width axis.

    The tuple is the jit-static fact the step function keys its compiled
    variants on: ``()`` (no pair quantises) compiles the exact pre-
    quantisation program — the quantise/dequantise code never enters the
    jaxpr — and at most ``2^|{2,4,8}|`` distinct tuples exist, so an
    annealing width schedule recompiles a bounded handful of times no
    matter how many distinct planned widths it visits.  Accepts ``[Q, Q]``
    or per-layer ``[L, Q, Q]`` maps (self-pairs never ship, so the
    diagonal is ignored)."""
    if width_map is None:
        return ()
    q = meta.q
    if q <= 1:
        return ()
    wm = np.asarray(width_map, np.float64).reshape(-1, q, q)
    off = ~np.eye(q, dtype=bool)
    ws = sorted({_snap_width(v) for v in wm[:, off].ravel()})
    return tuple(w for w in ws if w < 32)


def _packed_store_w(meta: DistMeta, width_map) -> int:
    """Static sub-byte **storage** width of a concrete width map
    (DESIGN.md §3.8): the maximum snapped off-diagonal width when every
    off-diagonal pair quantises (all snap below 32), else 0.

    Non-zero turns the quantised wires into true bit-packed byte buffers
    — ``8/store_w`` lanes per byte ride the collective instead of fp32
    lanes.  Pairs planned *below* the storage width store exactly (their
    levels fit the wider field; the ledger still charges the planned
    width).  Any pair at width ≥ 32 forces 0: fp32 lanes must travel for
    that pair, so the whole exchange stays on the exact straight-through
    value path.  Like `_packed_pair_w_for` this is a jit-static fact
    derived from `_snap_width`, so it adds no recompiles beyond the
    width-tuple's own variants."""
    if width_map is None:
        return 0
    q = meta.q
    if q <= 1:
        return 0
    wm = np.asarray(width_map, np.float64).reshape(-1, q, q)
    off = ~np.eye(q, dtype=bool)
    ws = {_snap_width(v) for v in wm[:, off].ravel()}
    if not ws or max(ws) >= 32:
        return 0
    return max(ws)


def plan_statics(meta: DistMeta, plan) -> tuple:
    """Host-side quantisation of a concrete rate plan to what a step
    compiles on: ``(rate_map, packed_k, width_map, wire_w, store_w)``.

    ``rate_map`` is ``plan.rates`` as f32 numpy and ``packed_k`` its
    static kept-block maxima (:func:`_packed_pair_k_for`); ``width_map``
    holds the widths snapped to the storage grid (:func:`_snap_width`)
    and ``wire_w`` their distinct sub-32 tuple
    (:func:`_packed_pair_w_for`) — ``(None, ())`` when no pair quantises,
    which compiles the exact pre-quantisation program; ``store_w`` is the
    sub-byte storage width (:func:`_packed_store_w`).  Every plan-driven
    step (auto, fault, serving; both backends) derives its jit-static
    arguments here."""
    rm = np.asarray(plan.rates, np.float32)
    wm, ww = None, ()
    if plan.widths is not None:
        wm = np.vectorize(_snap_width)(
            np.asarray(plan.widths, np.float32)).astype(np.float32)
        ww = _packed_pair_w_for(meta, wm)
        if not ww:
            wm = None
    return rm, _packed_pair_k_for(meta, rm), wm, ww, \
        _packed_store_w(meta, wm)


def _rate_tensor_layers(meta: DistMeta, rate_map) -> int:
    """Static layer count of a rate operand: 1 for ``None`` / ``[Q, Q]``
    pair maps, ``L`` for a per-layer ``[L, Q, Q]`` tensor — which must
    match the model's layer count (``len(meta.layer_dims)``), since layer
    ``li``'s exchanges index row ``li``."""
    if rate_map is None or jnp.ndim(rate_map) == 2:
        return 1
    if jnp.ndim(rate_map) != 3:
        raise ValueError(f"rate map must be [Q, Q] or [L, Q, Q], got "
                         f"ndim {jnp.ndim(rate_map)}")
    n_layers = int(jnp.shape(rate_map)[0])
    if n_layers != len(meta.layer_dims):
        raise ValueError(
            f"per-layer rate tensor has {n_layers} layer rows but the "
            f"model exchanges at {len(meta.layer_dims)} layers "
            f"(DistMeta.layer_dims {meta.layer_dims})")
    return n_layers


def _ring_targets(q: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(senders [Q, 1], receivers [Q, D])`` of the hop layout: sender
    ``j``'s ring-offset-``d`` buffer goes to worker ``(j + d) mod Q``
    (``D = max(Q-1, 1)``, degenerate but well-formed at ``Q == 1``)."""
    jj = jnp.arange(q)[:, None]
    rv = (jj + jnp.arange(1, max(q, 2))[None, :]) % q
    return jj, rv


def _scatter_pairs(vals_jd: jnp.ndarray, q: int) -> jnp.ndarray:
    """Reshape sender-major per-hop values ``[Q, D]`` into the receiver ×
    sender ``[Q, Q]`` pair matrix (diagonal 0)."""
    if q == 1:
        return jnp.zeros((1, 1), vals_jd.dtype)
    jj, rv = _ring_targets(q)
    return jnp.zeros((q, q), vals_jd.dtype).at[rv, jj].set(vals_jd)


def _pair_hop_energy(publish: jnp.ndarray, slot: jnp.ndarray,
                     valid: jnp.ndarray) -> jnp.ndarray:
    """Per-hop, per-lane-block energy of the published boundary rows.

    ``publish [Q, B, F]`` (pre-compression), ``slot``/``valid [Q, D, H]``
    (the p2p per-pair halo sets) → ``[Q, D, nb]`` summed squared values of
    hop ``(j, d)``'s genuine rows per 128-lane block.  The blockmask
    round-trip error of a pair is *exactly* its dropped blocks' energy, so
    the ``error`` controller's observation is this tensor masked by the
    pair's dropped set — identical arithmetic on both backends."""
    q, _, f = publish.shape
    nb = f // LANE
    be = jnp.sum(publish.reshape(q, -1, nb, LANE).astype(jnp.float32) ** 2,
                 axis=-1)                              # [Q, B, nb]

    def per_worker(bej, slots, vals):                  # [B,nb],[D,H],[D,H]
        return jnp.sum(bej[slots] * vals[..., None], axis=1)

    return jax.vmap(per_worker)(be, slot, valid)       # [Q, D, nb]


def _pair_ledger(meta: DistMeta, f: int, rate_map, row_bits,
                 pair_err, pair_delta, live=None, li: int = 0,
                 n_layers: int = 1, width_map=None) -> jnp.ndarray:
    """Flat per-pair ledger vector of one exchange:
    ``[analytic, transport, layer_transport (L·Q²), layer_err (L·Q²),
    layer_delta (L·Q²)]`` (length ``2 + 3·L·Q²``).

    ``row_bits [Q, Q]`` is each pair's realised on-wire bits *per shipped
    row* — ``kept columns · 32`` on the fp32 wire, ``kept blocks ·
    per_block_wire_bits(w)`` (low-bit payload + one fp32 scale per block,
    the PR-1 accounting convention) when the pair quantises; ``live``
    (0/1, default all-1) zeroes skipped pairs (the ``stale`` controller's
    reused hops ship nothing, forward or backward).

    ``width_map [Q, Q]`` scales the *analytic* column by each pair's
    ``w/32`` payload factor (scale overhead excluded — analytic is the
    paper's idealised element count, transport the wire truth), making
    rate × width one joint 2-D allocation on both ledger columns.

    ``li``/``n_layers`` place this exchange's pair blocks on the per-layer
    ledger axis (DESIGN.md §3.7): each block lands in layer ``li``'s
    ``Q²`` slice, zeros elsewhere, so summing the per-call vectors across
    a forward pass composes the ``[L, Q, Q]`` tensors exchange-by-
    exchange.  ``n_layers == 1`` is the legacy per-pair layout (all
    exchanges accumulate into the single slice)."""
    rows = jnp.asarray(meta.pair_table(), jnp.float32)
    live = jnp.ones_like(rows) if live is None else live
    r = jnp.maximum(jnp.asarray(rate_map, jnp.float32), 1.0)
    w_factor = 1.0
    if width_map is not None:
        w = jnp.asarray(width_map, jnp.float32)
        w_factor = jnp.where(w >= 32.0, 1.0, w / 32.0)
    analytic = jnp.sum(rows * live * f * 32.0 / r * w_factor)
    pair_t = rows * live * row_bits

    def embed(block):
        if n_layers == 1:
            return block.ravel()
        out = jnp.zeros((n_layers, block.size), block.dtype)
        return out.at[li].set(block.ravel()).ravel()

    return jnp.concatenate([
        jnp.stack([analytic, jnp.sum(pair_t)]),
        embed(pair_t), embed(pair_err), embed(pair_delta)])


def _dead_mix(meta: DistMeta, dead) -> jnp.ndarray:
    """Per-receiver fraction of remote halo rows served by DEAD pairs
    (``[Q]``): the blend weight of the local-only renormalisation.  A
    fully dark receiver (every remote pair dead) lands exactly on the
    isolated (No-Comm) aggregation weights — the paper's rate→0 limit
    (DESIGN.md §3.10)."""
    rows = jnp.asarray(meta.pair_table(), jnp.float32)
    dark = jnp.sum(rows * jnp.asarray(dead, jnp.float32), axis=1)
    return dark / jnp.maximum(jnp.sum(rows, axis=1), 1.0)


def _fault_live(q: int, fskip, dead, live):
    """Fold the fault masks into the ledger's live matrix: CACHED
    (``fskip``) and DEAD pairs ship nothing, forward or backward — both
    their analytic and transport charges go to zero, and the budget/PI
    loop re-spends those bits on live pairs."""
    if fskip is None and dead is None:
        return live
    lv = jnp.ones((q, q), jnp.float32) if live is None else live
    if fskip is not None:
        lv = lv * (1.0 - jnp.asarray(fskip, jnp.float32))
    if dead is not None:
        lv = lv * (1.0 - jnp.asarray(dead, jnp.float32))
    return lv


def _per_part(fn, *stacks):
    """``fn`` over the leading ``[Q]`` axis of ``stacks``.

    On TPU one partition at a time (``lax.map``), so the partitions' edge
    scatter-adds stay unbatched: XLA:TPU's compile time for a scatter-add
    grows with its operand, and ``vmap`` makes one ``[Q, P+1, F]``
    scatter per exchange — about two minutes of compilation per program
    at OGBN-Arxiv scale, where the per-partition body takes seconds.
    Elsewhere ``vmap``, the program the CPU parity tests pin (the two
    agree to f32 rounding, not bitwise)."""
    if jax.default_backend() == "tpu":
        return jax.lax.map(lambda a: fn(*a), stacks)
    return jax.vmap(fn)(*stacks)


def _make_aggregate_emulated(graph: dict, meta: DistMeta, policy: CommPolicy,
                             compressor: Compressor | None, rate, key,
                             packed_k: dict | None = None, rate_map=None,
                             skip=None, cache=None,
                             cache_out: list | None = None,
                             width_map=None, resid=None,
                             resid_out: list | None = None,
                             fskip=None, fcache=None,
                             fcache_out: list | None = None, dead=None,
                             rounding: str = "rint", store_w: int = 0,
                             wire_out: list | None = None):
    """AggregateFn over stacked ``[Q, P, F]`` tensors on one device.

    Numerically identical to the shard_map path: the all-gather becomes a
    reshape of the per-partition published blocks, and compression draws the
    worker-``i`` mask from ``fold_in(per-exchange key, i)`` exactly as
    ``compressed_all_gather`` does on device ``i``.  On the packed wire the
    same keys select the kept lane-blocks, and the wire payload is
    materialised through ``wire_pack``/``wire_unpack`` so the emulation
    exercises the real pack→ship→unpack round trip.  On the p2p wire each
    ``ppermute`` ring offset becomes a roll of the per-pair send buffers
    (same keys → same masks as ``neighbor_exchange``), and the local edges
    run through :func:`repro.kernels.ops.ell_aggregate`.

    ``rate_map`` (traced ``[Q, Q]``, receiver × sender) switches the packed
    and p2p wires to per-pair rates (DESIGN.md §3.6): every sender packs
    once at the static step maximum (``packed_k``), pairs below it are
    carved out by the nested-permutation column masks, and the returned
    ledger vector grows to ``2 + 3·Q²`` (per-pair transport, compression
    error, staleness delta).  A per-layer ``[L, Q, Q]`` tensor
    (DESIGN.md §3.7; ``L == len(meta.layer_dims)``) makes layer ``li``'s
    exchange draw its own ``[Q, Q]`` row, and the ledger vector grows a
    layer axis (``2 + 3·L·Q²``, exchange ``li``'s charges in slice
    ``li``).  ``skip``/``cache``/``cache_out`` are the ``stale``
    controller's hop reuse on the p2p wire: pair ``(i, j)`` with
    ``skip[i, j] == 1`` delivers ``cache[call]``'s rows instead of fresh
    ones and charges zero wire bits; the fresh buffers land in
    ``cache_out`` (one ``[Q, D, H, F]`` entry per exchange call).

    ``width_map`` (traced ``[Q, Q]`` or ``[L, Q, Q]``, same selection rule
    as ``rate_map``) quantises each pair's wire payload to its planned
    bit-width (DESIGN.md §3.8): the p2p wire quantises every hop at its
    *exact* per-pair width through the straight-through
    :func:`repro.kernels.ops.wire_quant`; the packed all-gather wire — one
    payload per sender — quantises at each sender's max width over its
    receivers (serve the most demanding, like ``k_send``).  The ledger
    charges the true ``per_block_wire_bits`` (payload at width + one fp32
    scale per kept block).  ``resid``/``resid_out`` are the error-feedback
    accumulators (p2p only): call ``i``'s residual ``[Q, D, H, F]`` is
    added to the pre-quantisation payload and the fresh quantisation error
    lands in ``resid_out``, so the compression error is re-shipped next
    step instead of lost (gradients see only the STE path — residual
    injection is ``stop_gradient``).

    ``fskip``/``fcache``/``fcache_out``/``dead`` are the FAULT channel
    (DESIGN.md §3.10) — deliberately separate from the ``stale``
    controller's ``skip``/``cache`` so degraded-mode halo service works
    under every policy: a pair with ``fskip[i, j] == 1`` (link dropped,
    cache still fresh enough) is served from ``fcache[call]`` and charges
    zero wire bits; ``dead[i, j] == 1`` (past ``max_stale``) zeroes the
    pair's rows and blends the receiver's local aggregation toward the
    isolated weights (:func:`_dead_mix`).  The served buffers land in
    ``fcache_out`` (one ``[Q, D, H, F]`` sender-major entry per exchange
    call) so the receiver's cache tracks the last content it actually
    aggregated.

    ``store_w`` (static, from :func:`_packed_store_w`) switches the
    quantised wires to **true sub-byte storage** (DESIGN.md §3.8): the
    materialised wire buffer is the bit-packed uint8 levels
    (``8/store_w`` lanes per byte) plus the fp32 block scales, and the
    delivered values are rebuilt as ``levels · scale`` from those bytes
    — elementwise identical to the shard backend's byte collectives, so
    mixed rate × width runs stay in the parity matrix.  ``wire_out``,
    when a list, captures each rate-map exchange's physically shipped
    buffers — ``(payload uint8, scales)`` under ``store_w``, ``(fp32
    buffer, None)`` otherwise (sender-major ``[Q, D, H, ·]`` hop stacks
    on the p2p wire, ``[Q, B, ·]`` payloads on the packed wire) — the
    ledger-vs-buffer conservation hook.

    The returned oracle carries the split-phase API of the pipelined
    forward (DESIGN.md §3.7): ``aggregate.start(li, x)`` issues the
    pack + exchange and returns ``(token, bits)``;
    ``aggregate.complete(li, x, token)`` runs the local aggregation and
    folds in the delivered halo.  ``aggregate(li, x)`` is exactly
    ``complete`` after ``start`` — one code path, so the fused and
    pipelined schedules are bitwise identical.
    """
    p_sz, b_sz, q = meta.part_size, meta.halo_size, meta.q
    packed_wire = meta.wire == "packed"
    p2p_wire = meta.wire == "p2p"
    if rate_map is not None and not (packed_wire or p2p_wire):
        raise ValueError("per-pair rate maps need wire='packed' or 'p2p'; "
                         "the dense wire keeps the scalar path")
    n_layers = _rate_tensor_layers(meta, rate_map)
    if width_map is not None:
        if rate_map is None:
            raise ValueError("per-pair width maps ride the rate-map wire; "
                             "pass rate_map alongside width_map")
        _rate_tensor_layers(meta, width_map)   # validate [L, Q, Q] shape
    if resid is not None and not p2p_wire:
        raise ValueError("error-feedback residuals are a p2p-wire feature")
    if store_w and width_map is None:
        raise ValueError("store_w (sub-byte storage) rides the width map; "
                         "pass width_map alongside it (DESIGN.md §3.8)")
    if (fskip is not None or fcache is not None or dead is not None) and \
            not (p2p_wire and rate_map is not None):
        raise ValueError("fault channels (fskip/fcache/dead) ride the "
                         "p2p rate-map wire; pass rate_map with "
                         "wire='p2p' (DESIGN.md §3.10)")
    calls = itertools.count()

    def pair_stats_p2p(publish, pos_all, k_used):
        """Per-pair dropped-block energy: ``k_used [Q, D]`` is the kept
        count governing hop ``(j, d)``, ``pos_all [Q, nb]`` each worker's
        permutation positions."""
        if "p2p_send_slot" not in graph:
            return jnp.zeros((q, q), jnp.float32)
        energy = _pair_hop_energy(publish, graph["p2p_send_slot"],
                                  graph["p2p_send_valid"])   # [Q, D, nb]
        dropped = pos_all[:, None, :] >= k_used[:, :, None]  # [Q, D, nb]
        return _scatter_pairs(jnp.sum(energy * dropped, -1), q)

    @jax.named_scope("halo")
    def start(li, x):                                  # x: [Q, P, F]
        """Issue layer ``li``'s exchange: pack, mask, ship.  Returns
        ``(halo token, bits)`` — the token is consumed by :func:`complete`
        (the only data dependence on the wire)."""
        call = next(calls)
        f = x.shape[-1]
        rm = wm = None
        lix = 0
        if rate_map is not None:
            # select by RANK, not by n_layers: a [1, Q, Q] tensor (1-layer
            # model under a per-layer controller) must still unsqueeze
            rm = rate_map if jnp.ndim(rate_map) == 2 else rate_map[li]
            lix = 0 if n_layers == 1 else li
        if width_map is not None:
            wm = width_map if jnp.ndim(width_map) == 2 else width_map[li]
        if not policy.communicates:                    # No-Comm baseline
            return None, jnp.zeros((2,), jnp.float32)

        if p2p_wire:
            # boundary block [Q, B, F]; a compressing policy packs it once
            # per worker (the real sender's move), the hop buffers are
            # sliced out of the (un)packed rows
            publish = jax.vmap(lambda xq, idx, v: xq[idx] * v[:, None])(
                x, graph["send_idx"], graph["send_valid"])
            bits = None
            if rm is not None:
                nb = f // LANE
                n_keep = _keep_of(f, rate, packed_k)
                k_call = jax.random.fold_in(key, call)
                kept, inv, pos_all = worker_block_maps_pos(k_call, q, nb,
                                                           n_keep)
                pos_kept = jax.vmap(lambda p, kk: p[kk])(pos_all, kept)
                k_pairs = _pair_keep(nb, rm, n_keep)              # [Q, Q]
                jj, rv = _ring_targets(q)
                k_jd = k_pairs[rv, jj]                            # [Q, D]
                packed = jax.vmap(wire_pack)(publish, kept, inv)
                hops = jax.vmap(lambda pk, slots, v:
                                pk[slots] * v[..., None])(
                    packed, graph["p2p_send_slot"],
                    graph["p2p_send_valid"])         # [Q, D, H, K·128]
                cmask = (pos_kept[:, None, :] <
                         k_jd[..., None]).astype(x.dtype)         # [Q, D, K]
                cmask_l = jnp.repeat(cmask, LANE, axis=-1)[:, :, None, :]
                hops = hops * cmask_l
                if wm is not None:
                    w_jd = wm[rv, jj]                             # [Q, D]
                    if resid is not None:
                        # error feedback: pack last step's residual onto
                        # this call's kept set, mask to the pair's live
                        # columns/rows, inject before quantising
                        r_pack = jax.vmap(lambda rq, kk, iv: jax.vmap(
                            lambda r_: wire_pack(r_, kk, iv))(rq))(
                            resid[call], kept, inv)   # [Q, D, H, K·128]
                        r_pack = r_pack * cmask_l * \
                            graph["p2p_send_valid"][..., None]
                        hops = hops + jax.lax.stop_gradient(r_pack)
                    rks = None
                    if rounding == "stochastic":
                        # per-(sender, hop) rounding keys — the exact
                        # streams the shard backend's workers draw from
                        # round_key(key, me, d-1), so both backends
                        # round identically (DESIGN.md §3.8)
                        rks = jax.vmap(lambda j_: jax.vmap(
                            lambda d_: round_key(k_call, j_, d_))(
                            jnp.arange(hops.shape[1])))(jnp.arange(q))
                    if store_w:
                        # sub-byte wire (DESIGN.md §3.8): the hop stack
                        # that would ride the ppermute is the bit-packed
                        # uint8 levels + fp32 scales; the delivered value
                        # is rebuilt from those bytes alone — elementwise
                        # the shard backend's byte hops, with the
                        # gradient passing straight through to the
                        # pre-quantisation rows (its grad carrier)
                        if rks is not None:
                            levels, scales = jax.vmap(jax.vmap(
                                lambda h_, w_, k_: quant_levels(
                                    h_, w_, key=k_)))(hops, w_jd, rks)
                        else:
                            levels, scales = quant_levels(
                                hops, w_jd[:, :, None, None])
                        payload = pack_bits(levels, store_w)
                        if wire_out is not None:
                            wire_out.append((payload, scales))
                        dq = dequant_bits(payload, scales, store_w)
                        hops_q = (hops - jax.lax.stop_gradient(hops)) + \
                            jax.lax.stop_gradient(dq)
                    elif rks is not None:
                        hops_q = jax.vmap(jax.vmap(
                            lambda h_, w_, k_: wire_quant(
                                h_, w_, key=k_)))(hops, w_jd, rks)
                    else:
                        hops_q = wire_quant(hops, w_jd[:, :, None, None])
                    if resid_out is not None:
                        err = jax.lax.stop_gradient(hops - hops_q)
                        resid_out.append(jax.vmap(
                            lambda eq, kk, iv: jax.vmap(
                                lambda e_: wire_unpack(e_, kk, iv))(eq))(
                            err, kept, inv))          # [Q, D, H, F]
                    hops = hops_q
                if wire_out is not None and not (wm is not None and store_w):
                    wire_out.append((hops, None))
                sent = jax.vmap(lambda hp, kk, iv: jax.vmap(
                    lambda h_: wire_unpack(h_, kk, iv))(hp))(
                    hops, kept, inv)                  # [Q, D, H, F]
                pair_err = pair_stats_p2p(publish, pos_all, k_jd)
                pair_delta = jnp.zeros((q, q), jnp.float32)
                live = None
                if cache is not None:
                    c = cache[call]
                    num = jnp.sum((sent - c) ** 2, axis=(-1, -2))
                    den = jnp.sum(sent ** 2, axis=(-1, -2)) + 1e-12
                    pair_delta = _scatter_pairs(num / den, q)
                    sk = skip[rv, jj]                             # [Q, D]
                    sent = jnp.where(sk[..., None, None] > 0.0, c, sent)
                    live = 1.0 - skip
                if fcache is not None:
                    # fault channel: dropped-but-fresh pairs serve the
                    # receiver's cached hop rows (zero wire bits)
                    fsk = fskip[rv, jj]                           # [Q, D]
                    sent = jnp.where(fsk[..., None, None] > 0.0,
                                     fcache[call], sent)
                if fcache_out is not None:
                    fcache_out.append(sent)
                if cache_out is not None:
                    cache_out.append(sent)
                if dead is not None:
                    # past max_stale: the pair ships nothing; its rows
                    # zero out and `complete` renormalises the receiver's
                    # local aggregation (_dead_mix)
                    dd = dead[rv, jj]                             # [Q, D]
                    sent = jnp.where(dd[..., None, None] > 0.0,
                                     jnp.zeros_like(sent), sent)
                live = _fault_live(q, fskip, dead, live)
                row_bits = k_pairs.astype(jnp.float32) * (
                    per_block_wire_bits(wm) if wm is not None
                    else LANE * 32.0)
                bits = _pair_ledger(meta, f, rm, row_bits,
                                    pair_err, pair_delta, live=live,
                                    li=lix, n_layers=n_layers,
                                    width_map=wm)
            else:
                wire_width = None
                if policy.compresses:
                    n_keep = _keep_of(f, rate, packed_k)
                    wire_width = n_keep * LANE
                    k_call = jax.random.fold_in(key, call)
                    kept, inv = worker_block_maps(k_call, q, f // LANE,
                                                  n_keep)
                    packed = jax.vmap(wire_pack)(publish, kept, inv)
                    publish = jax.vmap(wire_unpack)(packed, kept, inv)
                # per-pair hop buffers [Q, D, H, F]
                sent = jax.vmap(lambda pub, slots, v:
                                pub[slots] * v[..., None])(
                    publish, graph["p2p_send_slot"],
                    graph["p2p_send_valid"])
                bits = _exchange_bits(meta, f, rate, wire_width)
            # route: receiver i's hop-d rows come from worker (i - d) mod q
            if q > 1:
                src_w = (jnp.arange(q)[:, None] -
                         jnp.arange(1, q)[None, :]) % q         # [Q, D]
                compact = sent[src_w, jnp.arange(q - 1)[None, :]].reshape(
                    q, meta.p2p_compact, f)
            else:
                compact = jnp.zeros((q, meta.p2p_compact, f), x.dtype)
            return compact, bits

        sent = jax.vmap(lambda xq, idx, v: xq[idx] * v[:, None])(
            x, graph["send_idx"], graph["send_valid"])  # [Q, B, F]
        wire_width = None
        bits = None
        if packed_wire and rm is not None:
            # all-gather wire: one payload serves every receiver, so the
            # map degrades to per-SENDER rates — each sender keeps the max
            # over its receivers' kept counts (serve the most demanding)
            nb = f // LANE
            n_keep = _keep_of(f, rate, packed_k)
            k_call = jax.random.fold_in(key, call)
            kept, inv, pos_all = worker_block_maps_pos(k_call, q, nb, n_keep)
            pos_kept = jax.vmap(lambda p, kk: p[kk])(pos_all, kept)
            k_pairs = _pair_keep(nb, rm, n_keep)
            off = jnp.where(jnp.eye(q, dtype=bool), 0, k_pairs)
            k_send = jnp.maximum(jnp.max(off, axis=0), 1)         # [Q]
            pre = sent
            packed = jax.vmap(wire_pack)(sent, kept, inv)
            cmask = (pos_kept < k_send[:, None]).astype(x.dtype)  # [Q, K]
            packed = packed * jnp.repeat(cmask, LANE, axis=-1)[:, None, :]
            w_send = None
            if wm is not None:
                # one payload per sender: quantise at the max width over
                # its receivers (serve the most demanding), like k_send
                off_w = jnp.where(jnp.eye(q, dtype=bool), 0.0, wm)
                w_send = jnp.max(off_w, axis=0)                   # [Q]
                w_send = jnp.where(w_send > 0.0, w_send, 32.0)
                rks = None
                if rounding == "stochastic":
                    rks = jax.vmap(lambda j_: round_key(k_call, j_))(
                        jnp.arange(q))
                if store_w:
                    # sub-byte wire: the gathered buffer is bit-packed
                    # uint8 levels + fp32 scales (the shard backend's
                    # byte all-gather, elementwise)
                    if rks is not None:
                        levels, scales = jax.vmap(
                            lambda p_, w_, k_: quant_levels(
                                p_, w_, key=k_))(packed, w_send, rks)
                    else:
                        levels, scales = quant_levels(
                            packed, w_send[:, None, None])
                    payload = pack_bits(levels, store_w)
                    if wire_out is not None:
                        wire_out.append((payload, scales))
                    dq = dequant_bits(payload, scales, store_w)
                    packed = (packed - jax.lax.stop_gradient(packed)) + \
                        jax.lax.stop_gradient(dq)
                elif rks is not None:
                    packed = jax.vmap(lambda p_, w_, k_: wire_quant(
                        p_, w_, key=k_))(packed, w_send, rks)
                else:
                    packed = wire_quant(packed, w_send[:, None, None])
            if wire_out is not None and not (wm is not None and store_w):
                wire_out.append((packed, None))
            sent = jax.vmap(wire_unpack)(packed, kept, inv)
            k_jd = jnp.broadcast_to(k_send[:, None], (q, max(q - 1, 1)))
            pair_err = pair_stats_p2p(pre, pos_all, k_jd)
            row_bits = jnp.broadcast_to(
                (k_send.astype(jnp.float32) *
                 (per_block_wire_bits(w_send) if wm is not None
                  else LANE * 32.0))[None, :], (q, q))
            bits = _pair_ledger(meta, f, rm, row_bits, pair_err,
                                jnp.zeros((q, q), jnp.float32),
                                li=lix, n_layers=n_layers,
                                width_map=wm)
        elif packed_wire:
            n_keep = _keep_of(f, rate, packed_k)
            wire_width = n_keep * LANE
            k_call = jax.random.fold_in(key, call)
            kept, inv = worker_block_maps(k_call, q, f // LANE, n_keep)
            packed = jax.vmap(wire_pack)(sent, kept, inv)   # the wire buffer
            sent = jax.vmap(wire_unpack)(packed, kept, inv)
        elif compressor is not None:
            k_call = jax.random.fold_in(key, call)
            keys = jax.vmap(jax.random.fold_in, (None, 0))(
                k_call, jnp.arange(q))
            sent = jax.vmap(lambda k, blk: compressor(k, blk, rate)[0])(
                keys, sent)
        if bits is None:
            bits = _exchange_bits(meta, f, rate, wire_width)
        return sent.reshape(q * b_sz, f), bits

    def complete(li, x, token):
        """Consume layer ``li``'s delivered halo: local aggregation (ELL
        on the p2p wire — scheduled while the exchange is in flight) plus
        the remote scatter out of the token."""
        del li
        f = x.shape[-1]
        if not policy.communicates:                    # No-Comm baseline
            with jax.named_scope("local_agg"):
                return _per_part(lambda xq, ld, ls, w:
                                 jnp.zeros((p_sz + 1, f), x.dtype)
                                 .at[ld].add(w[:, None] * xq[ls])[:p_sz],
                                 x, graph["local_dst"], graph["local_src"],
                                 graph["local_w_iso"])

        if p2p_wire:
            with jax.named_scope("local_agg"):
                ell_w = _ell_w_for(graph, policy, rate)
                if dead is not None:
                    # local-only fallback: blend each receiver's
                    # aggregation weights toward the isolated normalisation
                    # by its dark remote-row fraction (all pairs dead →
                    # exact No-Comm)
                    mix = _dead_mix(meta, dead)
                    ell_w = ell_w + mix[:, None, None] * \
                        (graph["ell_w_iso"] - ell_w)

            def part_p2p(xq, nbr, w, rnbr, rslot, rd, rs, rw, halo_c):
                with jax.named_scope("local_agg"):
                    loc = ell_aggregate(xq, nbr, w, rnbr, rslot)
                with jax.named_scope("remote_agg"):
                    rem = jnp.zeros((p_sz + 1, f), x.dtype)
                    rem = rem.at[rd].add(rw[:, None] * halo_c[rs])
                return loc + rem[:p_sz]

            return _per_part(
                part_p2p, x, graph["ell_nbr"], ell_w, graph["ell_rnbr"],
                graph["ell_rslot"], graph["remote_dst"],
                graph["remote_src_p2p"], graph["remote_w"], token)

        halo = token
        with jax.named_scope("local_agg"):
            local_w = _local_w_for(graph, policy, rate)

        def part(xq, ld, ls, lw, rd, rs, rw):
            with jax.named_scope("local_agg"):
                out = jnp.zeros((p_sz + 1, f), x.dtype)
                out = out.at[ld].add(lw[:, None] * xq[ls])
            with jax.named_scope("remote_agg"):
                out = out.at[rd].add(rw[:, None] * halo[rs])
            return out[:p_sz]

        return _per_part(
            part, x, graph["local_dst"], graph["local_src"], local_w,
            graph["remote_dst"], graph["remote_src"], graph["remote_w"])

    def aggregate(li, x):
        token, bits = start(li, x)
        return complete(li, x, token), bits

    aggregate.start = start
    aggregate.complete = complete
    return aggregate


def _make_aggregate_shard(graph: dict, meta: DistMeta, policy: CommPolicy,
                          compressor: Compressor | None, rate, key,
                          axis: str = AXIS, packed_k: dict | None = None,
                          rate_map=None, width_map=None,
                          resid=None, resid_out: list | None = None,
                          fskip=None, fcache=None,
                          fcache_out: list | None = None, dead=None,
                          rounding: str = "rint", store_w: int = 0,
                          wire_out: list | None = None):
    """AggregateFn for one worker inside ``shard_map`` (blocks ``[1, P, F]``).

    Dense wire: :func:`compressed_all_gather` (or a plain all-gather at full
    communication).  Packed wire: :func:`packed_all_gather`, which ships the
    ``[B, K·128]`` lane-block payload.  P2P wire:
    :func:`repro.core.collectives.neighbor_exchange` — ``Q - 1`` ppermute
    hops carrying only the per-pair halo rows, with the local edges on the
    :func:`repro.kernels.ops.ell_aggregate` row gathers so XLA can overlap
    the hops with the local compute.  The per-worker masks derive from the
    same ``fold_in`` streams as the emulated path, so both backends agree
    bitwise.

    ``rate_map`` (traced ``[Q, Q]``, replicated to every worker) switches
    the packed and p2p wires to per-pair rates exactly as in
    :func:`_make_aggregate_emulated`: the collectives mask their packed
    columns with the nested per-pair kept sets, the per-pair error stats
    are all-gathered from each sender, and the returned ledger vector is
    the same ``2 + 3·Q²`` layout (pair staleness deltas stay zero — hop
    reuse is an emulated-backend feature).  A per-layer ``[L, Q, Q]``
    tensor selects row ``li`` per exchange and grows the ledger to
    ``2 + 3·L·Q²``, mirroring the emulated backend bit for bit
    (DESIGN.md §3.7).

    ``width_map`` threads the per-pair bit-widths into the collectives'
    ``pair_w`` channel (DESIGN.md §3.8): ``neighbor_exchange_start``
    quantises each hop at its exact per-pair width,
    ``packed_all_gather`` at each sender's receiver-max — the same
    sender-side arithmetic as the emulated backend, so mixed rate × width
    maps stay bitwise-parity across backends.

    ``resid``/``resid_out`` thread the error-feedback residual channel
    (p2p rate-map wire with ``width_map``): ``resid`` is the tuple of
    per-exchange-call residual *blocks* ``[1, D, H, F]`` (the worker's
    slice of the sender-major ``[Q, D, H, F]`` state, sharded over the
    worker axis) and each call appends its fresh quantisation error —
    same layout — to ``resid_out``, mirroring the emulated backend's EF
    arithmetic step for step so the two backends' residual caches stay
    ≤ 1e-6 apart (tests/parity.py's EF train parity).  ``rounding``
    selects the quantiser's rounding mode ("rint" | "stochastic") with
    the per-(sender, hop) key schedule of
    :func:`repro.kernels.ops.round_key` on both backends.

    ``fskip``/``fcache``/``fcache_out``/``dead`` are the fault channel
    (DESIGN.md §3.10; p2p rate-map wire only), applied RECEIVER-side
    after ``neighbor_exchange_finish``: the SPMD ``ppermute`` still
    executes shape-uniformly (a fault means delivery failed, not that the
    hop was never scheduled), but the receiver discards the dropped
    pair's rows in favour of ``fcache[call][0]`` (its ``[1, D, H, F]``
    receiver-major cache block, sharded over the worker axis) or zeros
    (dead pairs), and the ledger's ``live`` mask zeroes both charges —
    the same pair arithmetic as the emulated backend, so fault runs stay
    in the parity matrix.

    ``store_w`` (static, from :func:`_packed_store_w`) forwards into the
    byte-storage channel of :func:`neighbor_exchange_start` /
    :func:`packed_all_gather`: the collective physically carries the
    bit-packed uint8 levels + fp32 scales instead of fp32 lanes
    (DESIGN.md §3.8).  ``wire_out``, when a list, captures this worker's
    shipped ``(payload, scales)`` buffers per rate-map exchange — the
    caller must return them out of ``shard_map`` to observe them (the
    conservation tests do).

    Carries the same ``start``/``complete`` split-phase attributes as the
    emulated oracle; on this backend ``start`` ends at the ``ppermute``
    (``neighbor_exchange_start``) and ``complete`` begins at the unpack
    (``neighbor_exchange_finish``), so the hops genuinely overlap the ELL
    local aggregation under XLA's async collective scheduling.
    """
    p_sz, b_sz, q = meta.part_size, meta.halo_size, meta.q
    packed_wire = meta.wire == "packed"
    p2p_wire = meta.wire == "p2p"
    if rate_map is not None and not (packed_wire or p2p_wire):
        raise ValueError("per-pair rate maps need wire='packed' or 'p2p'; "
                         "the dense wire keeps the scalar path")
    n_layers = _rate_tensor_layers(meta, rate_map)
    if width_map is not None:
        if rate_map is None:
            raise ValueError("per-pair width maps ride the rate-map wire; "
                             "pass rate_map alongside width_map")
        _rate_tensor_layers(meta, width_map)   # validate [L, Q, Q] shape
    if (fskip is not None or fcache is not None or dead is not None) and \
            not (p2p_wire and rate_map is not None):
        raise ValueError("fault channels (fskip/fcache/dead) ride the "
                         "p2p rate-map wire; pass rate_map with "
                         "wire='p2p' (DESIGN.md §3.10)")
    if resid is not None and not (p2p_wire and width_map is not None):
        raise ValueError("error-feedback residuals ride the quantised "
                         "p2p wire; pass width_map with wire='p2p' "
                         "(DESIGN.md §3.8)")
    if store_w and width_map is None:
        raise ValueError("store_w (sub-byte storage) rides the width map; "
                         "pass width_map alongside it (DESIGN.md §3.8)")
    calls = itertools.count()

    def pair_err_shard(publish_pre, pos_me, k_d):
        """Sender-side dropped-block energy per hop, all-gathered into the
        replicated ``[Q, Q]`` pair matrix (same arithmetic as the emulated
        ``pair_stats_p2p``)."""
        nb = publish_pre.shape[-1] // LANE
        be = jnp.sum(publish_pre.reshape(-1, nb, LANE).astype(jnp.float32)
                     ** 2, axis=-1)                        # [B, nb]
        slot = graph["p2p_send_slot"][0]
        val = graph["p2p_send_valid"][0]
        energy = jnp.sum(be[slot] * val[..., None], axis=1)    # [D, nb]
        dropped = pos_me[None, :] >= k_d[:, None]              # [D, nb]
        err_d = jnp.sum(energy * dropped, -1)                  # [D]
        return _scatter_pairs(lax.all_gather(err_d, axis), q)

    @jax.named_scope("halo")
    def start(li, x):                                  # x: [1, P, F]
        """Issue layer ``li``'s exchange on this worker.  P2P tokens stop
        at the ``ppermute`` (packed hop rows, no unpack); all-gather
        tokens carry the decoded halo buffer."""
        call = next(calls)
        f = x.shape[-1]
        rm = wm = None
        lix = 0
        if rate_map is not None:
            # select by RANK, not by n_layers (see the emulated backend)
            rm = rate_map if jnp.ndim(rate_map) == 2 else rate_map[li]
            lix = 0 if n_layers == 1 else li
        if width_map is not None:
            wm = width_map if jnp.ndim(width_map) == 2 else width_map[li]
        if not policy.communicates:
            return None, jnp.zeros((2,), jnp.float32)
        xq = x[0]

        if p2p_wire:
            publish = xq[graph["send_idx"][0]] * \
                graph["send_valid"][0][:, None]
            if rm is not None:
                nb = f // LANE
                n_keep = _keep_of(f, rate, packed_k)
                k_call = jax.random.fold_in(key, call)
                k_pairs = _pair_keep(nb, rm, n_keep)
                r_out: list = []
                hops, _ = neighbor_exchange_start(
                    publish, graph["p2p_send_slot"][0],
                    graph["p2p_send_valid"][0], axis, key=k_call,
                    n_keep=n_keep, pair_k=k_pairs, pair_w=wm,
                    resid=None if resid is None else resid[call][0],
                    resid_out=r_out if resid is not None else None,
                    rounding=rounding, store_w=store_w if wm is not None
                    else 0, wire_out=wire_out)
                if resid is not None and resid_out is not None:
                    # [1, D, H, F] block — P(AXIS) out_spec stacks the
                    # workers back into the sender-major [Q, D, H, F]
                    resid_out.append(r_out[0][None] if r_out
                                     else resid[call])
                me = lax.axis_index(axis)
                _, _, pos_all = worker_block_maps_pos(k_call, q, nb, n_keep)
                k_d = k_pairs[(me + jnp.arange(1, max(q, 2))) % q, me]
                pair_err = pair_err_shard(publish, pos_all[me], k_d)
                row_bits = k_pairs.astype(jnp.float32) * (
                    per_block_wire_bits(wm) if wm is not None
                    else LANE * 32.0)
                bits = _pair_ledger(meta, f, rm, row_bits,
                                    pair_err,
                                    jnp.zeros((q, q), jnp.float32),
                                    live=_fault_live(q, fskip, dead, None),
                                    li=lix, n_layers=n_layers,
                                    width_map=wm)
            else:
                n_keep = wire_width = k_call = None
                if policy.compresses:
                    n_keep = _keep_of(f, rate, packed_k)
                    wire_width = n_keep * LANE
                    k_call = jax.random.fold_in(key, call)
                hops, _ = neighbor_exchange_start(
                    publish, graph["p2p_send_slot"][0],
                    graph["p2p_send_valid"][0], axis, key=k_call,
                    n_keep=n_keep)
                bits = _exchange_bits(meta, f, rate, wire_width)
            return (hops, k_call, n_keep, call), bits

        sent = xq[graph["send_idx"][0]] * graph["send_valid"][0][:, None]
        wire_width = None
        bits = None
        if packed_wire and rm is not None:
            nb = f // LANE
            n_keep = _keep_of(f, rate, packed_k)
            k_call = jax.random.fold_in(key, call)
            k_pairs = _pair_keep(nb, rm, n_keep)
            halo, _ = packed_all_gather(sent, axis, n_keep=n_keep,
                                        key=k_call, pair_k=k_pairs,
                                        pair_w=wm, rounding=rounding,
                                        store_w=store_w if wm is not None
                                        else 0, wire_out=wire_out)
            off = jnp.where(jnp.eye(q, dtype=bool), 0, k_pairs)
            k_send = jnp.maximum(jnp.max(off, axis=0), 1)
            me = lax.axis_index(axis)
            _, _, pos_all = worker_block_maps_pos(k_call, q, nb, n_keep)
            pair_err = jnp.zeros((q, q), jnp.float32)
            if "p2p_send_slot" in graph:
                k_d = jnp.broadcast_to(k_send[me], (max(q - 1, 1),))
                pair_err = pair_err_shard(sent, pos_all[me], k_d)
            w_send = None
            if wm is not None:
                off_w = jnp.where(jnp.eye(q, dtype=bool), 0.0, wm)
                w_send = jnp.max(off_w, axis=0)
                w_send = jnp.where(w_send > 0.0, w_send, 32.0)
            row_bits = jnp.broadcast_to(
                (k_send.astype(jnp.float32) *
                 (per_block_wire_bits(w_send) if wm is not None
                  else LANE * 32.0))[None, :], (q, q))
            bits = _pair_ledger(meta, f, rm, row_bits, pair_err,
                                jnp.zeros((q, q), jnp.float32),
                                li=lix, n_layers=n_layers,
                                width_map=wm)
        elif packed_wire:
            n_keep = _keep_of(f, rate, packed_k)
            wire_width = n_keep * LANE
            k_call = jax.random.fold_in(key, call)
            halo, _ = packed_all_gather(sent, axis, n_keep=n_keep,
                                        key=k_call)
        elif compressor is not None:
            k_call = jax.random.fold_in(key, call)
            halo, _ = compressed_all_gather(sent, axis, compressor=compressor,
                                            rate=rate, key=k_call)
        else:
            halo = lax.all_gather(sent, axis)          # [Q, B, F]
        if bits is None:
            bits = _exchange_bits(meta, f, rate, wire_width)
        return halo.reshape(q * b_sz, f), bits

    def complete(li, x, token):
        del li
        f = x.shape[-1]
        xq = x[0]
        if not policy.communicates:
            with jax.named_scope("local_agg"):
                out = jnp.zeros((p_sz + 1, f), x.dtype)
                out = out.at[graph["local_dst"][0]].add(
                    graph["local_w_iso"][0][:, None] *
                    xq[graph["local_src"][0]])
                return out[:p_sz][None]

        if p2p_wire:
            hops, k_call, n_keep, call = token
            with jax.named_scope("local_agg"):
                ell_w = _ell_w_for(graph, policy, rate)[0]
                if dead is not None:
                    me = lax.axis_index(axis)
                    mix = _dead_mix(meta, dead)[me]
                    ell_w = ell_w + mix * (graph["ell_w_iso"][0] - ell_w)
                loc = ell_aggregate(xq, graph["ell_nbr"][0], ell_w,
                                    graph["ell_rnbr"][0],
                                    graph["ell_rslot"][0])
            with jax.named_scope("halo"):
                halo = neighbor_exchange_finish(hops, axis, key=k_call,
                                                n_keep=n_keep, f=f)
                if q > 1 and (fcache is not None or dead is not None):
                    # receiver-side fault service: hop d's rows came from
                    # sender (me - d) mod q; substitute the cached block
                    # for CACHED pairs, zeros for DEAD ones
                    # (emulated-identical)
                    me = lax.axis_index(axis)
                    src = (me - jnp.arange(1, q)) % q              # [D]
                    hal3 = halo.reshape(q - 1, -1, f)
                    if fcache is not None:
                        fsk = fskip[me, src]
                        hal3 = jnp.where(fsk[:, None, None] > 0.0,
                                         fcache[call][0], hal3)
                    if fcache_out is not None:
                        fcache_out.append(hal3[None])
                    if dead is not None:
                        dd = dead[me, src]
                        hal3 = jnp.where(dd[:, None, None] > 0.0,
                                         jnp.zeros_like(hal3), hal3)
                    halo = hal3.reshape(-1, f)
            with jax.named_scope("remote_agg"):
                rem = jnp.zeros((p_sz + 1, f), x.dtype)
                rem = rem.at[graph["remote_dst"][0]].add(
                    graph["remote_w"][0][:, None] *
                    halo[graph["remote_src_p2p"][0]])
            return (loc + rem[:p_sz])[None]

        halo = token
        with jax.named_scope("local_agg"):
            out = jnp.zeros((p_sz + 1, f), x.dtype)
            out = out.at[graph["local_dst"][0]].add(
                _local_w_for(graph, policy, rate)[0][:, None] *
                xq[graph["local_src"][0]])
        with jax.named_scope("remote_agg"):
            out = out.at[graph["remote_dst"][0]].add(
                graph["remote_w"][0][:, None] * halo[graph["remote_src"][0]])
        return out[:p_sz][None]

    def aggregate(li, x):
        token, bits = start(li, x)
        return complete(li, x, token), bits

    aggregate.start = start
    aggregate.complete = complete
    return aggregate


# ---------------------------------------------------------------------------
# Loss / steps
# ---------------------------------------------------------------------------


def _local_loss_fn(params, cfg: GNNConfig, graph: dict, aggregate,
                   meta: DistMeta, psum: bool = False):
    """Masked CE over owned train nodes, normalised by the GLOBAL count.

    With the global normalisation, ``psum(per-worker grads)`` equals the full
    centralized gradient — the identity the grad-sync mode relies on.
    Returns ``(loss, forward wire bits)``.
    """
    logits, bits = gnn_forward(params, cfg, graph["features"], aggregate)
    loss_sum, _ = masked_loss_and_correct(logits, graph["labels"],
                                          graph["train_mask"])
    if psum:
        loss_sum = lax.psum(loss_sum, AXIS)
    return loss_sum / max(meta.n_train, 1), bits


@jax.named_scope("grad_sync")
def _pmean_inexact(tree, axis: str):
    """FedAvg server step: average float state, keep integer state local."""
    return jax.tree_util.tree_map(
        lambda t: lax.pmean(t, axis)
        if jnp.issubdtype(t.dtype, jnp.inexact) else t, tree)


@jax.named_scope("grad_sync")
def _psum_tree(tree, axis: str):
    """Gradient sync: sum every leaf over the workers."""
    return jax.tree_util.tree_map(lambda t: lax.psum(t, axis), tree)


def _step_metrics(loss, rate, bits) -> dict:
    """Common step metrics: ``bits`` is the forward ``[analytic, transport]``
    pair; a train step ships it twice (activations + cotangents)."""
    return {"loss": loss, "rate": jnp.asarray(rate, jnp.float32),
            "halo_bits": 2.0 * bits[0], "transport_bits": 2.0 * bits[1]}


def make_train_step(cfg: GNNConfig, policy: CommPolicy, opt: Optimizer,
                    meta: DistMeta, mesh: Mesh | None = None,
                    sync: str = "grad",
                    compiled_cache_size: int = COMPILED_CACHE_SIZE):
    """One full-batch step of Algorithm 1.

    ``step(params, opt_state, graph, step_idx, key)`` ->
    ``(params, opt_state, {loss, rate, halo_bits, transport_bits})``.

    ``mesh=None`` runs the single-device emulation over ``[Q, ...]`` stacks;
    with a ``workers`` mesh the same program runs under ``shard_map`` with
    real collectives.  ``sync``: ``'grad'`` psums gradients (exact
    centralized step), ``'fedavg'`` applies local updates then averages
    parameters (Algorithm 1's server step).

    ``meta.wire == "packed"`` runs the reduced-volume packed halo exchange;
    ``"p2p"`` the neighbor-only ppermute ring with ELL local aggregation
    (DESIGN.md §3.5; the graph pytree must carry the
    ``repro.dist.halo.attach_p2p`` arrays).  On both, a compressed payload's
    shape depends only on the kept-block counts, so each call quantises the
    schedule's rate to that static map outside jit (:func:`_packed_k_for`)
    while the rate itself stays a traced operand — a continuously-annealing
    VARCO schedule recompiles once per distinct kept-block map (at most
    ``Π (width/128)`` times, a handful), not per rate value.  A compressing
    policy must then use the ``blockmask`` compressor (these wires realise
    exactly that mechanism).  On the shard_map path the compiled
    executables live in an LRU cache of ``compiled_cache_size`` entries
    (exposed as ``step.cache_info`` / ``step.cache_clear``), so annealing
    across many maps evicts old executables instead of pinning every one
    forever.

    Example::

        step = make_train_step(cfg, varco(300, compressor="blockmask"),
                               adamw(5e-3), meta, mesh=None)
        params, opt_state, m = step(params, opt_state, graph, 0,
                                    jax.random.key(0))
    """
    if sync not in ("grad", "fedavg"):
        raise ValueError(f"sync must be 'grad' or 'fedavg', got {sync!r}")
    if policy.mode == "auto":
        raise ValueError(
            "auto policies plan per-pair rate maps closed-loop; build the "
            "step with repro.dist.ratectl.make_auto_train_step (train_gnn "
            "routes there automatically)")
    packed_wire = meta.wire == "packed"
    p2p_wire = meta.wire == "p2p"
    if (packed_wire or p2p_wire) and policy.compresses and \
            policy.compressor_name != "blockmask":
        raise ValueError(
            f"the {meta.wire} wire ships PRNG-selected lane-blocks; a "
            f"compressing policy must use the 'blockmask' compressor, got "
            f"{policy.compressor_name!r}")
    if p2p_wire and policy.compresses:
        for f_ in {meta.feat_dim, *meta.layer_dims}:
            if f_ % LANE:
                raise ValueError(
                    f"the p2p wire packs lane-blocks under a compressing "
                    f"policy, so every exchanged feature width must be "
                    f"divisible by {LANE}; got {f_} (exchanged widths: "
                    f"{sorted({meta.feat_dim, *meta.layer_dims})})")
    # a static kept-block map is needed whenever the wire payload shape
    # follows the rate: always on the packed wire, under compression on p2p
    needs_kb = packed_wire or (p2p_wire and policy.compresses)
    compressor = policy.compressor() if policy.compresses else None

    if mesh is None:
        @functools.partial(jax.jit, static_argnames=("packed_k",))
        def _jit_step(params, opt_state, graph, step_idx, key,
                      packed_k=None):
            rate = policy.rate(step_idx)

            def loss_fn(p):
                agg = _make_aggregate_emulated(
                    graph, meta, policy, compressor, rate, key,
                    packed_k=dict(packed_k) if packed_k else None)
                return _local_loss_fn(p, cfg, graph, agg, meta)

            (loss, bits), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, new_state = opt.update(grads, opt_state, params)
            new_params = apply_updates(params, updates)
            return new_params, new_state, _step_metrics(loss, rate, bits)

        if not needs_kb:
            return _jit_step

        def step(params, opt_state, graph, step_idx, key):
            kb = _packed_k_for(meta, float(policy.rate(int(step_idx))))
            return _jit_step(params, opt_state, graph, step_idx, key,
                             packed_k=kb)

        return step

    def make_worker(packed_k: dict | None):
        def worker(params, opt_state, gblk, rate, key):
            def loss_fn(p):
                agg = _make_aggregate_shard(gblk, meta, policy, compressor,
                                            rate, key, packed_k=packed_k)
                return _local_loss_fn(p, cfg, gblk, agg, meta)

            (loss, bits), grads = jax.value_and_grad(loss_fn,
                                                     has_aux=True)(params)
            loss = _psum_tree(loss, AXIS)
            if sync == "grad":
                grads = _psum_tree(grads, AXIS)
                updates, new_state = opt.update(grads, opt_state, params)
                params = apply_updates(params, updates)
            else:  # fedavg: local step, then parameter averaging
                updates, new_state = opt.update(grads, opt_state, params)
                params = apply_updates(params, updates)
                params = _pmean_inexact(params, AXIS)
                new_state = _pmean_inexact(new_state, AXIS)
            return params, new_state, _step_metrics(loss, rate, bits)

        return worker

    def make_sm(packed_k: dict | None):
        return jax.jit(shard_map(make_worker(packed_k), mesh=mesh,
                                 in_specs=(P(), P(), P(AXIS), P(), P()),
                                 out_specs=(P(), P(), P()), check_vma=False))

    if needs_kb:
        # bounded: an annealing schedule walks many kept-block maps; keep
        # the recent executables, evict the rest (regression-pinned by
        # tests/test_p2p_wire.py::test_compiled_cache_bounded)
        @functools.lru_cache(maxsize=compiled_cache_size)
        def _compiled_for(kblocks: tuple):
            return make_sm(dict(kblocks))

        def step(params, opt_state, graph, step_idx, key):
            kb = _packed_k_for(meta, float(policy.rate(int(step_idx))))
            return _compiled_for(kb)(params, opt_state, graph,
                                     policy.rate(step_idx), key)

        step.cache_info = _compiled_for.cache_info
        step.cache_clear = _compiled_for.cache_clear
        return step

    sm = make_sm(None)

    @jax.jit
    def step(params, opt_state, graph, step_idx, key):
        return sm(params, opt_state, graph, policy.rate(step_idx), key)

    return step


def make_eval_step(cfg: GNNConfig, meta: DistMeta, mesh: Mesh | None = None):
    """Full-communication accuracy over the train/val/test splits.

    ``evaluate(params, graph) -> {"train": acc, "val": acc, "test": acc}``.
    Always evaluates over the dense wire: at rate 1 the packed exchange
    keeps every lane-block, so the two formats are bitwise identical and
    the dense path avoids the packed wire's static-rate bookkeeping.

    Example::

        evaluate = make_eval_step(cfg, meta)
        accs = evaluate(params, graph)      # graph from pg.device_arrays()
    """
    meta = dataclasses.replace(meta, wire="dense")
    splits = (("train", "train_mask", meta.n_train),
              ("val", "val_mask", meta.n_val),
              ("test", "test_mask", meta.n_test))

    def _accs(logits, gblk, reduce_psum: bool):
        pred = jnp.argmax(logits, -1)
        out = {}
        for name, mask_key, n in splits:
            correct = jnp.sum((pred == gblk["labels"]) *
                              gblk[mask_key].astype(jnp.float32))
            if reduce_psum:
                correct = lax.psum(correct, AXIS)
            out[name] = correct / max(n, 1)
        return out

    if mesh is None:
        @jax.jit
        @jax.named_scope("eval")
        def evaluate(params, graph):
            agg = _make_aggregate_emulated(graph, meta, FULL_COMM, None,
                                           jnp.ones((), jnp.float32),
                                           jax.random.key(0))
            logits, _ = gnn_forward(params, cfg, graph["features"], agg)
            return _accs(logits, graph, reduce_psum=False)

        return evaluate

    def worker(params, gblk):
        agg = _make_aggregate_shard(gblk, meta, FULL_COMM, None,
                                    jnp.ones((), jnp.float32),
                                    jax.random.key(0))
        logits, _ = gnn_forward(params, cfg, gblk["features"], agg)
        return _accs(logits, gblk, reduce_psum=True)

    sm = shard_map(worker, mesh=mesh, in_specs=(P(), P(AXIS)),
                   out_specs=P(), check_vma=False)
    # the scope wraps shard_map, so the constants it hoists are named too
    return jax.jit(jax.named_scope("eval")(sm))


def make_infer_step(cfg: GNNConfig, policy: CommPolicy, meta: DistMeta,
                    rounding: str = "rint"):
    """Inference-only distributed forward for the serving runtime
    (DESIGN.md §3.11; emulated backend, p2p wire).

    ``infer(params, graph, key, plan, cache=()) -> (logits, hiddens,
    metrics, cache')`` — the serving analogue of
    ``repro.dist.ratectl.driver.make_auto_train_step`` with the grad
    plumbing skipped: no ``value_and_grad``, no optimizer, no cotangent
    traffic, and the forward still runs the split-phase ``start`` /
    ``complete`` oracle so the halo hops overlap the self-term matmuls
    exactly as in training.

    * ``plan`` is a :class:`repro.dist.ratectl.base.RatePlan`; its
      ``skip`` mask and the ``cache`` tuple (``init_halo_cache`` shapes)
      are the drift-gated halo service: a skipped pair's hop is served
      from ``cache`` at zero wire bits, the fresh buffers land in
      ``cache'``, and ``metrics["pair_delta"]`` carries the measured
      relative drift the gate (``repro.dist.ratectl.stale.drift_skip``)
      consumes.
    * ``hiddens`` is the tuple of every layer's post-activation output
      ``[Q, P, F_l]`` (the last entry is ``logits``) — the payload the
      serving embedding cache stores per (layer, node-block).
    * ``metrics`` charges the wire ONE WAY (``halo_bits`` /
      ``transport_bits`` / ``pair_transport`` are forward-only — no
      backward cotangents at inference), unlike the train step's doubled
      charges.

    Example::

        infer = make_infer_step(cfg, policy, meta)
        logits, hid, m, cache = infer(params, graph, key, plan, cache)
    """
    if policy.mode != "auto":
        raise ValueError(f"make_infer_step needs an 'auto' policy, got "
                         f"mode {policy.mode!r}")
    if meta.wire != "p2p":
        raise ValueError("the serving forward reuses the stale "
                         "controller's hop caches; it needs wire='p2p', "
                         f"got {meta.wire!r}")
    for f_ in {meta.feat_dim, *meta.layer_dims}:
        if f_ % LANE:
            raise ValueError(
                f"per-pair rate maps pack lane-blocks; every exchanged "
                f"width must be divisible by {LANE}, got {f_}")
    reps = 1 if cfg.conv == "sage" else max(cfg.k_taps - 1, 1)
    n_ex = cfg.layers * reps
    q = meta.q

    @functools.partial(jax.jit,
                       static_argnames=("packed_k", "wire_w", "store_w"))
    def _jit_infer(params, graph, key, rate_map, width_map, skip, cache,
                   packed_k, wire_w, store_w=0):
        cache_out: list = []
        hidden: list = []
        agg = _make_aggregate_emulated(
            graph, meta, policy, None, jnp.ones((), jnp.float32), key,
            packed_k=dict(packed_k), rate_map=rate_map,
            skip=skip if cache else None,
            cache=cache if cache else None,
            cache_out=cache_out if cache else None,
            width_map=width_map if wire_w else None,
            rounding=rounding, store_w=store_w if wire_w else 0)
        logits, bits = gnn_forward(params, cfg, graph["features"], agg,
                                   hidden_out=hidden)
        return logits, tuple(hidden), bits, tuple(cache_out)

    def infer(params, graph, key, plan, cache=()):
        rm, kb, wm, ww, sw = plan_statics(meta, plan)
        logits, hidden, bits, cache_new = _jit_infer(
            params, graph, key, jnp.asarray(rm),
            jnp.zeros((), jnp.float32) if wm is None else jnp.asarray(wm),
            jnp.asarray(plan.skip, jnp.float32), tuple(cache),
            packed_k=kb, wire_w=ww, store_w=sw)
        n_layers = 1 if rm.ndim == 2 else rm.shape[0]
        q2, lq2 = q * q, (1 if rm.ndim == 2 else rm.shape[0]) * q * q
        layer_t = bits[2:2 + lq2].reshape(n_layers, q, q)
        layer_e = bits[2 + lq2:2 + 2 * lq2].reshape(n_layers, q, q)
        layer_d = bits[2 + 2 * lq2:2 + 3 * lq2].reshape(n_layers, q, q)
        metrics = {"halo_bits": bits[0], "transport_bits": bits[1],
                   "pair_transport": jnp.sum(layer_t, axis=0),
                   "pair_err": jnp.sum(layer_e, axis=0),
                   "pair_delta": jnp.sum(layer_d, axis=0) / max(n_ex, 1)}
        return logits, hidden, metrics, cache_new

    infer._jit_infer = _jit_infer
    return infer
