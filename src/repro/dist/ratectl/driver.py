"""Trainer integration: auto policies → controller + per-pair train step.

``CommPolicy.parse("auto:<controller>:<budget-bits>")`` names a closed
loop; this module turns it into running machinery:

* :func:`make_controller` — instantiate the named controller with the
  shared budget pacing built from the partition facts;
* :func:`make_auto_train_step` — the per-pair-rate analogue of
  ``repro.dist.gnn_parallel.make_train_step``: same Algorithm-1 step, but
  the compression operand is a traced ``[Q, Q]`` rate map (+ skip mask
  and halo cache for the ``stale`` controller) planned by the controller
  each step.  The step quantises the concrete map to its static
  kept-block maximum per width (`_packed_pair_k_for`) outside jit —
  bounded recompiles, exactly the scalar wires' contract — and the
  shard_map executables sit behind the same LRU cache.

The loop a trainer runs (``repro.train.trainer.train_gnn`` does this):

    ctl = make_controller(policy, meta, cfg, total_steps)
    state, cache = ctl.init(), init_halo_cache(meta, cfg)
    step = make_auto_train_step(cfg, policy, opt, meta, mesh=mesh)
    for t in range(total_steps):
        plan, state = ctl.plan(state, t)
        params, opt_state, m, cache = step(params, opt_state, graph,
                                           key_t, plan, cache)
        state = ctl.observe(state, m)

``observe`` reads the metrics directly: the step returns
``pair_transport`` / ``pair_err`` / ``pair_delta`` ``[Q, Q]`` matrices
next to the usual scalars (History's per-pair transport columns come from
the same place).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.varco import CommPolicy
from repro.dist.gnn_parallel import (AXIS, COMPILED_CACHE_SIZE, DistMeta,
                                     _local_loss_fn, _make_aggregate_emulated,
                                     _make_aggregate_shard, _pmean_inexact,
                                     _psum_tree, plan_statics)
from repro.dist.ratectl.base import RateController, RatePlan, make_pacing
from repro.dist.ratectl.budget import budget_controller
from repro.dist.ratectl.error import error_controller
from repro.dist.ratectl.qos import qos_controller
from repro.dist.ratectl.stale import stale_controller
from repro.kernels.ops import default_wire_rounding
from repro.kernels.varco_pack import LANE
from repro.nn.gnn import GNNConfig, gnn_forward, masked_loss_and_correct
from repro.train.optim import Optimizer, apply_updates


def exchange_widths(cfg: GNNConfig) -> tuple[int, ...]:
    """Feature width of every halo exchange in one forward pass: each
    layer's input width, once per exchange call (sage: one per layer;
    poly: ``k_taps - 1`` per layer) — the controllers' transport model and
    the ``stale`` cache's buffer widths."""
    dims = [cfg.in_dim] + [cfg.hidden] * (cfg.layers - 1)
    reps = 1 if cfg.conv == "sage" else max(cfg.k_taps - 1, 1)
    return tuple(d for d in dims for _ in range(reps))


def layer_exchange_widths(cfg: GNNConfig) -> tuple[int, ...]:
    """Summed exchange width of each model *layer* (``[L]``): layer
    ``l``'s input width times its exchange count (sage 1, poly
    ``k_taps - 1``).  Sums to ``sum(exchange_widths(cfg))`` — the
    per-layer split of the controllers' transport model
    (``Pacing.layer_bits``, DESIGN.md §3.7)."""
    dims = [cfg.in_dim] + [cfg.hidden] * (cfg.layers - 1)
    reps = 1 if cfg.conv == "sage" else max(cfg.k_taps - 1, 1)
    return tuple(d * reps for d in dims)


def make_controller(policy: CommPolicy, meta: DistMeta, cfg: GNNConfig,
                    total_steps: int, **overrides) -> RateController:
    """Instantiate ``policy.controller`` with pacing scaled to
    ``policy.budget_bits`` over ``total_steps``.

    ``overrides`` pass through to :func:`repro.dist.ratectl.base.
    make_pacing` (``c_max``, ``slope``, ``kp``, ``ki``, ...) and to the
    controller factory (``threshold``/``max_stale`` for ``stale``,
    ``ema_decay`` for ``error``).

    Example::

        policy = CommPolicy.parse("auto:budget:2e9", epochs)
        ctl = make_controller(policy, meta, cfg, epochs)
    """
    if policy.mode != "auto":
        raise ValueError(f"policy mode must be 'auto', got {policy.mode!r}")
    ctl_kw = {k: overrides.pop(k) for k in ("threshold", "max_stale",
                                            "ema_decay")
              if k in overrides}
    per_layer = policy.per_layer
    pacing = make_pacing(meta, exchange_widths(cfg), total_steps,
                         policy.budget_bits,
                         layer_widths=layer_exchange_widths(cfg)
                         if per_layer else None,
                         **overrides)
    if policy.controller != "stale":
        bad = sorted(k for k in ("threshold", "max_stale") if k in ctl_kw)
        if bad:
            raise ValueError(
                f"{'/'.join(bad)} are stale-controller knobs; the "
                f"{policy.controller!r} controller does not accept them")
    if "ema_decay" in ctl_kw and policy.controller not in ("error", "qos") \
            and not per_layer:
        raise ValueError(
            f"ema_decay drives the error/qos EMAs; the scalar "
            f"{policy.controller!r} controller keeps none — use the "
            f"error or qos controller or a :per-layer policy")
    if policy.controller == "budget":
        return budget_controller(meta.q, pacing, per_layer=per_layer,
                                 max_width=policy.max_width, **ctl_kw)
    if policy.controller == "error":
        return error_controller(meta.q, pacing, meta.pair_table(),
                                per_layer=per_layer,
                                max_width=policy.max_width, **ctl_kw)
    if policy.controller == "qos":
        return qos_controller(meta.q, pacing, meta.pair_table(),
                              per_layer=per_layer,
                              max_width=policy.max_width, **ctl_kw)
    if policy.controller == "stale":
        return stale_controller(meta.q, pacing, per_layer=per_layer,
                                max_width=policy.max_width, **ctl_kw)
    raise ValueError(f"unknown controller {policy.controller!r}")


def init_halo_cache(meta: DistMeta, cfg: GNNConfig) -> tuple:
    """Zero-initialised per-exchange hop-buffer caches for the ``stale``
    controller (``[Q, D, H, width]`` per exchange call; p2p wire only).
    The controller never skips at step 0, so the zeros are never read."""
    d = max(meta.q - 1, 1)
    return tuple(jnp.zeros((meta.q, d, meta.p2p_hop_width, w), jnp.float32)
                 for w in exchange_widths(cfg))


def init_wire_residuals(meta: DistMeta, cfg: GNNConfig) -> tuple:
    """Zero-initialised per-exchange error-feedback residual accumulators
    for quantising policies (``max_width < 32``, p2p wire, emulated
    backend): one full-width ``[Q, D, H, width]`` buffer per exchange
    call — the same shapes as :func:`init_halo_cache`, because both ride
    the train step's ``cache`` channel (stale XOR error-feedback,
    DESIGN.md §3.8).  Each step the residual is packed onto the fresh
    kept set, added to the pre-quantisation payload, and replaced by the
    new quantisation error — so the wire's rounding error is re-shipped
    instead of lost and the compressed-gradient bias stays bounded."""
    return init_halo_cache(meta, cfg)


def _auto_metrics(loss, rate_map, bits, q: int, n_exchanges: int) -> dict:
    """Step metrics of the per-pair ledger vector (``2 + 3·L·Q²`` layout
    of ``gnn_parallel._pair_ledger``; ``L == 1`` for ``[Q, Q]`` pair
    maps); transports double for the backward cotangents exactly like the
    scalar `_step_metrics`.  The staleness delta accumulates one
    relative-change ratio per exchange call, so it is averaged over
    ``n_exchanges`` here — the controller-facing ``pair_delta`` is the
    mean per-buffer change, depth-independent (the ``stale`` threshold
    must not shrink with network depth).

    A per-layer ``[L, Q, Q]`` rate tensor additionally yields
    ``layer_transport`` / ``layer_err`` ``[L, Q, Q]`` tensors (the
    ``pair_*`` matrices are their sums over ``L``, so downstream
    consumers are layout-independent)."""
    n_layers = 1 if rate_map.ndim == 2 else rate_map.shape[0]
    eye = jnp.eye(q, dtype=bool)
    off = ~eye if rate_map.ndim == 2 else ~eye[None]
    mean_rate = jnp.sum(jnp.where(off, rate_map, 0.0)) / \
        max((q * q - q) * n_layers, 1)
    q2 = q * q
    lq2 = n_layers * q2
    layer_t = bits[2:2 + lq2].reshape(n_layers, q, q)
    layer_e = bits[2 + lq2:2 + 2 * lq2].reshape(n_layers, q, q)
    layer_d = bits[2 + 2 * lq2:2 + 3 * lq2].reshape(n_layers, q, q)
    out = {"loss": loss, "rate": mean_rate,
           "halo_bits": 2.0 * bits[0], "transport_bits": 2.0 * bits[1],
           "pair_transport": 2.0 * jnp.sum(layer_t, axis=0),
           "pair_err": jnp.sum(layer_e, axis=0),
           "pair_delta": jnp.sum(layer_d, axis=0) / max(n_exchanges, 1)}
    if rate_map.ndim == 3:
        # keyed on the PLAN's rank, not on L > 1: a per-layer controller
        # on a 1-layer model still needs its layer_err feedback (and its
        # History columns), even though the ledger kept the legacy layout
        out["layer_transport"] = 2.0 * layer_t
        out["layer_err"] = layer_e
    return out


def make_auto_train_step(cfg: GNNConfig, policy: CommPolicy, opt: Optimizer,
                         meta: DistMeta, mesh: Mesh | None = None,
                         sync: str = "grad", stale: bool | None = None,
                         compiled_cache_size: int = COMPILED_CACHE_SIZE,
                         rounding: str | None = None):
    """One Algorithm-1 step driven by a :class:`RatePlan`.

    ``step(params, opt_state, graph, key, plan, cache=()) ->
    (params, opt_state, metrics, cache')`` — ``plan.rates`` must be a
    concrete ``[Q, Q]`` map or per-layer ``[L, Q, Q]`` tensor with
    ``L == cfg.layers`` (the step quantises it to the static kept-block
    maximum per width; passing it traced would defeat the
    bounded-recompile contract).  ``metrics`` adds ``pair_transport`` /
    ``pair_err`` / ``pair_delta`` ``[Q, Q]`` matrices to the usual
    scalars — plus ``layer_transport`` / ``layer_err`` ``[L, Q, Q]``
    tensors for per-layer plans (DESIGN.md §3.7).  ``cache`` is the
    ``stale`` controller's halo-cache tuple (:func:`init_halo_cache`) —
    or, for a quantising policy (``max_width < 32``) on the emulated p2p
    wire, the error-feedback residual tuple
    (:func:`init_wire_residuals`); the two uses are exclusive
    (stale XOR EF).  Other configurations pass ``()`` and get ``()``
    back.

    ``plan.widths`` (``None`` or a concrete ``[Q, Q]`` / ``[L, Q, Q]``
    map) quantises each pair's wire payload (DESIGN.md §3.8): the step
    snaps the widths to the storage grid and keys its compiled variants
    on the distinct sub-32 widths (`_packed_pair_w_for`) exactly like the
    kept-block maps — ``widths=None`` or an all-32 map compiles the
    pre-quantisation program bit-for-bit.

    Requirements: ``policy.mode == "auto"``, ``meta.wire`` in
    ``("packed", "p2p")``, every exchanged width on the 128-lane grid,
    and the graph pytree carrying the ``attach_p2p`` arrays (the per-pair
    ledger and error stats read the per-pair halo sets on every wire).
    Hop reuse (``stale``) additionally needs ``wire == "p2p"`` and the
    emulated backend; error feedback runs on both backends with
    bitwise-identical residual state (tests/test_ratectl.py pins it).

    ``rounding`` picks the quantiser's rounding mode — ``"rint"``
    (deterministic nearest-even) or ``"stochastic"`` (unbiased, per-step
    ``(seed, step, pair)`` key schedule, DESIGN.md §3.8).  ``None``
    defers to :func:`repro.kernels.ops.default_wire_rounding`:
    stochastic on TPU, ``rint`` elsewhere, so CPU golden traces are
    unchanged and TPU wires are unbiased by default.

    Example::

        step = make_auto_train_step(cfg, policy, adamw(5e-3), meta)
        plan, state = ctl.plan(state, t)
        params, opt_state, m, cache = step(params, opt_state, graph,
                                           jax.random.key(t), plan, cache)
    """
    if policy.mode != "auto":
        raise ValueError(f"make_auto_train_step needs an 'auto' policy, "
                         f"got mode {policy.mode!r}")
    if meta.wire not in ("packed", "p2p"):
        raise ValueError(f"per-pair rate maps need wire='packed' or 'p2p', "
                         f"got {meta.wire!r} (the dense wire is scalar-only)")
    if sync not in ("grad", "fedavg"):
        raise ValueError(f"sync must be 'grad' or 'fedavg', got {sync!r}")
    for f_ in {meta.feat_dim, *meta.layer_dims}:
        if f_ % LANE:
            raise ValueError(
                f"per-pair rate maps pack lane-blocks; every exchanged "
                f"width must be divisible by {LANE}, got {f_}")
    n_ex = len(exchange_widths(cfg))
    stale = (policy.controller == "stale") if stale is None else stale
    if stale and meta.wire != "p2p":
        raise ValueError("the stale controller reuses per-pair hop buffers; "
                         "it needs wire='p2p'")
    if stale and mesh is not None:
        raise ValueError(
            "hop reuse is emulated-backend only: a shape-uniform SPMD "
            "ppermute cannot drop individual pairs' buffers (DESIGN.md "
            "§3.6); run the stale controller with mesh=None")
    if rounding is None:
        rounding = default_wire_rounding()
    if rounding not in ("rint", "stochastic"):
        raise ValueError(f"rounding must be 'rint' or 'stochastic', "
                         f"got {rounding!r}")
    # error feedback accumulates per-exchange residual state through the
    # same cache channel hop reuse owns — stale XOR error-feedback; a
    # stale run at max_width < 32 quantises without EF (DESIGN.md §3.8)
    use_ef = policy.max_width < 32 and meta.wire == "p2p" and not stale

    if mesh is None:
        @functools.partial(jax.jit,
                           static_argnames=("packed_k", "wire_w", "store_w"))
        def _jit_step(params, opt_state, graph, key, rate_map, width_map,
                      skip, cache, packed_k, wire_w, store_w=0):
            wm = width_map if wire_w else None
            ef = use_ef and bool(wire_w) and bool(cache)

            def loss_fn(p):
                cache_out: list = []
                agg = _make_aggregate_emulated(
                    graph, meta, policy, None, jnp.ones((), jnp.float32),
                    key, packed_k=dict(packed_k), rate_map=rate_map,
                    skip=skip if stale else None,
                    cache=cache if stale else None,
                    cache_out=cache_out if stale else None,
                    width_map=wm,
                    resid=cache if ef else None,
                    resid_out=cache_out if ef else None,
                    rounding=rounding,
                    store_w=store_w if wire_w else 0)
                logits, bits = gnn_forward(p, cfg, graph["features"], agg)
                loss_sum, _ = masked_loss_and_correct(
                    logits, graph["labels"], graph["train_mask"])
                return loss_sum / max(meta.n_train, 1), \
                    (bits, tuple(cache_out))

            (loss, (bits, cache_new)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, new_state = opt.update(grads, opt_state, params)
            new_params = apply_updates(params, updates)
            return (new_params, new_state,
                    _auto_metrics(loss, rate_map, bits, meta.q, n_ex),
                    cache_new)

        def step(params, opt_state, graph, key, plan: RatePlan, cache=()):
            rm, kb, wm, ww, sw = plan_statics(meta, plan)
            out = _jit_step(params, opt_state, graph, key,
                            jnp.asarray(rm),
                            jnp.zeros((), jnp.float32) if wm is None
                            else jnp.asarray(wm),
                            jnp.asarray(plan.skip, jnp.float32),
                            tuple(cache), packed_k=kb, wire_w=ww,
                            store_w=sw)
            # an exact (unquantised) step neither reads nor rewrites EF
            # residuals — carry them unchanged instead of dropping them
            return out if out[3] or not cache else (*out[:3], tuple(cache))

        step._jit_step = _jit_step
        return step

    def make_worker(packed_k: tuple, wire_w: tuple, ef: bool,
                    store_w: int = 0):
        def worker(params, opt_state, gblk, rate_map, width_map, key,
                   cache):
            # `cache` is the EF residual tuple sharded along its leading
            # [Q] axis: this worker sees [1, D, H, F] blocks and passes
            # its own sender-major slab into the exchange
            def loss_fn(p):
                cache_out: list = []
                agg = _make_aggregate_shard(
                    gblk, meta, policy, None, jnp.ones((), jnp.float32),
                    key, packed_k=dict(packed_k), rate_map=rate_map,
                    width_map=width_map if wire_w else None,
                    resid=cache if ef else None,
                    resid_out=cache_out if ef else None,
                    rounding=rounding,
                    store_w=store_w if wire_w else 0)
                loss, bits = _local_loss_fn(p, cfg, gblk, agg, meta)
                return loss, (bits, tuple(cache_out))

            (loss, (bits, cache_new)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            loss = _psum_tree(loss, AXIS)
            if sync == "grad":
                grads = _psum_tree(grads, AXIS)
                updates, new_state = opt.update(grads, opt_state, params)
                params = apply_updates(params, updates)
            else:  # fedavg
                updates, new_state = opt.update(grads, opt_state, params)
                params = apply_updates(params, updates)
                params = _pmean_inexact(params, AXIS)
                new_state = _pmean_inexact(new_state, AXIS)
            return (params, new_state,
                    _auto_metrics(loss, rate_map, bits, meta.q, n_ex),
                    cache_new)

        return worker

    @functools.lru_cache(maxsize=compiled_cache_size)
    def _compiled_for(kblocks: tuple, wire_w: tuple = (), ef: bool = False,
                      store_w: int = 0):
        return jax.jit(shard_map(
            make_worker(kblocks, wire_w, ef, store_w), mesh=mesh,
            in_specs=(P(), P(), P(AXIS), P(), P(), P(), P(AXIS)),
            out_specs=(P(), P(), P(), P(AXIS)), check_vma=False))

    def step(params, opt_state, graph, key, plan: RatePlan, cache=()):
        rm, kb, wm, ww, sw = plan_statics(meta, plan)
        ef = use_ef and bool(ww) and bool(cache)
        params, opt_state, m, cache_new = _compiled_for(kb, ww, ef, sw)(
            params, opt_state, graph, jnp.asarray(rm),
            jnp.zeros((), jnp.float32) if wm is None else jnp.asarray(wm),
            key, tuple(cache))
        # an exact (unquantised) step neither reads nor rewrites EF
        # residuals — carry them unchanged instead of dropping them
        return params, opt_state, m, \
            tuple(cache_new) if ef else tuple(cache)

    step.cache_info = _compiled_for.cache_info
    step.cache_clear = _compiled_for.cache_clear
    return step
