"""Optimizers + LR schedules (no optax on this box — built from scratch).

``Optimizer`` is the usual (init, update) pair over param pytrees.  AdamW
supports a *dtype policy* for its moments so the 398B-class dry-run configs
fit HBM (bf16 moments is a standard production trick; see DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array


class Optimizer(NamedTuple):
    """The optimizers built here, and :func:`apply_updates`, trace under
    the ``optimizer`` scope (``repro.tracing``)."""
    init: Callable
    update: Callable  # (grads, state, params) -> (updates, state)


def _tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def global_norm(tree) -> Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return _tree_map(lambda g: g * scale.astype(g.dtype), grads), norm


# ---------------------------------------------------------------------------
# SGD with momentum
# ---------------------------------------------------------------------------


def sgd(lr: float | Callable, momentum: float = 0.0,
        weight_decay: float = 0.0) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        mom = _tree_map(jnp.zeros_like, params) if momentum else None
        return {"step": jnp.zeros((), jnp.int32), "mom": mom}

    @jax.named_scope("optimizer")
    def update(grads, state, params):
        step = state["step"] + 1
        eta = lr_fn(step)
        if weight_decay:
            grads = _tree_map(lambda g, p: g + weight_decay * p, grads, params)
        if momentum:
            mom = _tree_map(lambda m, g: momentum * m + g, state["mom"], grads)
            upd = _tree_map(lambda m: -eta * m, mom)
        else:
            mom = None
            upd = _tree_map(lambda g: -eta * g, grads)
        return upd, {"step": step, "mom": mom}

    return Optimizer(init, update)


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------


def adamw(lr: float | Callable, b1: float = 0.9, b2: float = 0.999,
          eps: float = 1e-8, weight_decay: float = 0.0,
          moment_dtype=None) -> Optimizer:
    """AdamW; ``moment_dtype=jnp.bfloat16`` halves optimizer HBM."""
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        def zeros(p):
            dt = moment_dtype or p.dtype
            return jnp.zeros(p.shape, dt)
        return {"step": jnp.zeros((), jnp.int32),
                "mu": _tree_map(zeros, params),
                "nu": _tree_map(zeros, params)}

    @jax.named_scope("optimizer")
    def update(grads, state, params):
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        eta = lr_fn(step)
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t

        def upd_moments(mu, nu, g):
            gf = g.astype(jnp.float32)
            mu_f = b1 * mu.astype(jnp.float32) + (1 - b1) * gf
            nu_f = b2 * nu.astype(jnp.float32) + (1 - b2) * gf * gf
            return mu_f, nu_f

        mus, nus, upds = [], [], []
        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        mu_leaves = treedef.flatten_up_to(state["mu"])
        nu_leaves = treedef.flatten_up_to(state["nu"])
        p_leaves = treedef.flatten_up_to(params)
        for g, mu, nu, p in zip(g_leaves, mu_leaves, nu_leaves, p_leaves):
            mu_f, nu_f = upd_moments(mu, nu, g)
            u = -eta * (mu_f / bc1) / (jnp.sqrt(nu_f / bc2) + eps)
            if weight_decay:
                u = u - eta * weight_decay * p.astype(jnp.float32)
            dt = moment_dtype or p.dtype
            mus.append(mu_f.astype(dt))
            nus.append(nu_f.astype(dt))
            upds.append(u.astype(p.dtype))
        unf = jax.tree_util.tree_unflatten
        return unf(treedef, upds), {"step": step,
                                    "mu": unf(treedef, mus),
                                    "nu": unf(treedef, nus)}

    return Optimizer(init, update)


@jax.named_scope("optimizer")
def apply_updates(params, updates):
    return _tree_map(lambda p, u: p + u.astype(p.dtype), params, updates)


# ---------------------------------------------------------------------------
# LR schedules
# ---------------------------------------------------------------------------


def constant_lr(v: float) -> Callable:
    return lambda _step: jnp.asarray(v, jnp.float32)


def cosine_lr(peak: float, total_steps: int, warmup: int = 0,
              floor: float = 0.0) -> Callable:
    def fn(step):
        s = jnp.asarray(step, jnp.float32)
        warm = peak * s / max(warmup, 1)
        frac = jnp.clip((s - warmup) / max(total_steps - warmup, 1), 0.0, 1.0)
        cos = floor + 0.5 * (peak - floor) * (1.0 + jnp.cos(math.pi * frac))
        return jnp.where(s < warmup, warm, cos)
    return fn


def linear_decay_lr(peak: float, total_steps: int, warmup: int = 0) -> Callable:
    def fn(step):
        s = jnp.asarray(step, jnp.float32)
        warm = peak * s / max(warmup, 1)
        frac = jnp.clip((s - warmup) / max(total_steps - warmup, 1), 0.0, 1.0)
        return jnp.where(s < warmup, warm, peak * (1.0 - frac))
    return fn


OPTIMIZERS = {"adamw": adamw, "sgd": sgd}
