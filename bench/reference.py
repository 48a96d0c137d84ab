"""The plain float32 training step: the yardstick for ``correct``.

The parts every model family shares; a family (``models/<family>.py``,
named by the configuration's ``model.family``) brings its weights
(``init_params``), the graph arrays its layers read (``prepare``) and its
``forward``, written from the model's equations.  Like this module, a
family imports nothing of ``repro``: the reference is independent of the
code under test.

* loss: cross-entropy summed over the training nodes, divided by their
  count;
* Adam (Kingma and Ba) with bias correction and no weight decay.

Everything runs in float32.  ``precision`` picks how the dense products
(``matmul``, which every family calls) multiply: ``highest`` (float32
products, the reference) or ``high``, the next precision down and the
control: each float32 operand split into two bfloat16 parts and three of
the four products summed, the last, smallest one dropped, written out
here so that it computes the same on every backend.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np


def _split(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def matmul(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    (a1, a2), (b1, b2) = _split(a), _split(b)

    def mm(u, v):
        return jnp.matmul(u, v, preferred_element_type=jnp.float32)

    return mm(a1, b1) + (mm(a1, b2) + mm(a2, b1))


def loss(forward, model, params, graph, x, labels, train_mask,
         precision="highest"):
    logits = forward(model, params, graph, x, precision)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    m = train_mask.astype(jnp.float32)
    return jnp.sum((logz - gold) * m) / jnp.sum(m)


def adam(params, grads, mu, nu, t: int, lr, b1, b2, eps):
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                nu, grads)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t))
        / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, mu, nu)
    return params, mu, nu


class _Static(dict):
    """The model's description as a static argument of the jitted step."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


@functools.partial(jax.jit,
                   static_argnames=("forward", "model", "opt", "precision"))
def _step(p, mu, nu, t, graph, x, labels, mask, *, forward, model, opt,
          precision):
    val, g = jax.value_and_grad(loss, argnums=2)(forward, model, p, graph, x,
                                                  labels, mask, precision)
    lr, b1, b2, eps = opt
    p, mu, nu = adam(p, g, mu, nu, t, lr, b1, b2, eps)
    return p, mu, nu, val, g


def train_readings(family, arrays: dict, model: dict, seed: int,
                   steps: int, precision: str,
                   edge_keep: np.ndarray | None = None) -> dict:
    """``steps`` reference steps of the ``family`` module's model from the
    seed's weights.

    Returns numpy ``loss`` per step (at the weights the step starts
    from), the first step's gradient ``grad1`` and the weights before the
    first and after the last step (``p0``, ``pn``), as flat
    ``{path: array}`` dicts.  ``edge_keep`` drops edges from the
    aggregation (their weight becomes 0), for planted faults.
    """
    params = family.init_params(seed, model, arrays["features"].shape[1],
                                int(arrays["labels"].max()) + 1)
    data = (family.prepare(arrays, edge_keep),
            jnp.asarray(arrays["features"]), jnp.asarray(arrays["labels"]),
            jnp.asarray(arrays["train_mask"]))
    o = model["optimizer"]
    static = {"forward": family.forward, "model": _Static(model),
              "opt": (o["lr"], o["b1"], o["b2"], o["eps"]),
              "precision": precision}
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu = zeros, zeros
    p0 = flat(params)
    losses, grad1 = [], None
    for t in range(1, steps + 1):
        params, mu, nu, val, g = _step(params, mu, nu, jnp.float32(t),
                                       *data, **static)
        losses.append(float(val))
        if grad1 is None:
            grad1 = flat(g)
    return {"loss": np.asarray(losses), "grad1": grad1, "p0": p0,
            "pn": flat(params)}


def flat(tree) -> dict:
    """``{"layers/0/self/w": np.ndarray, ...}`` of a params-shaped tree."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        out[key] = np.asarray(leaf, np.float64)
    return out
