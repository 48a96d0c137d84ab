"""Plain float32 GraphSAGE training step: the yardstick for ``correct``.

Written from the model's equations, independent of the code under test
(it imports nothing of ``repro``):

* mean aggregation ``(S x)_i = (1 / deg i) * sum_{j in N(i)} x_j`` as a
  segment sum over the graph's directed edges, in blocks of edges so the
  gathered rows of the largest graph fit beside the rest;
* layer ``h' = relu(h W_self + b + (S h) W_neigh)``, no relu on the last;
* loss: cross-entropy summed over the training nodes, divided by their
  count;
* Adam (Kingma and Ba) with bias correction and no weight decay.

The weights come from the run's seed the way the published model code
draws them: per layer, the key splits into four and the self and
neighbour matrices are normal with standard deviation ``1/sqrt(d_in)``,
the bias zero.  Everything runs in float32.  ``precision`` picks how the
dense products multiply: ``highest`` (float32 products, the reference)
or ``high``, the next precision down and the control: each float32
operand split into two bfloat16 parts and three of the four products
summed, the last, smallest one dropped, written out here so that it
computes the same on every backend.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: edges per block of the aggregation's segment sum
EDGE_BLOCK = 1 << 20


def init_params(seed: int, dims: list[tuple[int, int]]) -> dict:
    key = jax.random.key(seed)
    layers = []
    for d_in, d_out in dims:
        key, k_self, k_neigh, _ = jax.random.split(key, 4)
        std = 1.0 / math.sqrt(d_in)
        layers.append({
            "self": {"w": jax.random.normal(k_self, (d_in, d_out),
                                            jnp.float32) * std,
                     "b": jnp.zeros((d_out,), jnp.float32)},
            "neigh": {"w": jax.random.normal(k_neigh, (d_in, d_out),
                                             jnp.float32) * std},
        })
    return {"layers": layers}


def edge_blocks(indptr: np.ndarray, indices: np.ndarray,
                keep: np.ndarray | None = None) -> dict:
    """The directed edges ``(dst, src, 1/deg dst)`` in ``[B, EDGE_BLOCK]``
    blocks; padding edges carry weight 0, and so do the edges that
    ``keep`` (a mask over the edges) leaves out."""
    n = len(indptr) - 1
    deg = np.diff(indptr)
    dst = np.repeat(np.arange(n, dtype=np.int32), deg)
    src = indices.astype(np.int32)
    w = (1.0 / np.maximum(deg, 1).astype(np.float32))[dst]
    if keep is not None:
        w = np.where(keep, w, np.float32(0.0))
    blocks = -(-len(dst) // EDGE_BLOCK)
    pad = blocks * EDGE_BLOCK - len(dst)

    def shape(a):
        return jnp.asarray(np.pad(a, (0, pad)).reshape(blocks, EDGE_BLOCK))

    return {"dst": shape(dst), "src": shape(src), "w": shape(w), "n": n}


def aggregate(edges: dict, x: jax.Array) -> jax.Array:
    def body(acc, blk):
        dst, src, w = blk
        return acc.at[dst].add(x[src] * w[:, None]), None

    acc = jnp.zeros((edges["n"], x.shape[1]), x.dtype)
    acc, _ = jax.lax.scan(body, acc, (edges["dst"], edges["src"], edges["w"]))
    return acc


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


def forward(params: dict, edges: dict, x: jax.Array,
            precision: str = "highest") -> jax.Array:
    h = x
    last = len(params["layers"]) - 1
    for li, layer in enumerate(params["layers"]):
        s = aggregate(edges, h)
        h = (matmul(h, layer["self"]["w"], precision) + layer["self"]["b"]
             + matmul(s, layer["neigh"]["w"], precision))
        if li < last:
            h = jax.nn.relu(h)
    return h


def loss(params, edges, x, labels, train_mask, precision="highest"):
    logits = forward(params, edges, x, precision)
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


@functools.partial(jax.jit, static_argnames=("n", "opt", "precision"))
def _step(p, mu, nu, t, dst, src, w, x, labels, mask, *, n, opt, precision):
    edges = {"dst": dst, "src": src, "w": w, "n": n}
    val, g = jax.value_and_grad(loss)(p, edges, x, labels, mask, precision)
    lr, b1, b2, eps = opt
    p, mu, nu = adam(p, g, mu, nu, t, lr, b1, b2, eps)
    return p, mu, nu, val, g


def train_readings(arrays: dict, model: dict, seed: int, steps: int,
                   precision: str, edge_keep: np.ndarray | None = None
                   ) -> dict:
    """``steps`` reference steps from the seed's weights.

    Returns numpy ``loss`` per step (at the weights the step starts
    from), the first step's gradient ``grad1`` and the weights before the
    first and after the last step (``p0``, ``pn``), as flat
    ``{path: array}`` dicts.  ``edge_keep`` drops edges from the
    aggregation (their weight becomes 0), for planted faults.
    """
    dims = model_dims(model, arrays["features"].shape[1],
                      int(arrays["labels"].max()) + 1)
    params = init_params(seed, dims)
    e = edge_blocks(arrays["indptr"], arrays["indices"], edge_keep)
    data = (e["dst"], e["src"], e["w"], jnp.asarray(arrays["features"]),
            jnp.asarray(arrays["labels"]), jnp.asarray(arrays["train_mask"]))
    o = model["optimizer"]
    static = {"n": e["n"], "opt": (o["lr"], o["b1"], o["b2"], o["eps"]),
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


def model_dims(model: dict, in_dim: int, out_dim: int):
    ds = [in_dim] + [model["hidden"]] * (model["num_layers"] - 1) + [out_dim]
    return list(zip(ds[:-1], ds[1:]))


def flat(tree) -> dict:
    """``{"layers/0/self/w": np.ndarray, ...}`` of a params-shaped tree."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        out[key] = np.asarray(leaf, np.float64)
    return out
