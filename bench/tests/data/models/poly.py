"""The paper's polynomial graph convolution (eq. 2) with K taps and an
optional residual connection: a second model family, made of new files
only, for the harness's tests.

Layer ``h' = relu(sum_{k<K} (S^k h) H_k + b [+ h])``: tap 0 carries the
bias, each further tap aggregates the previous one, the residual adds
the layer's input where the widths agree, and the last layer has no
relu.  ``S`` is the normalisation the program's ``train_gnn`` partitions
every graph with, ``(S x)_i = (1 / deg i) * sum_{j in N(i)} x_j``
(``model.norm`` is ``"mean"``; eq. (2)'s symmetric ``S`` does not reach
the program through ``train_gnn``).

The weights come from the run's seed as the program draws them: per
layer the key splits into four, the first of the three it keeps splits
into one key per tap, and each tap's matrix is normal with standard
deviation ``1/sqrt(d_in)``, tap 0's bias zero.  Imports nothing of
``repro``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import matmul


def program_fields(model: dict) -> dict:
    return {"conv": "poly", "hidden": model["hidden"],
            "layers": model["num_layers"], "k_taps": model["k_taps"],
            "residual": model["residual"]}


def layer_widths(model: dict, in_dim: int, out_dim: int):
    ds = [in_dim] + [model["hidden"]] * (model["num_layers"] - 1) + [out_dim]
    return list(zip(ds[:-1], ds[1:]))


# -- the reference -------------------------------------------------------------

def init_params(seed: int, model: dict, in_dim: int, out_dim: int) -> dict:
    key = jax.random.key(seed)
    layers = []
    for d_in, d_out in layer_widths(model, in_dim, out_dim):
        key, k_taps, _, _ = jax.random.split(key, 4)
        std = 1.0 / math.sqrt(d_in)
        taps = [{"w": jax.random.normal(k, (d_in, d_out), jnp.float32) * std}
                for k in jax.random.split(k_taps, model["k_taps"])]
        taps[0]["b"] = jnp.zeros((d_out,), jnp.float32)
        layers.append({"taps": taps})
    return {"layers": layers}


def prepare(arrays: dict, edge_keep: np.ndarray | None = None) -> dict:
    indptr = arrays["indptr"]
    deg = np.diff(indptr)
    dst = np.repeat(np.arange(len(deg), dtype=np.int32), deg)
    w = (1.0 / np.maximum(deg, 1).astype(np.float32))[dst]
    if edge_keep is not None:
        w = np.where(edge_keep, w, np.float32(0.0))
    return {"dst": jnp.asarray(dst), "w": jnp.asarray(w),
            "src": jnp.asarray(arrays["indices"].astype(np.int32))}


def forward(model: dict, params: dict, graph: dict, x: jax.Array,
            precision: str = "highest") -> jax.Array:
    def s(v):
        return jax.ops.segment_sum(v[graph["src"]] * graph["w"][:, None],
                                   graph["dst"], num_segments=v.shape[0])

    h = x
    last = len(params["layers"]) - 1
    for li, layer in enumerate(params["layers"]):
        taps = layer["taps"]
        sk = h
        out = matmul(h, taps[0]["w"], precision) + taps[0]["b"]
        for tap in taps[1:]:
            sk = s(sk)
            out = out + matmul(sk, tap["w"], precision)
        if model["residual"] and out.shape == h.shape:
            out = out + h
        h = jax.nn.relu(out) if li < last else out
    return h


# -- work counts ---------------------------------------------------------------

def local_agg_widths(model: dict, in_dim: int, out_dim: int) -> list[int]:
    """Feature width of each local aggregation in one training step:
    ``K - 1`` per layer forward, and their transposes in the backward for
    every layer whose input depends on the weights (all but the first)."""
    fwd = [d_in for d_in, _ in layer_widths(model, in_dim, out_dim)
           for _ in range(model["k_taps"] - 1)]
    return fwd + fwd[model["k_taps"] - 1:]


def forward_flops(model: dict, n: int, e: int, in_dim: int,
                  out_dim: int) -> float:
    """One forward pass: per layer ``K`` dense products ``2 N d_in d_out``
    and ``K - 1`` aggregations ``2 E d_in``."""
    k = model["k_taps"]
    return sum(2.0 * k * n * a * b + 2.0 * (k - 1) * e * a
               for a, b in layer_widths(model, in_dim, out_dim))


def epoch_flops(model: dict, n: int, e: int, in_dim: int, out_dim: int,
                eval_every: int) -> float:
    """A training step (the forward; every layer's weight gradients; all
    but the first layer's input gradients, through the products and the
    transposed aggregations) and the evaluation's forward at one in
    ``eval_every``."""
    k = model["k_taps"]
    dims = layer_widths(model, in_dim, out_dim)
    fwd = forward_flops(model, n, e, in_dim, out_dim)
    bwd = sum(2.0 * k * n * a * b for a, b in dims)
    bwd += sum(2.0 * k * n * a * b + 2.0 * (k - 1) * e * a
               for a, b in dims[1:])
    return fwd + bwd + fwd / eval_every
