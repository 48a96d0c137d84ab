"""GraphSAGE with the mean aggregator (the paper's section V model).

The family's three parts, as ``harness.py``, ``reference.py`` and the
metric readers call them:

* ``program_fields``: the model as the program's ``GNNConfig`` fields
  (``in_dim`` and ``out_dim`` come from the graph);
* the plain float32 reference, written from the model's equations and
  independent of the code under test (it imports nothing of ``repro``):
  ``init_params``, ``prepare`` (the graph's arrays) and ``forward``;
* the work counts: ``local_agg_widths`` and ``epoch_flops``.

The model:

* mean aggregation ``(S x)_i = (1 / deg i) * sum_{j in N(i)} x_j`` as a
  segment sum over the graph's directed edges, in blocks of edges so the
  gathered rows of the largest graph fit beside the rest;
* layer ``h' = relu(h W_self + b + (S h) W_neigh)``, no relu on the last.

The weights come from the run's seed the way the published model code
draws them: per layer, the key splits into four and the self and
neighbour matrices are normal with standard deviation ``1/sqrt(d_in)``,
the bias zero.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import matmul

#: edges per block of the aggregation's segment sum
EDGE_BLOCK = 1 << 20


def program_fields(model: dict) -> dict:
    return {"conv": "sage", "hidden": model["hidden"],
            "layers": model["num_layers"]}


def layer_widths(model: dict, in_dim: int, out_dim: int):
    ds = [in_dim] + [model["hidden"]] * (model["num_layers"] - 1) + [out_dim]
    return list(zip(ds[:-1], ds[1:]))


# -- the reference -------------------------------------------------------------

def init_params(seed: int, model: dict, in_dim: int, out_dim: int) -> dict:
    key = jax.random.key(seed)
    layers = []
    for d_in, d_out in layer_widths(model, in_dim, out_dim):
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

    return {"dst": shape(dst), "src": shape(src), "w": shape(w)}


def prepare(arrays: dict, edge_keep: np.ndarray | None = None) -> dict:
    return edge_blocks(arrays["indptr"], arrays["indices"], edge_keep)


def aggregate(edges: dict, x: jax.Array) -> jax.Array:
    def body(acc, blk):
        dst, src, w = blk
        return acc.at[dst].add(x[src] * w[:, None]), None

    acc = jnp.zeros(x.shape, x.dtype)
    acc, _ = jax.lax.scan(body, acc, (edges["dst"], edges["src"], edges["w"]))
    return acc


def forward(model: dict, params: dict, edges: dict, x: jax.Array,
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


# -- work counts ---------------------------------------------------------------

def local_agg_widths(model: dict, in_dim: int, out_dim: int) -> list[int]:
    """Feature width of each local aggregation in one training step: one
    per layer forward, and its transpose in the backward for every layer
    whose input depends on the weights (all but the first)."""
    fwd = [d_in for d_in, _ in layer_widths(model, in_dim, out_dim)]
    return fwd + fwd[1:]


def forward_flops(model: dict, n: int, e: int, in_dim: int,
                  out_dim: int) -> float:
    """One forward pass over the whole graph: per layer two dense
    products ``2 N d_in d_out`` (self and neighbour) and the aggregation
    ``2 E d_in``."""
    return sum(4.0 * n * a * b + 2.0 * e * a
               for a, b in layer_widths(model, in_dim, out_dim))


def train_step_flops(model: dict, n: int, e: int, in_dim: int,
                     out_dim: int) -> float:
    """Forward and backward of one full-graph training step.  The
    backward takes the weight gradients of every layer (``2 N d_in d_out``
    per product) and the input gradients of all but the first layer
    (another two products and the transposed aggregation)."""
    dims = layer_widths(model, in_dim, out_dim)
    bwd = sum(4.0 * n * a * b for a, b in dims)
    bwd += sum(4.0 * n * a * b + 2.0 * e * a for a, b in dims[1:])
    return forward_flops(model, n, e, in_dim, out_dim) + bwd


def epoch_flops(model: dict, n: int, e: int, in_dim: int, out_dim: int,
                eval_every: int) -> float:
    """A training epoch with its share of the periodic evaluation's
    forward pass."""
    return (train_step_flops(model, n, e, in_dim, out_dim)
            + forward_flops(model, n, e, in_dim, out_dim) / eval_every)
