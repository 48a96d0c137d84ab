"""GNN models (paper §II eq. (2) + §V experimental setup).

The model is written against an abstract *aggregation oracle*
``aggregate(layer, x) -> (Sx, wire_bits)`` so the **same model code** runs

* centralised (single device, exact full-graph aggregation) — the reference
  the distributed runtime must match under full communication, and
* distributed (`repro.dist.gnn_parallel`) — per-partition aggregation with a
  compressed halo exchange supplying the remote neighbour terms.

Conv types
----------
``sage``  GraphSAGE mean aggregator (paper §V):
          ``h = ρ(x W_self + (S_mean x) W_neigh + b)``
``poly``  The paper's polynomial graph convolution (eq. 2) with K taps:
          ``h = ρ(Σ_k (S^k x) H_k)`` with S symmetric-normalised.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from .modules import dense, dense_init, softmax_cross_entropy

Array = jax.Array

# aggregate(layer_idx, x) -> (aggregated, wire_bits)
AggregateFn = Callable[[int, Array], tuple[Array, Array]]


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    conv: str = "sage"          # "sage" | "poly"
    in_dim: int = 128
    hidden: int = 256           # paper §V: 256 hidden units
    out_dim: int = 40
    layers: int = 3             # paper §V: 3 layers
    k_taps: int = 2             # poly conv: number of filter taps K
    residual: bool = False

    def dims(self) -> list[tuple[int, int]]:
        ds = [self.in_dim] + [self.hidden] * (self.layers - 1) + [self.out_dim]
        return list(zip(ds[:-1], ds[1:]))


#: the layers' dense products, named as such in the compiled program
_dense = jax.named_scope("dense")(dense)


def init_gnn(key: Array, cfg: GNNConfig) -> dict:
    params: dict = {"layers": []}
    for li, (d_in, d_out) in enumerate(cfg.dims()):
        key, *sub = jax.random.split(key, 4)
        if cfg.conv == "sage":
            layer = {
                "self": dense_init(sub[0], d_in, d_out, bias=True),
                "neigh": dense_init(sub[1], d_in, d_out, bias=False),
            }
        elif cfg.conv == "poly":
            layer = {"taps": [dense_init(k, d_in, d_out, bias=(t == 0))
                              for t, k in enumerate(
                                  jax.random.split(sub[0], cfg.k_taps))]}
        else:
            raise ValueError(f"unknown conv {cfg.conv!r}")
        params["layers"].append(layer)
    return params


def gnn_forward(params: dict, cfg: GNNConfig, x: Array,
                aggregate: AggregateFn,
                hidden_out: list | None = None) -> tuple[Array, Array]:
    """Run the GNN; returns (logits, total_wire_bits).

    ``hidden_out`` (optional list) collects every layer's post-activation
    output — one entry per layer, the last being the returned logits —
    without touching the compute graph; the serving embedding cache
    (``repro.serve``, DESIGN.md §3.11) stores these per (layer,
    node-block).

    ``aggregate`` is called once per (layer, tap>0): every call corresponds
    to one halo exchange in the distributed runtime (Fig. 2's
    compute → compress → communicate → decompress round).

    When the oracle carries the split-phase attributes ``start(li, x) ->
    (token, bits)`` / ``complete(li, x, token) -> agg`` (the distributed
    p2p/packed oracles of ``repro.dist.gnn_parallel`` do), the forward
    runs the **pipelined halo prefetch** schedule (DESIGN.md §3.7): each
    layer's pack + exchange is issued *first*, the exchange-independent
    local work (the self-term matmul here, the ELL local aggregation
    inside ``complete``) is scheduled while the hops are in flight, and
    the wire is consumed only at the unpack inside ``complete``.  The two
    phases are the fused oracle's own halves, so the pipelined and fused
    schedules are bitwise identical (pinned by tests/test_layer_rates.py)
    — at most two exchanges' hop buffers are ever live (double-buffered
    hop slots).
    """
    bits = jnp.zeros((), jnp.float32)
    h = x
    n_layers = len(params["layers"])
    start = getattr(aggregate, "start", None)
    complete = getattr(aggregate, "complete", None)
    pipelined = start is not None and complete is not None

    for li, layer in enumerate(params["layers"]):
        with jax.named_scope(f"layer{li}"):
            if cfg.conv == "sage":
                if pipelined:
                    token, b = start(li, h)                # issue the exchange
                    self_term = _dense(layer["self"], h)   # overlaps the wire
                    agg = complete(li, h, token)           # unpack + aggregate
                    bits = bits + b
                    h_new = self_term + _dense(layer["neigh"], agg)
                else:
                    agg, b = aggregate(li, h)
                    bits = bits + b
                    h_new = _dense(layer["self"], h) + \
                        _dense(layer["neigh"], agg)
            else:  # poly, eq. (2) — taps chain (tap t+1 consumes tap t), so
                # there is no exchange-independent work to interleave and
                # the fused call is the pipelined schedule already
                sk = h
                h_new = _dense(layer["taps"][0], h)
                for t in range(1, cfg.k_taps):
                    sk, b = aggregate(li, sk)
                    bits = bits + b
                    h_new = h_new + _dense(layer["taps"][t], sk)
            if cfg.residual and h_new.shape == h.shape:
                h_new = h_new + h
            h = jax.nn.relu(h_new) if li < n_layers - 1 else h_new
        if hidden_out is not None:
            hidden_out.append(h)
    return h, bits


@jax.named_scope("loss")
def masked_loss_and_correct(logits: Array, labels: Array, mask: Array
                            ) -> tuple[Array, Array]:
    """Sum of CE over masked nodes + count of correct predictions."""
    ce = softmax_cross_entropy(logits, labels)
    m = mask.astype(jnp.float32)
    loss_sum = jnp.sum(ce * m)
    correct = jnp.sum((jnp.argmax(logits, -1) == labels) * m)
    return loss_sum, correct


# ---------------------------------------------------------------------------
# Centralised aggregation oracle (reference semantics)
# ---------------------------------------------------------------------------


def centralized_aggregate_fn(n: int, dst: Array, src: Array, w: Array
                             ) -> AggregateFn:
    """Exact full-graph ``S x`` via segment-sum; zero wire bits."""
    dst = jnp.asarray(dst, jnp.int32)
    src = jnp.asarray(src, jnp.int32)
    w = jnp.asarray(w, jnp.float32)

    def aggregate(_li: int, x: Array) -> tuple[Array, Array]:
        contrib = x[src] * w[:, None]
        agg = jnp.zeros((n,) + x.shape[1:], x.dtype).at[dst].add(contrib)
        return agg, jnp.zeros((), jnp.float32)

    return aggregate


def centralized_forward(params: dict, cfg: GNNConfig, g, norm: str = "mean"
                        ) -> Array:
    """Full-graph forward on a host GraphData (test/eval reference)."""
    from repro.graph.data import normalized_edge_weights
    dst, src = g.edge_list()
    w = normalized_edge_weights(g, kind=norm)
    agg = centralized_aggregate_fn(g.num_nodes, dst, src, w)
    logits, _ = gnn_forward(params, cfg, jnp.asarray(g.features), agg)
    return logits
