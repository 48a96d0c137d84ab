"""Stochastic block model graph with class-correlated features.

The benchmark's own copy of the OGBN-Arxiv-shaped generator: communities
follow the labels (``homophily`` of the edge mass stays inside a class),
features are noisy class centroids, row-normalised.  An optional hub
overlay (``hub_fraction`` of the nodes each attach to about
``hub_degree`` random nodes) gives the heavy-tailed degrees of a
co-purchase graph such as OGBN-Products.

The structure (edges and labels) comes from the configuration's
``structure_seed`` alone, so every run of a cell trains on one graph, of
one set of sizes.  The run's ``--seed`` relabels its nodes, draws the
features' class centroids and noise and the train/val/test split.
"""

from __future__ import annotations

import numpy as np


def _sbm_edges(rng, labels, n_classes, deg_in, deg_out):
    """SBM edges drawn block by block in O(E): per class, ``deg_in / 2``
    intra-class and ``deg_out / 2`` inter-class edges per node."""
    n = len(labels)
    dsts, srcs = [], []
    for c in range(n_classes):
        members = np.flatnonzero(labels == c)
        if len(members) < 2:
            continue
        m_in = rng.poisson(len(members) * deg_in / 2.0)
        if m_in:
            dsts.append(rng.choice(members, m_in))
            srcs.append(rng.choice(members, m_in))
        m_out = rng.poisson(len(members) * deg_out / 2.0)
        if m_out:
            dsts.append(rng.choice(members, m_out))
            srcs.append(rng.integers(0, n, m_out))
    return np.concatenate(dsts), np.concatenate(srcs)


def symmetric_csr(n: int, dst: np.ndarray, src: np.ndarray):
    """``(indptr, indices)`` of the undirected graph on ``dst``/``src``:
    self-loops dropped, both directions present, duplicates merged."""
    dst = np.asarray(dst, np.int64)
    src = np.asarray(src, np.int64)
    keep = dst != src
    a = np.concatenate([dst[keep], src[keep]])
    b = np.concatenate([src[keep], dst[keep]])
    key = np.unique(a * n + b)                 # sorted by (dst, src)
    rows = key // n
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, rows + 1, 1)
    return np.cumsum(indptr), (key % n).astype(np.int32)


def structure(cfg: dict):
    """Edges and labels of the base graph, from ``structure_seed``:
    ``(dst, src, labels)`` with node ``v`` in base partition ``v % Q``."""
    rng = np.random.default_rng(cfg["structure_seed"])
    n, c = cfg["num_nodes"], cfg["num_classes"]
    labels = rng.integers(0, c, n).astype(np.int32)
    hub_edges = 2.0 * cfg.get("hub_fraction", 0.0) * cfg.get("hub_degree", 0)
    sbm_degree = cfg["mean_degree"] - hub_edges
    h = cfg["homophily"]
    dst, src = _sbm_edges(rng, labels, c, sbm_degree * h,
                          sbm_degree * (1.0 - h))
    if cfg.get("hub_fraction", 0.0) > 0:
        n_hubs = max(int(cfg["hub_fraction"] * n), 1)
        hubs = rng.choice(n, n_hubs, replace=False)
        m_hub = rng.poisson(cfg["hub_degree"], n_hubs)
        dst = np.concatenate([dst, np.repeat(hubs, m_hub)])
        src = np.concatenate([src, rng.integers(0, n, int(m_hub.sum()))])
    return dst, src, labels


def generate(cfg: dict, seed: int) -> dict:
    """The run's graph as numpy arrays: ``indptr``/``indices`` (CSR by
    destination), ``features``, ``labels`` and the three split masks.

    Node ``v`` of the base graph becomes node ``perm[v]``, with ``perm``
    the permutation that an equal-size random partition draws from
    ``seed`` (it gives partition ``i`` the nodes ``perm[i::Q]``).  So that
    partition, drawn from the run's seed, cuts every run's graph along the
    same base partition ``v % Q``: each partition's rows, local edges and
    halo rows, and with them every array shape, are the same for every
    seed, in another order.
    """
    n, c, f = cfg["num_nodes"], cfg["num_classes"], cfg["feat_dim"]
    dst, src, base_labels = structure(cfg)
    perm = np.random.default_rng(seed).permutation(n)
    indptr, indices = symmetric_csr(n, perm[dst], perm[src])
    labels = np.empty(n, np.int32)
    labels[perm] = base_labels

    rng = np.random.default_rng([seed, 1])
    centroids = rng.normal(0.0, 1.0, (c, f)).astype(np.float32)
    feats = rng.normal(0.0, 1.0, (n, f)).astype(np.float32)
    feats += cfg["feature_signal"] * centroids[labels]
    feats /= np.linalg.norm(feats, axis=1, keepdims=True) + 1e-6

    order = rng.permutation(n)
    n_train = int(cfg["splits"][0] * n)
    n_val = int(cfg["splits"][1] * n)
    masks = {k: np.zeros(n, bool) for k in ("train", "val", "test")}
    masks["train"][order[:n_train]] = True
    masks["val"][order[n_train:n_train + n_val]] = True
    masks["test"][order[n_train + n_val:]] = True
    return {"indptr": indptr, "indices": indices, "features": feats,
            "labels": labels, "train_mask": masks["train"],
            "val_mask": masks["val"], "test_mask": masks["test"],
            "perm": perm}
