"""Each configuration's graph at seed 0, and the same sizes at any seed."""

import numpy as np
import pytest

import harness
import work

PINNED = {
    # nodes, directed edges, largest local degree, local edges per part
    "sage3-arxiv": (169_343, 2_339_410, 14, [145_948, 146_318, 146_116,
                                            145_448]),
    "sage3-products": (84_700, 4_234_286, 84, [266_068, 262_706, 263_638,
                                              264_702]),
}


def counts(cfg, seed):
    a = harness.graph_arrays(cfg, seed)
    n = len(a["indptr"]) - 1
    owner = np.empty(n, np.int64)
    owner[a["perm"]] = np.arange(n) % 4       # the seed's random partition
    return a, owner, work.partition_counts(a["indptr"], a["indices"],
                                           owner, 4)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_graph_at_seed_0(name):
    cfg = harness.load("configs", name)
    a, owner, c = counts(cfg, 0)
    n, e, k, local = PINNED[name]
    assert c["nodes"] == n == cfg["graph"]["num_nodes"]
    assert c["edges"] == e
    assert abs(e / n - cfg["graph"]["mean_degree"]) < 0.6
    dst = np.repeat(np.arange(n), np.diff(a["indptr"]))
    loc = owner[dst] == owner[a["indices"]]
    assert np.bincount(dst[loc], minlength=n).max() == k
    assert c["local_edges"].tolist() == local
    assert a["features"].shape == (n, cfg["graph"]["feat_dim"])
    assert a["labels"].max() + 1 == cfg["graph"]["num_classes"]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_every_seed_has_the_same_sizes(name):
    cfg = harness.load("configs", name)
    a0, _, c0 = counts(cfg, 0)
    a1, _, c1 = counts(cfg, 2**31 + 77)
    for key in ("rows", "local_edges", "remote_edges", "halo_rows"):
        assert np.array_equal(c0[key], c1[key]), key
    assert not np.array_equal(a0["features"], a1["features"])
    assert not np.array_equal(a0["train_mask"], a1["train_mask"])


def test_seed_partition_is_the_base_partition():
    from repro.graph.data import GraphData
    from repro.graph.partition import random_partition
    cfg = harness.load("configs", "sage3-arxiv")
    cfg["graph"]["num_nodes"] = 1000
    a = harness.graph_arrays(cfg, 12345)
    g = GraphData(a["indptr"], a["indices"], a["features"], a["labels"],
                  a["train_mask"], a["val_mask"], a["test_mask"])
    owner = random_partition(g, 4, seed=12345)
    assert np.array_equal(owner[a["perm"]], np.arange(1000) % 4)
