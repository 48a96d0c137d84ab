"""Work counts against hand counts at the OGBN-Arxiv shapes: the shared
ones (``work.py``) and the SAGE family's (``models/sage.py``)."""

import numpy as np
import pytest

import harness
import work

N, E = 169_343, 2_339_410            # nodes, directed edges (seed 0)
MODEL = {"hidden": 256, "num_layers": 3}
V5E = work.peak("TPU v5 lite")
SAGE = harness.load_module("models", "sage")


def test_peaks_name_their_chip():
    assert V5E == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16e9}
    with pytest.raises(KeyError):
        work.peak("cpu")


def test_ell_call_counts():
    # partition 0 of the arxiv graph: 42,336 rows, 145,948 local edges
    flops, nbytes = work.ell_cost(256, 42_336, 145_948)
    assert flops == 2 * 145_948 * 256 == 74_725_376
    assert nbytes == 4 * 256 * 84_672 + 8 * 145_948 == 87_871_712
    t, bound = work.least_seconds(flops, nbytes, V5E)
    assert bound == "memory" and t == pytest.approx(87_871_712 / 819e9)


def test_ell_calls_per_step():
    # forward 128, 256, 256; the backward transposes the last two
    assert SAGE.local_agg_widths(MODEL, 128, 40) == [128, 256, 256, 256,
                                                     256]


def test_local_agg_least_sums_calls_and_partitions():
    counts = {"rows": np.array([42_336, 42_336]),
              "local_edges": np.array([145_948, 145_948])}
    one = 87_871_712 / 819e9                        # F = 256, as above
    assert work.local_agg_least(counts, [256, 256, 256], V5E) == \
        pytest.approx(6 * one)


def test_epoch_flops():
    fwd = (4 * N * 128 * 256 + 2 * E * 128
           + 4 * N * 256 * 256 + 2 * E * 256
           + 4 * N * 256 * 40 + 2 * E * 256)
    weight_grads = 4 * N * (128 * 256 + 256 * 256 + 256 * 40)
    input_grads = 4 * N * (256 * 256 + 256 * 40) + 2 * E * (256 + 256)
    assert SAGE.forward_flops(MODEL, N, E, 128, 40) == fwd
    step = fwd + weight_grads + input_grads
    assert SAGE.train_step_flops(MODEL, N, E, 128, 40) == step
    assert SAGE.epoch_flops(MODEL, N, E, 128, 40, 5) == step + fwd / 5
    assert step == 203_767_874_048


def test_partition_counts_by_hand():
    # path 0-1-2-3 plus edge 0-2, owners [0, 0, 1, 1]
    indptr = np.array([0, 2, 4, 7, 8])
    indices = np.array([1, 2, 0, 2, 0, 1, 3, 2])
    c = work.partition_counts(indptr, indices, np.array([0, 0, 1, 1]), 2)
    assert c["rows"].tolist() == [2, 2]
    assert c["local_edges"].tolist() == [2, 2]
    assert c["remote_edges"].tolist() == [2, 2]
    # partition 1 needs rows 0 and 1; partition 0 needs row 2
    assert c["halo_rows"].tolist() == [[0, 2], [1, 0]]
