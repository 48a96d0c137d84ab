"""Work counts: the operations and bytes that the algorithm needs, from the
graph's real rows and edges and the model's widths.  The parts every
model family shares; a family (``models/<family>.py``) counts its own
``epoch_flops`` and the widths of its local aggregations
(``local_agg_widths``).

Nothing here looks at how a kernel tiles its grid or pads its degrees, so
the counts stay valid when a kernel is replaced.  A partition's "local"
edges join two nodes it owns; the rest are "remote" and need a halo row.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = json.loads((Path(__file__).parent / "peaks.json").read_text())


def peak(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    try:
        return PEAKS["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS['devices'])}")


def partition_counts(indptr: np.ndarray, indices: np.ndarray,
                     owner: np.ndarray, q: int) -> dict:
    """Per partition: ``rows``, ``local_edges``, ``remote_edges`` (directed,
    by destination), and ``halo_rows[i, j]``: rows partition ``i`` owns
    that partition ``j`` needs."""
    n = len(indptr) - 1
    dst = np.repeat(np.arange(n), np.diff(indptr))
    src = indices.astype(np.int64)
    od, os_ = owner[dst], owner[src]
    local = od == os_
    rows = np.bincount(owner, minlength=q)
    local_edges = np.bincount(od[local], minlength=q)
    remote_edges = np.bincount(od[~local], minlength=q)
    pairs = np.unique(src[~local] * q + od[~local])     # (row, receiver)
    halo = np.zeros((q, q), np.int64)
    np.add.at(halo, (owner[pairs // q], pairs % q), 1)
    return {"rows": rows, "local_edges": local_edges,
            "remote_edges": remote_edges, "halo_rows": halo,
            "edges": len(indices), "nodes": n}


def ell_cost(f: int, rows: int, local_edges: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one partition's local aggregation at width
    ``f``: a multiply-add per edge and feature; read every source row and
    write every destination row once in float32, and read each edge's
    int32 index and float32 weight."""
    return 2.0 * local_edges * f, 4.0 * f * (rows + rows) + 8.0 * local_edges


def least_seconds(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / pk["flops_per_s"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def local_agg_least(counts: dict, widths: list[int], pk: dict) -> float:
    """Least seconds of one training step's local aggregations at the
    feature ``widths``, summed over calls and partitions (each partition
    is its own call)."""
    return sum(least_seconds(*ell_cost(f, int(rows), int(loc)), pk)[0]
               for f in widths
               for rows, loc in zip(counts["rows"], counts["local_edges"]))
