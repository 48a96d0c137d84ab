"""Names for the program's layers, on the device and on the host.

Device side, the program names each layer with ``jax.named_scope``: the
name lands in every HLO instruction's ``op_name`` metadata at trace time
(no runtime cost) and, from there, in the ``tf_op`` of each op of a
profiler trace.  Autodiff keeps it inside its transform wrappers, so a
transposed op of the local aggregation reads
``transpose(jvp(...))/.../local_agg/...``.  The names, the same on the
emulated and the ``shard_map`` backends:

``layer<i>``    everything GNN layer ``i`` does (``nn/gnn.py``)
``halo``        the halo exchange: send-buffer gather, pack/quantise,
                exchange, unpack
``local_agg``   aggregation over local edges (the ELL row gathers on the
                p2p wire, the local scatter on the dense/packed wires)
``remote_agg``  aggregation of the delivered halo rows
``dense``       the layers' dense products
``loss``        the masked cross-entropy
``optimizer``   the optimizer's update and its application
``grad_sync``   the ``shard_map`` step's collectives over the workers
                (loss, gradients, FedAvg averaging)
``eval``        the periodic evaluation

Host side, :func:`span` opens a ``jax.profiler.TraceAnnotation``, which
the profiler puts on the calling thread's line of the host plane, on the
same clock as the device ops, when it is tracing.  Every span also adds
to a process-wide table (``count``, ``total_s``, ``first_s`` per name)
that :func:`table` returns whether or not the profiler runs.
"""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

#: the clock of the span table, monotonic: what ``History.wall_s`` reads
clock = time.perf_counter

_table: dict[str, dict] = {}


def reset() -> None:
    """Empty the span table."""
    _table.clear()


def table() -> dict[str, dict]:
    """A copy of the span table: ``{name: {count, total_s, first_s}}``."""
    return {k: dict(v) for k, v in _table.items()}


@contextlib.contextmanager
def span(name: str, step_num: int | None = None):
    """Time the enclosed host work under ``name``; with ``step_num`` the
    profiler marks it as a step (``StepTraceAnnotation``).  The span
    closes and is counted when the body raises, too."""
    annotation = (jax.profiler.TraceAnnotation(name) if step_num is None
                  else jax.profiler.StepTraceAnnotation(name,
                                                        step_num=step_num))
    t0 = clock()
    try:
        with annotation:
            yield
    finally:
        dt = clock() - t0
        row = _table.get(name)
        if row is None:
            _table[name] = {"count": 1, "total_s": dt, "first_s": dt}
        else:
            row["count"] += 1
            row["total_s"] += dt


def pull(x):
    """The host's float64 copy of a device value (a ``float`` for a
    scalar), read under a ``sync`` span: the host waits here for the
    device."""
    with span("sync"):
        a = np.asarray(x, np.float64)
    return float(a) if a.ndim == 0 else a
