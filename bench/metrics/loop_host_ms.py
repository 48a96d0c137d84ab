"""The trainer loop's own host work per window epoch: the window's
``epoch`` spans less their ``sync`` spans (the host waits for the
device) and ``log`` spans (the caller's hook), from the program's host
spans in the trace (``spans.Scoped.host_loop_s``).  Moves ``epoch_s``.
"""

import spans

UNIT = "ms/epoch"


def read(run):
    s = spans.for_run(run)
    host = None if s is None else s.host_loop_s()
    return None if host is None else 1e3 * host / run.epochs
