"""Host reads of device values per window epoch: the program's ``sync``
spans in the trace over the window's epochs.  Each one stops the host
until the device catches up.  Moves ``epoch_s``.
"""

import spans

UNIT = "count"


def read(run):
    s = spans.for_run(run)
    if s is None or not s.count("epoch"):
        return None
    return s.count("sync") / run.epochs
