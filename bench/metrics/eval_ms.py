"""Device time of the periodic evaluation per window epoch, averaged over
chips: the ops under the program's ``eval`` scope, read by ``spans.py``.
Moves ``epoch_s``.
"""

import spans

UNIT = "ms/epoch"


def read(run):
    s = spans.for_run(run)
    return None if s is None else s.ms_per_epoch("eval", run.epochs)
