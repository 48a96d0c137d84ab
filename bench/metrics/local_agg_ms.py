"""Device time of the local aggregation per epoch, averaged over chips:
the train step's ops under the program's ``local_agg`` scope (the ELL
row gathers and multiply-adds with their glue, forward and transposed, on
the p2p wire), read by ``spans.py``, whatever kernel does the work.
Moves ``epoch_s``.
"""

import spans

UNIT = "ms/epoch"


def read(run):
    s = spans.for_run(run)
    return None if s is None else s.ms_per_epoch("local_agg", run.epochs)
