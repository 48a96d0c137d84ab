"""Device time of the remote aggregation per epoch, averaged over chips:
the train step's ops under the program's ``remote_agg`` scope (the
scatter of the delivered halo rows, forward and transposed), read by
``spans.py``; the evaluation's are ``eval_ms``'s.  Moves ``epoch_s``.
"""

import spans

UNIT = "ms/epoch"


def read(run):
    s = spans.for_run(run)
    return None if s is None else s.ms_per_epoch("remote_agg", run.epochs)
