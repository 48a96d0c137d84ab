"""Device time of the halo exchange per epoch, averaged over chips: the
train step's ops under the program's ``halo`` scope (send-buffer gather,
pack, exchange, unpack), read by ``spans.py``.  Moves ``epoch_s``.
"""

import spans

UNIT = "ms/epoch"


def read(run):
    s = spans.for_run(run)
    return None if s is None else s.ms_per_epoch("halo", run.epochs)
