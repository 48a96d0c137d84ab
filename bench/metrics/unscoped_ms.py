"""Device time per epoch, averaged over chips, of the work ops under no
program scope (``spans.py``): the blind spot of the scope map, such as
the fusions XLA makes without an ``op_name``.  Read only where the
program names its scopes.  Moves ``epoch_s``.
"""

import spans

UNIT = "ms/epoch"


def read(run):
    s = spans.for_run(run)
    return None if s is None else s.ms_per_epoch("unscoped", run.epochs)
