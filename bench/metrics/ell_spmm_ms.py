"""Device time of the local aggregation kernel (``kernels/ell_spmm.py``)
per epoch, averaged over chips.

The Pallas kernel runs as a ``tpu_custom_call`` whose first operand is the
2-D int32 neighbour list ``s32[rows, K]``; the codec kernels' first
operand is a 1-D int32 block list.  Moves ``epoch_s``.
"""

import re

UNIT = "ms/epoch"


def is_ell(op) -> bool:
    return (op.opcode == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in op.text
            and re.match(r"s32\[\d+,\d+\]", op.operands) is not None)


def read(run):
    return run.device_ms_per_epoch(is_ell)
