"""Device time of scatter-adds per epoch, averaged over chips: the remote
aggregation of the train step (``_make_aggregate_emulated`` and
``_make_aggregate_shard``) and all aggregation of the evaluation, which
runs on the dense wire.

XLA on the TPU runs a scatter as a ``kind=kCustom`` fusion whose operands
hold the 1-D int32 indices ``s32[L]`` and the updates ``f32[L, ...]``,
both of the same length ``L``; a gather's table has another length.
Moves ``epoch_s``.
"""

import re

UNIT = "ms/epoch"

_IDX = re.compile(r"s32\[(\d+)\]")
_UPD = re.compile(r"f32\[(\d+),\d+\]")


def is_scatter(op) -> bool:
    if op.opcode != "fusion" or "kind=kCustom" not in op.text:
        return False
    ops = op.operands
    return bool(set(_IDX.findall(ops)) & set(_UPD.findall(ops)))


def read(run):
    return run.device_ms_per_epoch(is_scatter)
