"""Device time of the dense layers' products per epoch, averaged over
chips (``nn/gnn.py``, ``nn/modules.dense``): XLA on the TPU runs a dot as
a convolution, alone or as the root of a ``kind=kOutput`` fusion.
Moves ``epoch_s``.
"""

UNIT = "ms/epoch"


def is_matmul(op) -> bool:
    return (op.opcode == "convolution"
            or (op.opcode == "fusion" and "kind=kOutput" in op.text))


def read(run):
    return run.device_ms_per_epoch(is_matmul)
