"""The benchmark's own tests.  They run on the CPU at small sizes
(``JAX_PLATFORMS=cpu python -m pytest bench/tests``), outside the
repository's ``tests/``."""

import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


@pytest.fixture(scope="session")
def is_ell_kernel():
    """Picks the Pallas ELL kernel's launches in the recorded traces
    (taken while the train step still ran it): a ``tpu_custom_call`` whose
    first operand is the 2-D int32 neighbour list ``s32[rows, K]``."""
    def pick(op) -> bool:
        return (op.opcode == "custom-call"
                and 'custom_call_target="tpu_custom_call"' in op.text
                and re.match(r"s32\[\d+,\d+\]", op.operands) is not None)
    return pick
