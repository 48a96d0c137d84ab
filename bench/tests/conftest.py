"""The benchmark's own tests.  They run on the CPU at small sizes
(``JAX_PLATFORMS=cpu python -m pytest bench/tests``), outside the
repository's ``tests/``."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
