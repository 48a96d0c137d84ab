#!/usr/bin/env python3
"""Benchmark entry: one run of one cell on the chips of this machine.

    python3 bench/run.py --workload sage3-arxiv.random.full --seed 7 \\
        --seconds 40 --trace 0

Prints the run's result as the last line of standard output (one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``: each number
that decided ``correct`` beside its limit), and the same numbers as the
last lines of standard error.  Exits non-zero with no result when JAX
finds no TPU or fewer chips than the cell asks for, and when the run
fails.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

# the TPU runtime's logs stay inside the checkout, not at a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", str(harness.OUT / "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
