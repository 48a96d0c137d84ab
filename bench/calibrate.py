#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not part of a
benchmark run).

    python3 bench/calibrate.py --workload sage3-arxiv.random.full \\
        --seeds 101-112 --fault-seeds 3

For each seed it drives the cell's timed path as a run does, through the
first steps only, and prints one JSON line: the program's numbers
against the reference (the lower readings), the control's (the
reference at the next precision down, ``high``, against the reference),
and on the first ``--fault-seeds`` seeds the planted faults', each the
reference with the fault against the reference:

* ``half_batch``: every other training node left out, the mean over the
  rest;
* ``no_exchange``: the edges between partitions left out of the
  aggregation (the halo exchange), weights unchanged.

A step that returns its state unchanged reads ``change_gap`` 1 by
definition and needs no run.  Everything runs in one process, so the
programs compile once.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402


def seeds(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi) + 1)) if hi else [int(lo)]
    return out


def detail(got: dict, want: dict) -> dict:
    """Per step and per leaf: where a number's worst case comes from."""
    steps = np.abs(got["loss"] - want["loss"]) / np.abs(want["loss"])
    leaves = {}
    for k in want["grad1"]:
        g, w = (float(np.linalg.norm(t[k])) for t in (got["grad1"],
                                                       want["grad1"]))
        cg, cw = (float(np.linalg.norm(t["pn"][k] - t["p0"][k]))
                  for t in (got, want))
        leaves[k] = {"size": int(want["grad1"][k].size), "grad": w,
                     "grad_gap": abs(g - w) / w, "change": cw,
                     "change_gap": abs(cg - cw) / cw}
    return {"loss_gap_per_step": steps.tolist(), "leaves": leaves}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112,7")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out", help="also append the lines to this file")
    ap.add_argument("--detail", action="store_true",
                    help="add each step's loss gap and each leaf's gaps")
    args = ap.parse_args(argv)
    for i, seed in enumerate(seeds(args.seeds)):
        t0 = time.perf_counter()
        print(f"seed {seed}: program", file=sys.stderr, flush=True)
        kept: dict = {}
        result = harness.run(args.workload, seed, 0.0, False, keep=kept)
        line = {"workload": args.workload, "seed": seed,
                "program": kept["nums"], "correct": result["correct"]}
        arrays, model, ref = kept["arrays"], kept["model"], kept["ref"]
        family = kept["family"]
        steps = harness.CHECKED_STEPS
        print(f"seed {seed}: control", file=sys.stderr, flush=True)
        ctl = reference.train_readings(family, arrays, model, seed, steps,
                                       "high")
        line["control"] = check.numbers(ctl, ref)
        if args.detail:
            line["detail"] = {"program": detail(kept["prog"], ref),
                              "control": detail(ctl, ref)}
        if i < args.fault_seeds:
            print(f"seed {seed}: faults", file=sys.stderr, flush=True)
            half = dict(arrays)
            train = np.flatnonzero(arrays["train_mask"])
            half["train_mask"] = arrays["train_mask"].copy()
            half["train_mask"][train[::2]] = False
            faults = {"half_batch": reference.train_readings(
                family, half, model, seed, steps,
                model["matmul_precision"])}
            owner = kept["owner"]
            n = len(arrays["indptr"]) - 1
            dst = np.repeat(np.arange(n), np.diff(arrays["indptr"]))
            local = owner[dst] == owner[arrays["indices"]]
            faults["no_exchange"] = reference.train_readings(
                family, arrays, model, seed, steps,
                model["matmul_precision"], edge_keep=local)
            line["faults"] = {k: check.numbers(v, ref)
                              for k, v in faults.items()}
        line["seconds"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
