#!/usr/bin/env python3
"""Device time by program scope, and the program's host spans, from the
window's profiler trace (``.xplane.pb``).

The program names its layers (``repro.tracing``): each device op's HLO
``op_name`` carries the ``jax.named_scope`` names it was traced under,
and the trace keeps it as the ``tf_op`` stat of the op's event metadata.
``jax.profiler.ProfileData`` does not expose event-metadata stats, so
this module decodes the file with a small protobuf wire-format reader of
its own (``XSpace`` → ``planes`` → ``event_metadata``/``stat_metadata``
and the lines' events), with no dependency.

* buckets: every work op of the ``XLA Ops`` line (containers excluded,
  as in ``xplane.py``) goes to exactly one bucket, its first program scope
  in the order of :data:`BUCKETS` (``grad_sync`` counts as
  ``optimizer``, ``layer<i>`` as ``layer``); an op under none is
  ``unscoped``;
* host spans: the program's ``repro.tracing.span`` events, by name, from
  whichever host line holds them (the thread that ran ``train_gnn``);
* idle gaps: the longest gaps between device ops, each put down to the
  innermost program span open at its middle.

    python3 bench/spans.py bench_out/trace/<workload> [--chips N]

prints that reduction as JSON.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import json
import re
import sys
import typing
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import xplane  # noqa: E402

#: bucket order: an op goes to the first of these among its scopes
BUCKETS = ("eval", "halo", "local_agg", "remote_agg", "dense", "loss",
           "optimizer", "layer", "unscoped")
_SCOPE_BUCKET = {"grad_sync": "optimizer"}
_LAYER = re.compile(r"layer\d+")
_IDENT = re.compile(r"[A-Za-z_]\w*")

#: the program's host span names (``setup/*`` besides)
HOST_SPANS = {"epoch", "step", "sync", "eval", "plan", "observe", "log",
              "checkpoint"}


# -- protobuf wire format ----------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an ``int`` for varint
    and fixed-width fields, a ``memoryview`` for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            value = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _int64(v: int) -> int:
    """A varint read as the two's-complement ``int64`` it encodes."""
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for f, v in _fields(buf):
        if f == 1:
            key = _int64(v)
        elif f == 2:
            value = v
    return key, value


@dataclasses.dataclass
class Plane:
    name: str
    lines: list          # [(name, [(metadata id, start ps, duration ps)])]
    metadata: dict       # event metadata id -> (name, {stat name: str})


def _event_metadata(buf, stat_names: dict) -> tuple[str, dict]:
    """``(name, string stats)`` of one ``XEventMetadata``."""
    name, stats = "", {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 5:                                   # XStat
            sid, val = 0, None
            for sf, sv in _fields(v):
                if sf == 1:
                    sid = _int64(sv)
                elif sf == 5:
                    val = _text(sv)
                elif sf == 7:                          # ref to a stat name
                    val = _int64(sv)
            stats[sid] = val
    return name, {stat_names.get(k, str(k)):
                  stat_names.get(v, "") if isinstance(v, int) else v
                  for k, v in stats.items() if v is not None}


def _line(buf) -> tuple[str, list]:
    name, t0_ns, events = "", 0, []
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0_ns = _int64(v)
        elif f == 4:
            events.append(v)
    out = []
    for ev in events:
        mid = off = dur = 0
        for f, v in _fields(ev):
            if f == 1:
                mid = _int64(v)
            elif f == 2:
                off = _int64(v)
            elif f == 3:
                dur = _int64(v)
        out.append((mid, t0_ns * 1000 + off, dur))
    return name, out


def planes(path: Path) -> list[Plane]:
    """The planes of one ``.xplane.pb``: their lines' events and their
    event metadata."""
    data = memoryview(Path(path).read_bytes())
    out = []
    for f, plane in _fields(data):
        if f != 1:
            continue
        name, lines, ev_meta, stat_names = "", [], [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = _text(v)
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                ev_meta.append(v)
            elif pf == 5:
                key, sm = _map_entry(v)
                stat_names[key] = next((_text(sv) for sf, sv in _fields(sm)
                                        if sf == 2), "")
        metadata = {}
        for v in ev_meta:
            key, md = _map_entry(v)
            metadata[key] = _event_metadata(md, stat_names)
        out.append(Plane(name, [_line(v) for v in lines], metadata))
    return out


# -- reduction ---------------------------------------------------------------

def bucket(op_name: str) -> str:
    """The bucket of an op from its ``op_name``: transforms wrap the
    scopes (``transpose(jvp(layer0))/vmap(local_agg)/...``), so every
    identifier in it is a candidate."""
    names = {_SCOPE_BUCKET.get(t, t) for t in _IDENT.findall(op_name)}
    if any(_LAYER.fullmatch(t) for t in names):
        names.add("layer")
    return next((b for b in BUCKETS[:-1] if b in names), "unscoped")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ps: int
    dur_ps: int

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.dur_ps


class ScopedOp(typing.NamedTuple):
    """One work op: its HLO text, its ``op_name`` and its bucket."""
    chip: int
    text: str
    op_name: str
    bucket: str
    start_ps: int
    dur_ps: int


@dataclasses.dataclass
class Scoped:
    chips: int
    ops: list                 # ScopedOp of every work op, all chips
    busy_s: float             # union of op intervals, mean over chips
    work_s: float             # summed work-op time, mean over chips
    bucket_s: dict            # bucket -> summed work-op time, mean/chip
    bucket_ops: dict          # bucket -> op count, all chips
    scoped: bool              # some op carries a program scope
    spans: list               # the program's host spans, by start
    gaps: list                # [(seconds, innermost program span)]

    def ms_per_epoch(self, name: str, epochs: int) -> float | None:
        """A bucket's device time per epoch; ``None`` where the program
        names no scope or nothing ran under this one."""
        if not self.scoped or not self.bucket_ops.get(name):
            return None
        return 1e3 * self.bucket_s[name] / epochs

    def host_loop_s(self) -> float | None:
        """The host's own work in the window's epochs: from the first
        ``epoch`` span's start to the last program span's end, less the
        ``sync`` and ``log`` spans in between (where the host waits for
        the device or runs its caller's hook)."""
        epochs = [s for s in self.spans if s.name == "epoch"]
        if not epochs:
            return None
        t0 = min(s.start_ps for s in epochs)
        t1 = max(s.end_ps for s in self.spans)
        waits = sum(s.dur_ps for s in self.spans
                    if s.name in ("sync", "log") and s.start_ps >= t0
                    and s.end_ps <= t1)
        return (t1 - t0 - waits) / 1e12

    def count(self, name: str) -> int:
        return sum(s.name == name for s in self.spans)

    def summary(self) -> dict:
        return {"chips": self.chips, "ops": len(self.ops),
                "busy_s": self.busy_s,
                "work_s": self.work_s, "bucket_s": self.bucket_s,
                "bucket_ops": self.bucket_ops,
                "host_spans": dict(collections.Counter(
                    s.name for s in self.spans)),
                "host_loop_s": self.host_loop_s(),
                "idle_gaps": self.gaps}


def _label(spans: list, t: int) -> str:
    """The innermost (shortest) program span open at ``t``."""
    best = None
    for s in spans:
        if s.start_ps <= t <= s.end_ps and (best is None
                                            or s.dur_ps < best.dur_ps):
            best = s
    return best.name if best else "no span"


def reduce(trace_dir: Path, chips: int, top: int = 10) -> Scoped:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    dev, spans = collections.defaultdict(list), []
    for plane in planes(files[-1]):
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line_name, events in plane.lines:
            if m and line_name == "XLA Ops":
                chip = int(m.group(1))
                # per op: (text, op_name, bucket); no bucket for a
                # container, which holds other ops and does no work
                kind = {}
                for mid, (text, stats) in plane.metadata.items():
                    op_name = stats.get("tf_op", "")
                    work = xplane.Op(chip, text, 0, 0).opcode \
                        not in xplane.CONTAINERS
                    kind[mid] = (text, op_name,
                                 bucket(op_name) if work else None)
                unknown = ("", "", "unscoped")
                dev[chip] += [ScopedOp(chip, *kind.get(mid, unknown), s, d)
                              for mid, s, d in events]
            elif plane.name == "/host:CPU":
                for mid, s, d in events:
                    name = plane.metadata.get(mid, ("", {}))[0]
                    if name in HOST_SPANS or name.startswith("setup/"):
                        spans.append(Span(name, s, d))
    if len(dev) < chips:
        raise RuntimeError(f"the trace holds ops of {len(dev)} chip(s); "
                           f"the cell runs on {chips}")
    spans.sort(key=lambda s: s.start_ps)
    ops, busy, gaps = [], 0, []
    for chip in sorted(dev)[:chips]:
        covered, holes = xplane._union_ns(
            (op.start_ps, op.start_ps + op.dur_ps) for op in dev[chip])
        busy += covered
        gaps += holes
        ops += [op for op in dev[chip] if op.bucket is not None]
    buckets, n_ops = collections.Counter(), collections.Counter()
    for op in ops:
        buckets[op.bucket] += op.dur_ps
        n_ops[op.bucket] += 1
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [[(e - s) / 1e12, _label(spans, (s + e) // 2)]
                for s, e in gaps[:top]]
    per_chip = 1e12 * chips
    return Scoped(chips=chips, ops=ops, busy_s=busy / per_chip,
                  work_s=sum(buckets.values()) / per_chip,
                  bucket_s={b: buckets[b] / per_chip for b in BUCKETS},
                  bucket_ops={b: n_ops[b] for b in BUCKETS},
                  scoped=len(ops) > n_ops["unscoped"], spans=spans,
                  gaps=labelled)


@functools.lru_cache(maxsize=4)
def _cached(trace_dir: str, chips: int, mtime: float) -> Scoped:
    return reduce(Path(trace_dir), chips)


def for_run(run) -> Scoped | None:
    """The reduction of a run's window trace (where the harness writes
    it), read once however many metrics ask; ``None`` without one."""
    import harness
    trace_dir = harness.OUT / "trace" / run.workload
    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    if run.trace is None or not files:
        return None
    return _cached(str(trace_dir), run.chips, files[-1].stat().st_mtime)


def program_table() -> dict:
    """The program's span table (``repro.tracing``) after the run, empty
    for a program that keeps none."""
    try:
        from repro import tracing
    except ImportError:
        return {}
    return tracing.table()


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", type=Path)
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    print(json.dumps(reduce(args.trace_dir, args.chips).summary()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
