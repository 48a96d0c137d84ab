"""Reduction of a profiler trace (``.xplane.pb``) to device times.

The TPU runtime writes one plane per chip (``/device:TPU:<n>``); its
``XLA Ops`` line holds one event per HLO instruction run, named by the
instruction's text (``%name = <shape> <opcode>(<operands>), ...``), and
the host's Python thread is the line of ``/host:CPU`` named after the
interpreter (``python3``).
Control-flow instructions (``while``, ``conditional``, ``call``) contain
the instructions they run, so only the others count as work.

* busy time: the union of the chip's op intervals, averaged over chips;
* per-pattern time: the summed durations of the ops a predicate picks,
  averaged over chips (``None`` when it picks none);
* breakdown: the ops that took the most time, and the longest gaps
  between ops labelled by the innermost host span that covers each.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path

CONTAINERS = {"while", "conditional", "call"}
_OPCODE = re.compile(r"([\w\-]+)\(")


@dataclasses.dataclass(frozen=True)
class Op:
    chip: int
    text: str
    start_ns: int
    dur_ns: int

    @property
    def name(self) -> str:
        return self.text.split(" = ", 1)[0].lstrip("%")

    @property
    def shape(self) -> str:
        """The result's shape: ``f32[42337,256]`` or a tuple."""
        rest = self.text.split(" = ", 1)[1] if " = " in self.text else ""
        if rest.startswith("("):
            depth = 0
            for i, ch in enumerate(rest):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    return rest[:i + 1]
        return rest.split(" ", 1)[0]

    @property
    def opcode(self) -> str:
        rest = self.text.split(" = ", 1)[1] if " = " in self.text else ""
        m = _OPCODE.match(rest[len(self.shape):].lstrip())
        return m.group(1) if m else ""

    @property
    def operands(self) -> str:
        """The operand list's text, shapes included."""
        rest = self.text.split(" = ", 1)[1] if " = " in self.text else ""
        tail = rest[len(self.shape):].lstrip()
        start = tail.find("(")
        depth = 0
        for i in range(start, len(tail)):
            depth += tail[i] == "("
            depth -= tail[i] == ")"
            if depth == 0:
                return tail[start + 1:i]
        return ""


@dataclasses.dataclass
class Reduction:
    ops: list            # work ops (no containers), all chips
    chips: int
    busy_s: float        # mean over chips
    window_s: float      # first op start to last op end, over all chips
    gaps: list           # [(seconds, label)] longest first

    def op_seconds(self, pick) -> float | None:
        got = [op.dur_ns for op in self.ops if pick(op)]
        return sum(got) / 1e9 / self.chips if got else None

    def breakdown(self, top: int = 10) -> dict:
        per = collections.Counter()
        for op in self.ops:
            per[f"{op.opcode} {op.shape} {op.name}"[:160]] += op.dur_ns
        return {"device_ops": [[k, v / 1e9 / self.chips]
                               for k, v in per.most_common(top)],
                "idle_gaps": [[label, s] for s, label in self.gaps[:top]]}


def _union_ns(intervals) -> tuple[int, list]:
    """Total covered length and the gaps ``(start, end)`` between the
    covered stretches."""
    total, gaps, cur_s, cur_e = 0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s > cur_e:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def _host_label(spans, t: int) -> str:
    """The shortest host span covering time ``t``, else the last one that
    ended before it."""
    best, last = None, None
    for s, d, name in spans:
        if s <= t <= s + d and (best is None or d < best[0]):
            best = (d, name)
        elif s + d < t and (last is None or s + d > last[0]):
            last = (s + d, name)
    if best:
        return best[1][:120]
    return f"after {last[1]}"[:120] if last else "host: no span"


def load(path: Path):
    """``(device ops by chip, host python spans)`` of one trace file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    dev, host = collections.defaultdict(list), []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                dev[int(m.group(1))] += [
                    Op(int(m.group(1)), e.name, int(e.start_ns),
                       int(e.duration_ns)) for e in line.events]
            elif plane.name == "/host:CPU" and line.name.startswith("python"):
                host += [(int(e.start_ns), int(e.duration_ns), e.name)
                         for e in line.events]
    return dev, host


def reduce(trace_dir: Path, chips: int) -> Reduction:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    dev, host = load(files[-1])
    if len(dev) < chips:
        raise RuntimeError(f"the trace holds ops of {len(dev)} chip(s); "
                           f"the cell runs on {chips}")
    ops, busy, gaps = [], 0, []
    lo = min(op.start_ns for c in dev for op in dev[c])
    hi = max(op.start_ns + op.dur_ns for c in dev for op in dev[c])
    for chip in sorted(dev)[:chips]:
        chip_ops = dev[chip]
        covered, holes = _union_ns((op.start_ns, op.start_ns + op.dur_ns)
                                   for op in chip_ops)
        busy += covered
        gaps += holes
        ops += [op for op in chip_ops if op.opcode not in CONTAINERS]
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [((e - s) / 1e9, _host_label(host, (s + e) // 2))
                for s, e in gaps[:10]]
    return Reduction(ops=ops, chips=chips, busy_s=busy / 1e9 / chips,
                     window_s=(hi - lo) / 1e9, gaps=labelled)
