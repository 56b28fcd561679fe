"""From a profiler trace to device busy time, per-op times and labelled idle gaps.

``from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict of intervals (seconds on the trace's clock):

    {"spans":   {name: [[start, end], ...]},      # host annotations "bench.*", "repro.*"
     "devices": [{"name": plane, "ops": [[name, start, end], ...],
                  "modules": [[name, start, end], ...]}]}

The host spans are the benchmark's (``bench/spans.py``) and the program's own
(``repro.*``), each under its whole name. The same dict, written as JSON, is
what the tests read. Everything else here works on that dict.
"""
from __future__ import annotations

import bisect
import glob
import itertools
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "repro."
Interval = Tuple[float, float]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _line(plane, name: str):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def _events(line) -> List[list]:
    if line is None:
        return []
    return [[e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9] for e in line.events]


def from_xplane(path: str) -> dict:
    import jax  # only the reading of a trace needs JAX

    data = jax.profiler.ProfileData.from_file(path)
    spans: Dict[str, List[Interval]] = defaultdict(list)
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX)):
                        spans[e.name].append([e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9])
        elif plane.name.startswith("/device:TPU:"):
            devices.append({"name": plane.name, "ops": _events(_line(plane, "XLA Ops")),
                            "modules": _events(_line(plane, "XLA Modules"))})
    return {"spans": dict(spans), "devices": devices}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted((float(s), float(e)) for s, e in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def window_of(trace: dict) -> Optional[Interval]:
    """The measured window: the one ``bench.window`` span."""
    spans = trace["spans"].get(SPAN_PREFIX + "window")
    return tuple(spans[0]) if spans else None


def busy(device: dict, window: Interval) -> List[Interval]:
    """Union of the device's op intervals inside the window."""
    return union(clip([(s, e) for _, s, e in device["ops"]], window))


def busy_seconds(trace: dict, window: Interval) -> Optional[float]:
    """Busy seconds averaged over the devices that ran anything."""
    per = [total(busy(d, window)) for d in trace["devices"] if d["ops"]]
    return sum(per) / len(per) if per else None


def idle_gaps(device: dict, window: Interval) -> List[Interval]:
    lo, hi = window
    gaps, t = [], lo
    for s, e in busy(device, window):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


class SpanIndex:
    """Host spans sorted by start, to name what the host was doing at a time:
    the benchmark's by their call (``bench.`` dropped), the program's by
    their whole name."""

    def __init__(self, spans: Dict[str, List[Interval]]):
        self.items = sorted((s, e, name.removeprefix(SPAN_PREFIX)) for name, ivs in spans.items()
                            if name != SPAN_PREFIX + "window" for s, e in ivs)
        self.starts = [s for s, _, _ in self.items]
        # the latest end among the spans that start at or before each one:
        # once it is at or before t, no earlier span is running at t
        self.reach = list(itertools.accumulate((e for _, e, _ in self.items), max))

    def label(self, t: float) -> str:
        """The innermost span running at ``t`` (of those running, the one
        that began last); "no span" where the host was in none."""
        for i in range(bisect.bisect_right(self.starts, t) - 1, -1, -1):
            if self.reach[i] <= t:
                break
            if self.items[i][1] > t:
                return self.items[i][2]
        return "no span"


def idle_by_span(trace: dict, window: Interval, top: int = 10) -> List[list]:
    """Idle seconds of the first device, summed by the host span each gap's
    midpoint falls in, largest first."""
    devs = [d for d in trace["devices"] if d["ops"]]
    if not devs:
        return []
    index = SpanIndex(trace["spans"])
    acc: Dict[str, float] = defaultdict(float)
    for s, e in idle_gaps(devs[0], window):
        acc[index.label((s + e) / 2)] += e - s
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def _short(hlo: str) -> str:
    """"%fusion.3 = bf16[8,128]{...} fusion(...)" -> "%fusion.3 bf16[8,128]"."""
    name, _, rest = hlo.partition(" = ")
    return f"{name} {rest.split('{')[0].split(' ')[0]}".strip() if rest else hlo


def leaves(ops: List[list]) -> List[list]:
    """The ops that hold no other op: a loop's event spans the ops of its
    body on the same line, and would count them twice."""
    out = []
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    for i, (name, s, e) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or not (nxt[1] >= s and nxt[2] <= e):
            out.append([name, s, e])
    return out


def top_ops(trace: dict, window: Interval, top: int = 10) -> List[list]:
    """Device seconds per op inside the window (first device, innermost ops
    only), named by their program and short HLO name, largest first."""
    devs = [d for d in trace["devices"] if d["ops"]]
    if not devs:
        return []
    mods = sorted((s, e, name.split("(")[0]) for name, s, e in devs[0]["modules"])
    starts = [m[0] for m in mods]
    acc: Dict[str, float] = defaultdict(float)
    for name, s, e in leaves(devs[0]["ops"]):
        for cs, ce in clip([(s, e)], window):
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= e else "?"
            acc[f"{prog}:{_short(name)}"] += ce - cs
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def module_seconds(trace: dict, window: Interval, needle: str) -> Tuple[float, int]:
    """(device seconds, executions) of the programs whose name holds
    ``needle``, started inside the window, summed over devices."""
    secs, n = 0.0, 0
    for d in trace["devices"]:
        for name, s, e in d["modules"]:
            if needle in name and window[0] <= s < window[1]:
                secs += e - s
                n += 1
    return secs, n


def span_seconds(trace: dict, window: Interval, names: Sequence[str]) -> Tuple[float, int]:
    """(seconds of the union of the named spans, number of spans) inside the
    window; nested and overlapping spans count once."""
    ivs = [tuple(iv) for n in names for iv in trace["spans"].get(SPAN_PREFIX + n, [])]
    inside = [iv for iv in ivs if window[0] <= iv[0] < window[1]]
    return total(union(clip(inside, window))), len(inside)
