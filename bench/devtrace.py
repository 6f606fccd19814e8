"""Reduce a profiler trace to the device's busy time, idle gaps and
heaviest operations.

A trace is read into plain events: per device, the intervals in which
an XLA operation ran (``start_ns, end_ns, name``), and the benchmark's
own host spans (names starting with ``bench.``).  Busy time is the
union of a device's operation intervals, so overlapping operations
count once; the idle share of a window is one minus busy over its
length.  Each idle gap is labelled by the innermost benchmark span open
at its midpoint, which says what the host was doing meanwhile.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

Interval = Tuple[int, int]
SPAN_PREFIX = "bench."
OUTSIDE = "outside_spans"
NAME_CHARS = 120     # an XLA op's name is its whole HLO line; keep its head


def merge(intervals) -> List[Interval]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted((int(s), int(e)) for s, e in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(union: List[Interval], lo: int, hi: int) -> int:
    """Length of ``union`` inside ``[lo, hi]``."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in union)


def gaps(union: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle stretches of ``[lo, hi]`` that ``union`` leaves open."""
    out, t = [], lo
    for s, e in union:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class Trace:
    """Device operations per device and the benchmark's host spans."""

    def __init__(self, device_ops: Dict[str, List[tuple]],
                 spans: List[tuple]):
        self.device_ops = device_ops
        self.spans = sorted(spans)
        self.busy = {d: merge((s, e) for s, e, _ in ops)
                     for d, ops in device_ops.items()}

    @classmethod
    def from_events(cls, events: dict) -> "Trace":
        """From ``{"device_ops": {dev: [[start, end, name], ...]},
        "spans": [[start, end, name], ...]}`` (nanoseconds)."""
        return cls({d: [tuple(o) for o in ops]
                    for d, ops in events["device_ops"].items()},
                   [tuple(s) for s in events["spans"]])

    def spans_named(self, name: str) -> List[tuple]:
        return [s for s in self.spans if s[2] == name]

    def busy_ns(self, lo: int, hi: int) -> float:
        """Busy nanoseconds in ``[lo, hi]``, averaged over devices."""
        if not self.busy:
            return 0.0
        return sum(covered(u, lo, hi) for u in self.busy.values()) / len(
            self.busy)

    def label(self, t: int) -> str:
        """The innermost benchmark span open at ``t``."""
        best = None
        for s, e, name in self.spans:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return OUTSIDE if best is None else best[2]

    def idle_gaps(self, lo: int, hi: int, top: int = 10) -> List[list]:
        """The longest idle gaps of ``[lo, hi]`` (on the device that is
        busiest), as ``[label, seconds]``, longest first."""
        if not self.busy:
            return []
        dev = max(self.busy, key=lambda d: covered(self.busy[d], lo, hi))
        gs = sorted(gaps(self.busy[dev], lo, hi), key=lambda g: g[0] - g[1])
        return [[self.label((s + e) // 2), (e - s) * 1e-9]
                for s, e in gs[:top]]

    def top_ops(self, lo: int, hi: int, top: int = 10) -> List[list]:
        """Device operations by total self time inside ``[lo, hi]``
        (an operation's time less that of the operations nested in it,
        such as a while loop's body), averaged over devices, as
        ``[name, seconds]``, heaviest first."""
        tot: Dict[str, float] = {}
        for ops in self.device_ops.values():
            for (s, e, name), own in zip(ops, self_times(ops)):
                d = max(0, min(e, hi) - max(s, lo))
                if d and own:
                    tot[name] = tot.get(name, 0.0) + own * d / (e - s)
        n = max(len(self.device_ops), 1)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns * 1e-9 / n] for name, ns in ranked]


def self_times(ops) -> List[int]:
    """Each operation's duration less the durations of the operations
    nested inside it (profilers list a loop and its body ops on one
    line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [e - s for s, e, _ in ops]
    stack: List[int] = []
    for i in order:
        s, e, _ = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, ops[stack[-1]][1]) - s
        stack.append(i)
    return own


def _op_line(plane):
    lines = list(plane.lines)
    named = [ln for ln in lines if ln.name == "XLA Ops"]
    if named:
        return named[0]
    return max(lines, key=lambda ln: sum(1 for _ in ln.events), default=None)


def events_from_profile(profile) -> dict:
    """Plain events from a ``jax.profiler.ProfileData``: the "XLA Ops"
    line of every accelerator plane, and every host event whose name
    starts with ``bench.``."""
    device_ops, spans = {}, []
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            line = _op_line(plane)
            if line is None:
                continue
            device_ops[plane.name] = [
                [int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                 ev.name[:NAME_CHARS]] for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      ev.name])
    return {"device_ops": device_ops, "spans": spans}


def load(trace_dir: str) -> Trace:
    """The trace the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Trace.from_events(
        events_from_profile(ProfileData.from_file(paths[-1])))
