"""From a profiler trace and the program's spans to busy, idle and gaps.

Device busy time is the union of the intervals of the device's ``XLA Ops``
events, clipped to the window.  The window, and the offset between the
profiler's clock and ``time.perf_counter`` (the program's Tracer clock),
come from one host annotation that the harness opens when the window opens
and closes when it closes.  Idle time is then summed by the innermost
program span the serving thread was in (``untraced`` outside every span).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import pathlib

import numpy as np

WINDOW_ANNOTATION = "chipbench.window"


@dataclasses.dataclass
class DeviceTrace:
    window: tuple[float, float]  # perf_counter seconds
    busy: list[list[tuple[float, float]]]  # per chip, merged, perf_counter s
    ops: dict[str, float]  # op name -> device seconds in the window, all chips

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        return float(np.mean([sum(e - s for s, e in b) for b in self.busy]))


def merge(intervals) -> list[tuple[float, float]]:
    """Union of intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def complement(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The gaps of sorted disjoint ``busy`` inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].lstrip("%").strip() or hlo[:40]


def _module_name(name: str) -> str:
    return name.split("(", 1)[0]


def reduce_xplane(path: str | pathlib.Path, perf_window: tuple[float, float],
                  annotation: str = WINDOW_ANNOTATION) -> DeviceTrace:
    """Reduce one ``.xplane.pb``: the host annotation ``annotation`` marks
    the window, whose ``time.perf_counter`` ends are ``perf_window``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    mark = None
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == annotation:
                        mark = (ev.start_ns, ev.end_ns)
    if mark is None:
        raise ValueError(f"{path}: no {annotation!r} annotation")
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    # profiler ns -> perf_counter s, anchored at the annotation's start.
    off = perf_window[0] - mark[0] * 1e-9

    def conv(ns):
        return ns * 1e-9 + off

    lo, hi = conv(mark[0]), conv(mark[1])
    busy, ops = [], collections.Counter()
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        modules = sorted((conv(e.start_ns), conv(e.end_ns),
                          _module_name(e.name))
                         for e in (lines["XLA Modules"].events
                                   if "XLA Modules" in lines else ()))
        starts = [m[0] for m in modules]
        spans = []
        for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
            s, e = conv(ev.start_ns), conv(ev.end_ns)
            spans.append((s, e))
            cs, ce = max(s, lo), min(e, hi)
            if ce > cs:
                k = bisect.bisect_right(starts, s) - 1
                mod = modules[k][2] if k >= 0 and modules[k][1] >= s else "?"
                ops[f"{mod}/{_op_name(ev.name)}"] += ce - cs
        busy.append(clip(merge(spans), lo, hi))
    return DeviceTrace((lo, hi), busy, dict(ops))


def label_time(spans, intervals) -> dict[str, float]:
    """Seconds of ``intervals`` (sorted, disjoint) under each innermost span
    of ``spans`` ((name, start, end), one thread, nested or disjoint), and
    under ``untraced`` where no span is open."""
    bounds = []
    for k, (name, s, e) in enumerate(spans):
        bounds.append((s, 1, k))
        bounds.append((e, 0, k))
    bounds.sort()
    out: dict[str, float] = collections.defaultdict(float)
    stack: list[int] = []
    prev = -np.inf
    j = 0
    for t, kind, k in bounds + [(np.inf, 0, -1)]:
        # Segment [prev, t) carries the label at the stack's top.
        label = spans[stack[-1]][0] if stack else "untraced"
        while j < len(intervals) and intervals[j][1] <= prev:
            j += 1
        jj = j
        while jj < len(intervals) and intervals[jj][0] < t:
            a, b = max(intervals[jj][0], prev), min(intervals[jj][1], t)
            if b > a:
                out[label] += b - a
            jj += 1
        if kind == 1:
            stack.append(k)
        elif k in stack:
            stack.remove(k)
        prev = t
    return dict(out)


def latest_xplane(trace_dir: str | pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
