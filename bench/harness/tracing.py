"""Profiler trace: recording a window, and its reduction to numbers.

The harness wraps each phase of a run in a host span of its own
(``jax.profiler.TraceAnnotation``): ``window`` around the measured window,
``search`` around each call of the system under test, ``wait`` while no
request is due. The reduction needs no name of the program's operations:

- busy: the union of the intervals in which an operation ran on a chip;
- device time of a call: that union inside the call's ``search`` span;
- host time of a call: the rest of the span (no operation on the chip);
- idle gaps: the stretches of the window with no operation on the chip,
  each labelled by the harness span open at its middle.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np

SPANS = ("window", "search", "wait")
Interval = Tuple[float, float]


@dataclasses.dataclass
class Trace:
    """What the reduction reads: device operations per chip and the
    harness's spans, all in ns on one clock."""

    ops: dict            # chip index -> list of (start, end, name)
    spans: list          # (name, start, end, queries)


def span_factory(enabled: bool):
    """``span(name, **stats)`` context manager: a profiler annotation when
    tracing, nothing otherwise."""
    if not enabled:
        return lambda name, **kw: contextlib.nullcontext()
    from jax.profiler import TraceAnnotation

    return lambda name, **kw: TraceAnnotation(name, **kw)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Interval], lo: float, hi: float,
            starts: Optional[np.ndarray] = None) -> float:
    """Length of [lo, hi) covered by the disjoint sorted ``merged``
    (``starts``: their start times, when the caller has them already)."""
    if not merged or hi <= lo:
        return 0.0
    if starts is None:
        starts = np.fromiter((s for s, _ in merged), float, len(merged))
    i = max(int(np.searchsorted(starts, lo, side="right")) - 1, 0)
    tot = 0.0
    while i < len(merged) and merged[i][0] < hi:
        s, e = merged[i]
        tot += max(0.0, min(e, hi) - max(s, lo))
        i += 1
    return tot


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi) that ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in merged:
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


class SpanIndex:
    """The harness spans other than ``window`` (they never nest), for
    finding the one open at a given time."""

    def __init__(self, spans: Sequence[tuple]):
        inner = sorted((s, e, n) for n, s, e, _ in spans if n != "window")
        self.starts = np.array([s for s, _, _ in inner], float)
        self.ends = [e for _, e, _ in inner]
        self.names = [n for _, _, n in inner]

    def label_at(self, t: float) -> str:
        i = int(np.searchsorted(self.starts, t, side="right")) - 1
        if i >= 0 and t < self.ends[i]:
            return self.names[i]
        return "harness"


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                 # averaged over the chips
    search_device_s: float        # device time inside search spans
    search_host_s: float          # search span time with no device op
    search_queries: int
    calls: list                   # per search span: (queries, device_s)
    top_ops: list                 # [(name, seconds)] most time first
    idle_gaps: list               # [(label, seconds)] longest first
    idle_by_label: dict


def reduce(trace: Trace, chips: int = 1, top: int = 10) -> Reduction:
    wins = [(s, e) for n, s, e, _ in trace.spans if n == "window"]
    if not wins:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = wins[0]
    searches = sorted((s, e, q) for n, s, e, q in trace.spans
                      if n == "search" and s >= lo and e <= hi)
    busy, dev_in, host_in = 0.0, 0.0, 0.0
    calls = [[q, 0.0] for _, _, q in searches]
    op_time = defaultdict(float)
    gap_list, by_label = [], defaultdict(float)
    used = sorted(trace.ops)[:chips] or [0]
    index = SpanIndex(trace.spans)
    for chip in used:
        merged = union((s, e) for s, e, _ in trace.ops.get(chip, ()))
        starts = np.array([s for s, _ in merged], float)
        busy += covered(merged, lo, hi, starts)
        for j, (s, e, _) in enumerate(searches):
            d = covered(merged, s, e, starts)
            calls[j][1] += d / len(used)
            dev_in += d
            host_in += (e - s) - d
        for s, e, name in trace.ops.get(chip, ()):
            if s >= lo and e <= hi:
                op_time[name] += (e - s) / len(used)
        if chip == used[0]:
            for s, e in gaps(merged, lo, hi):
                lab = index.label_at(0.5 * (s + e))
                gap_list.append((lab, (e - s) * 1e-9))
                by_label[lab] += (e - s) * 1e-9
    k = len(used)
    gap_list.sort(key=lambda g: -g[1])
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=busy / k * 1e-9,
        search_device_s=dev_in / k * 1e-9, search_host_s=host_in / k * 1e-9,
        search_queries=int(sum(q for _, _, q in searches)),
        calls=[(int(q), d * 1e-9) for q, d in calls],
        top_ops=[(n, t * 1e-9) for n, t in ops_sorted],
        idle_gaps=gap_list[:top], idle_by_label=dict(by_label))


def require_device_work(red: Reduction):
    """Raise where the traced window shows no operation on the chip, or no
    ``search`` call, or no device time inside those calls: the trace was
    then misread (a plane or line name the reader does not know), and a
    metric read from it would be silently wrong."""
    if red.search_queries <= 0:
        raise ValueError("the traced window holds no 'search' span with "
                         "queries")
    if red.busy_s <= 0:
        raise ValueError("no device operation was read in the traced window "
                         "(is the chip's plane or its 'XLA Ops' line named "
                         "otherwise?)")
    if red.search_device_s <= 0:
        raise ValueError("no device operation was read inside the 'search' "
                         "spans")


# ---------------------------------------------------------------------------
# reading the profiler's file
# ---------------------------------------------------------------------------

DEVICE_LINES = ("XLA Ops",)


def _stat(ev, key) -> Optional[str]:
    try:
        for k, v in ev.stats:
            if k == key:
                return v
    except Exception:   # stats are optional in a plane
        return None
    return None


def load(path: str) -> Trace:
    """Read ``path`` (an ``.xplane.pb`` file, or a directory holding one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    """The device operations and harness spans of a ``ProfileData``."""
    ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            try:
                chip = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            lst = ops.setdefault(chip, [])
            for line in plane.lines:
                if line.name not in DEVICE_LINES:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    mod = _stat(ev, "hlo_module")
                    name = f"{mod}/{ev.name}" if mod else ev.name
                    lst.append((s, s + float(ev.duration_ns), name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        s = float(ev.start_ns)
                        q = _stat(ev, "queries")
                        spans.append((ev.name, s, s + float(ev.duration_ns),
                                      int(q) if q is not None else 0))
    return Trace(ops=ops, spans=spans)
