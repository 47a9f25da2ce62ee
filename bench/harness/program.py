"""The program's own host spans in a profiler trace, and what the chip did
inside them.

The serving engine opens host spans named ``fcvi.<layer>``
(``repro.serve.spans``) at its layer boundaries: ``fcvi.search`` around a
call, ``fcvi.validate`` and ``fcvi.cache`` inside it, one ``fcvi.batch`` per
padded batch, and inside a batch ``fcvi.step`` (stage 1, up to the margins'
arrival on the host), ``fcvi.escalate`` (the stage-2 sub-batch's bookkeeping
and dispatch) and ``fcvi.fetch`` (the host copy, which waits for the last
stage). They lie on the same clock as the device operations that
``tracing.load`` reads, so the device time of each layer is the union of
device operations inside its spans. The harness's own spans (``window``,
``search``, ``wait``) are read by ``tracing`` and never carry the prefix.

A program that opens no such span (an older engine) gives an empty list,
and every reduction here then gives nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

from harness import tracing

PREFIX = "fcvi."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float        # ns, on the clock of the device operations
    end: float
    attrs: dict         # the span's trace statistics
    thread: str         # the host line (thread) it was opened on


@dataclasses.dataclass
class Layer:
    """What one span name covered inside a window: ``count`` spans,
    ``wall_s`` their summed length, ``self_s`` that less the part their
    child spans cover, ``device_s`` the device-operation time inside them
    and ``idle_s`` the rest (no operation on the chip); seconds, device
    times averaged over the chips."""

    count: int = 0
    wall_s: float = 0.0
    self_s: float = 0.0
    device_s: float = 0.0
    idle_s: float = 0.0


# ---------------------------------------------------------------------------
# reading the profiler's file
# ---------------------------------------------------------------------------

def load(path: str) -> List[Span]:
    """The program spans of ``path`` (an ``.xplane.pb`` file, or a
    directory holding one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> List[Span]:
    """The host events of a ``ProfileData`` whose name starts with
    ``fcvi.``, sorted by start."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = float(ev.start_ns)
                    out.append(Span(ev.name, s, s + float(ev.duration_ns),
                                    dict(ev.stats), line.name))
    out.sort(key=lambda sp: (sp.start, -sp.end))
    return out


def within(spans: List[Span], lo: float, hi: float) -> List[Span]:
    return [sp for sp in spans if sp.start >= lo and sp.end <= hi]


# ---------------------------------------------------------------------------
# nesting
# ---------------------------------------------------------------------------

def parents(spans: List[Span]) -> List[Optional[int]]:
    """For each span (sorted by start, longest first at equal starts), the
    index of the innermost span of its thread that encloses it, or None."""
    out: List[Optional[int]] = []
    stacks: Dict[str, list] = defaultdict(list)
    for i, sp in enumerate(spans):
        stack = stacks[sp.thread]
        while stack and spans[stack[-1]].end < sp.end:
            stack.pop()
        out.append(stack[-1] if stack else None)
        stack.append(i)
    return out


class Innermost:
    """The innermost program span open at a given time, on the thread of
    the last span to start before it."""

    def __init__(self, spans: List[Span]):
        self.spans = spans
        self.starts = [sp.start for sp in spans]
        self.parent = parents(spans)

    def at(self, t: float) -> Optional[Span]:
        i = bisect.bisect_right(self.starts, t) - 1
        # a span open at t that started before span i encloses span i
        while i is not None and i >= 0:
            if t < self.spans[i].end:
                return self.spans[i]
            i = self.parent[i]
        return None


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def _chips(trace: tracing.Trace, chips: int):
    return sorted(trace.ops)[:chips] or [0]


def attribute(trace: tracing.Trace, spans: List[Span], lo: float, hi: float,
              chips: int = 1) -> Dict[str, Layer]:
    """Per span name, what its spans inside [lo, hi) covered (``Layer``);
    an empty dict where the window holds no program span."""
    inner = within(spans, lo, hi)
    if not inner:
        return {}
    used = _chips(trace, chips)
    merged = []
    for chip in used:
        m = tracing.union((s, e) for s, e, _ in trace.ops.get(chip, ()))
        merged.append((m, np.array([s for s, _ in m], float)))
    child_ns = [0.0] * len(inner)
    for i, p in enumerate(parents(inner)):
        if p is not None:
            child_ns[p] += inner[i].end - inner[i].start
    out: Dict[str, Layer] = defaultdict(Layer)
    for sp, kids in zip(inner, child_ns):
        wall = sp.end - sp.start
        dev = sum(tracing.covered(m, sp.start, sp.end, st)
                  for m, st in merged) / len(used)
        lay = out[sp.name]
        lay.count += 1
        lay.wall_s += wall * 1e-9
        lay.self_s += (wall - kids) * 1e-9
        lay.device_s += dev * 1e-9
        lay.idle_s += (wall - dev) * 1e-9
    return dict(out)


def idle_gaps(trace: tracing.Trace, spans: List[Span], lo: float, hi: float,
              chips: int = 1, top: int = 10):
    """The device's idle gaps in [lo, hi) on the first chip used, longest
    first, each labelled ``<harness span>><innermost program span>`` at its
    middle, or by the harness span alone where no program span is open
    (the labels ``tracing.reduce`` gives). Returns ([(label, s)] of the
    ``top`` longest, {label: s} over all gaps)."""
    chip = _chips(trace, chips)[0]
    merged = tracing.union((s, e) for s, e, _ in trace.ops.get(chip, ()))
    harness = tracing.SpanIndex(trace.spans)
    program = Innermost(within(spans, lo, hi))
    gap_list, by_label = [], defaultdict(float)
    for s, e in tracing.gaps(merged, lo, hi):
        mid = 0.5 * (s + e)
        lab = harness.label_at(mid)
        sp = program.at(mid)
        if sp is not None:
            lab = f"{lab}>{sp.name}"
        gap_list.append((lab, (e - s) * 1e-9))
        by_label[lab] += (e - s) * 1e-9
    gap_list.sort(key=lambda g: -g[1])
    return gap_list[:top], dict(by_label)


def window(trace: tracing.Trace):
    """(lo, hi) of the trace's harness ``window`` span."""
    wins = [(s, e) for n, s, e, _ in trace.spans if n == "window"]
    if not wins:
        raise ValueError("the trace holds no 'window' span")
    return wins[0]
