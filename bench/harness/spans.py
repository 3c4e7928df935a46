"""The program's own phase spans, put on the device trace's clock.

The program records its phases (``server.tick``, ``engine.lookup``, ...)
in one process-wide ring, ``repro.launch.metering.SPANS``, on
``time.perf_counter``.  The benchmark's ``bench.*`` spans and the device's
operations are on the profiler's clock.  Each program span that the
benchmark wraps, a ``server.tick`` inside its ``bench.server_step`` or a
``train.step`` inside its ``bench.train_step``, bounds the offset between
the two clocks: ``bench.start - prog.start <= offset <= bench.end -
prog.end``.  The intersection over every pair in the window fixes the
offset to within its width.

A reader gets None where the program has no recorder, where the pairs do
not nest (the intersection is empty), where the intersection is wider
than ``MAX_WIDTH_S``, or where the ring no longer holds the whole window.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace as tr

MAX_WIDTH_S = 50e-6

# the benchmark's span -> the program's span it wraps, per flow
WRAPS = {"serve": ("bench.server_step", "server.tick"),
         "train": ("bench.train_step", "train.step")}

# what a tick of the server is spent on, each phase a span of its own
TICK_PHASES = ("server.sweep", "engine.lookup", "engine.plan",
               "engine.first_run", "engine.dispatch", "server.pack",
               "server.unpack")


@dataclasses.dataclass
class Spans:
    offset: float               # trace clock minus the program's clock
    width: float                # of the interval the offset lies in
    events: List[tr.Event]      # inside the window, on the trace's clock,
                                # sorted by start

    def named(self, name: str) -> List[tr.Event]:
        return [e for e in self.events if e.name == name]

    def inside(self, outer: str) -> List[Tuple[tr.Event, List[tr.Event]]]:
        """Each ``outer`` span with the spans that lie within it."""
        starts = [e.start for e in self.events]
        out = []
        for o in self.named(outer):
            lo = bisect.bisect_left(starts, o.start)
            hi = bisect.bisect_right(starts, o.end)
            out.append((o, [e for e in self.events[lo:hi]
                            if e is not o and e.end <= o.end]))
        return out


def recorder():
    """The program's span recorder, or None where the program has none."""
    try:
        from repro.launch import metering
    except ImportError:
        return None
    return getattr(metering, "SPANS", None)


def fit_offset(outer: Sequence[tr.Event], inner: Sequence[tr.Event]
               ) -> Optional[Tuple[float, float]]:
    """The offsets (lo, hi) that put each ``inner[i]`` inside ``outer[i]``,
    or None where no offset does."""
    if not outer or len(outer) != len(inner):
        return None
    lo = max(o.start - i.start for o, i in zip(outer, inner))
    hi = min(o.end - i.end for o, i in zip(outer, inner))
    return (lo, hi) if lo <= hi else None


def align(run, rec=None) -> Optional[Spans]:
    """The recorder's spans inside the run's traced window, on the trace's
    clock.  The last program spans of the wrapped kind pair, in order,
    with the benchmark's wrapping spans: nothing runs the program between
    the window's end and the read."""
    rec = rec if rec is not None else recorder()
    if rec is None or run.trace is None:
        return None
    outer_name, inner_name = WRAPS["serve" if run.serve is not None
                                   else "train"]
    held = [tr.Event(n, a, b, {}) for n, a, b in rec.spans()]
    outer = [s for s in run.trace.spans if s.name == outer_name]
    inner = [e for e in held if e.name == inner_name]
    if len(inner) < len(outer):
        return None
    fit = fit_offset(outer, inner[len(inner) - len(outer):])
    if fit is None or fit[1] - fit[0] > MAX_WIDTH_S:
        return None
    offset = 0.5 * (fit[0] + fit[1])
    lo, hi = run.trace.window()
    if rec.dropped and held[0].end + offset >= lo:
        return None                 # spans that ended in the window are gone
    events = [tr.Event(e.name, e.start + offset, e.end + offset, {})
              for e in held if lo <= e.start + offset and e.end + offset <= hi]
    events.sort(key=lambda e: e.start)
    return Spans(offset, fit[1] - fit[0], events)


def dispatching_ticks(s: Spans) -> List[Dict[str, float]]:
    """Per server tick that dispatched a batch: seconds per phase inside
    it, with ``server.tick`` the whole tick and ``batches`` the number of
    dispatches."""
    out = []
    for tick, within in s.inside("server.tick"):
        phases = {"server.tick": tick.seconds, "batches": 0}
        for e in within:
            phases[e.name] = phases.get(e.name, 0.0) + e.seconds
            phases["batches"] += e.name in ("engine.dispatch",
                                            "engine.first_run")
        if phases["batches"]:
            out.append(phases)
    return out


def per_batch_ms(run, phase: str) -> Optional[float]:
    """Milliseconds of ``phase`` per batch the window's ticks dispatched."""
    s = align(run)
    if s is None:
        return None
    ticks = dispatching_ticks(s)
    batches = sum(t["batches"] for t in ticks)
    if not batches:
        return None
    return 1e3 * sum(t.get(phase, 0.0) for t in ticks) / batches


def per_step_ms(run, phases: Sequence[str]) -> Optional[float]:
    """Milliseconds of ``phases`` inside each train step of the window, on
    average."""
    s = align(run)
    if s is None:
        return None
    steps = s.inside("train.step")
    if not steps:
        return None
    total = sum(e.seconds for _, within in steps for e in within
                if e.name in phases)
    return 1e3 * total / len(steps)


def total_s(name: str) -> Optional[float]:
    """Seconds of every ``name`` span the process recorded."""
    rec = recorder()
    if rec is None:
        return None
    totals = rec.totals()
    return totals[name][1] if name in totals else None


def clip(intervals: Sequence[tr.Interval], starts: Sequence[float],
         lo: float, hi: float) -> List[tr.Interval]:
    """The parts of sorted, disjoint ``intervals``, whose starts are
    ``starts``, inside [lo, hi]."""
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    out = []
    for a, b in intervals[i:]:
        if a >= hi:
            break
        if b > lo:
            out.append((max(a, lo), min(b, hi)))
    return out


def covered_s(intervals: Sequence[tr.Interval],
              cover: Sequence[tr.Interval]) -> float:
    """Seconds of sorted, disjoint ``intervals`` that sorted, disjoint
    ``cover`` overlaps."""
    total, j = 0.0, 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            total += tr.overlap((a, b), cover[k])
            k += 1
    return total

