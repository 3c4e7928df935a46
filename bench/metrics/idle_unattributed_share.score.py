"""idle_unattributed_share.score: of the device's idle time inside the
program's ``server.tick`` spans in the traced window, the share that no
phase span of the tick covers (sweep, lookup, plan, first run, dispatch,
pack, unpack), averaged over the cell's devices."""

from harness import spans
from harness import trace as tr


def read(run):
    if run.serve is None:
        return None
    s = spans.align(run)
    if s is None:
        return None
    ticks = s.inside("server.tick")
    idle = unnamed = 0.0
    for d in run.device_ids:
        busy = run.trace.busy(d)
        starts = [a for a, _ in busy]
        for tick, within in ticks:
            gaps = tr.complement(spans.clip(busy, starts, tick.start,
                                            tick.end), tick.start, tick.end)
            named = tr.union([(e.start, e.end) for e in within
                              if e.name in spans.TICK_PHASES])
            gap_s = sum(b - a for a, b in gaps)
            idle += gap_s
            unnamed += gap_s - spans.covered_s(gaps, named)
    if idle <= 0:
        return None
    return unnamed / idle
