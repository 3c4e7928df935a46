"""prelayout_hit_share.score: of the traced window's ticks that dispatched
a batch, the share that laid out no stationary weight (no
``engine.prelayout`` span inside the tick): 1.0 where every weight's
kernel layout was made before the window.  Nothing where the process
never recorded an ``engine.prelayout`` span, as a program that lays out
no weight ahead does not."""

from harness import spans


def read(run):
    if run.serve is None or spans.total_s("engine.prelayout") is None:
        return None
    s = spans.align(run)
    if s is None:
        return None
    ticks = spans.dispatching_ticks(s)
    if not ticks:
        return None
    return sum("engine.prelayout" not in t for t in ticks) / len(ticks)
