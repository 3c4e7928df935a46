"""The program's phase spans put on the trace's clock, and the per-layer
metrics that read them, on synthetic runs of the tiny cells.

The program's clock runs ``OFFSET`` seconds behind the trace's.  Each
program ``server.tick``/``train.step`` lies ``SLACK`` inside the
benchmark's span around it, so the pairs fix the offset to
[OFFSET - SLACK, OFFSET + SLACK].
"""
import numpy as np
import pytest

import tiny
from harness import load, runner, spans
from harness import trace as tr
from harness.manifest import metric_reader
from repro.launch import metering
from repro.launch.metering import SpanRecorder

OFFSET = 100.0
SLACK = 2e-6
WINDOW = (10.0, 11.0)
STARTS = (10.1, 10.3, 10.5, 10.7)        # of each tick or step, trace clock

# (name, start, end) after a tick's start, in s
TICK = [("server.sweep", 1e-5, 0.00101), ("engine.lookup", 0.002, 0.0025),
        ("server.pack", 0.003, 0.013), ("engine.dispatch", 0.013, 0.0135),
        ("server.unpack", 0.014, 0.094),
        ("server.queue", -0.05, 0.001), ("server.queue", -0.02, 0.001)]
STEP = [("engine.lookup", 1e-5, 0.00051), ("engine.dispatch", 0.001, 0.003)]


def _prog(rec, name, a, b):
    rec.record(name, a - OFFSET, b - OFFSET)


def _recorder(inner, phases, slack=SLACK, starts=STARTS, capacity=1 << 10):
    """A recorder holding set-up spans, then the window's: ``inner`` around
    ``phases`` at each of ``starts``, in the order they end."""
    rec = SpanRecorder(capacity=capacity)
    _prog(rec, "engine.plan", 5.0, 5.5)
    _prog(rec, "engine.first_run", 6.0, 7.0)
    _prog(rec, inner, 7.0, 7.1)               # a step or tick of set-up
    for t in starts:
        for name, a, b in sorted(phases, key=lambda p: p[2]):
            _prog(rec, name, t + a, t + b)
        _prog(rec, inner, t + slack, t + 0.1 - slack)
    return rec


def _trace(outer, starts=STARTS, ops=()):
    spans_ = [tr.Event("bench.window", *WINDOW, {})] + [
        tr.Event(outer, t, t + 0.1, {}) for t in starts]
    return tr.Trace({0: sorted(ops, key=lambda e: e.start)}, spans_)


def _score_run(trace):
    n = 2 * len(STARTS)
    window = load.OpenLoopWindow(
        1.0, np.zeros(n), np.zeros(n), np.zeros(n), [None] * n, 0,
        [(t - WINDOW[0], t + 0.1 - WINDOW[0], 2) for t in STARTS], 1.0)
    return runner.Run(tiny.cell("speech-100k", "score_poisson"), 1,
                      tiny.PEAKS, tiny.devices(), 1.0, 1.0, n, 0, {}, 0,
                      serve=window, trace=trace)


def _train_run(trace):
    window = load.TrainWindow(len(STARTS), 0.8, [1.0] * len(STARTS),
                              [0.2 * (k + 1) for k in range(len(STARTS))])
    return runner.Run(tiny.cell("speech-100k", "train"), 1, tiny.PEAKS,
                      tiny.devices(), 1.0, 0.8, len(STARTS), 0, {}, 0,
                      train=window, trace=trace)


def _score_ops():
    """Per tick: 10 ms of device work, inside unpack."""
    return [e for t in STARTS for e in (
        tr.Event("%pad.9", t + 0.014, t + 0.018, {}),
        tr.Event("%matmul.2", t + 0.018, t + 0.024, {}))]


@pytest.fixture
def use(monkeypatch):
    def install(rec):
        monkeypatch.setattr(metering, "SPANS", rec)
    return install


def _events(pairs):
    return [tr.Event("x", a, b, {}) for a, b in pairs]


# -- the clock offset ----------------------------------------------------

def test_fit_offset_is_the_intersection_of_the_nested_pairs():
    outer = _events([(1.0, 2.0), (3.0, 3.5)])
    inner = _events([(0.85, 1.75), (2.82, 3.28)])
    # the first pair admits [0.15, 0.25], the second [0.18, 0.22]
    assert spans.fit_offset(outer[:1], inner[:1]) == pytest.approx(
        (0.15, 0.25))
    assert spans.fit_offset(outer, inner) == pytest.approx((0.18, 0.22))


def test_fit_offset_is_none_where_no_offset_nests_every_pair():
    outer = _events([(1.0, 2.0), (3.0, 3.5)])
    inner = _events([(0.85, 1.75), (2.5, 2.9)])     # [0.15, 0.25], [0.5, 0.6]
    assert spans.fit_offset(outer, inner) is None
    assert spans.fit_offset(outer, inner[:1]) is None
    assert spans.fit_offset([], []) is None


def test_align_puts_the_window_on_the_trace_clock(use):
    use(_recorder("server.tick", TICK))
    s = spans.align(_score_run(_trace("bench.server_step")))
    assert s.offset == pytest.approx(OFFSET, abs=1e-9)
    assert s.width == pytest.approx(2 * SLACK)
    ticks = s.named("server.tick")
    assert [t.start for t in ticks] == pytest.approx(
        [t + SLACK for t in STARTS])
    # set-up's spans lie before the window and are left out
    assert not s.named("engine.plan") and not s.named("engine.first_run")
    assert len(s.named("server.queue")) == 2 * len(STARTS)


def test_align_is_none_where_the_pairs_do_not_nest(use):
    use(_recorder("server.tick", TICK, starts=STARTS[:-1] + (10.9,)))
    assert spans.align(_score_run(_trace("bench.server_step"))) is None


def test_align_is_none_where_the_offset_is_not_fixed_to_50_us(use):
    use(_recorder("server.tick", TICK, slack=30e-6))
    assert spans.align(_score_run(_trace("bench.server_step"))) is None
    use(_recorder("server.tick", TICK, slack=20e-6))
    assert spans.align(_score_run(_trace("bench.server_step"))) is not None


def test_align_is_none_where_the_ring_dropped_spans_of_the_window(use):
    whole = _recorder("server.tick", TICK)
    # room for the window's spans and set-up's last one, not for more
    window = sum(1 for _, a, b in whole.spans() if a + OFFSET > WINDOW[0])
    use(_recorder("server.tick", TICK, capacity=window + 1))
    assert spans.align(_score_run(_trace("bench.server_step"))) is not None
    use(_recorder("server.tick", TICK, capacity=window - 1))
    assert spans.align(_score_run(_trace("bench.server_step"))) is None


def test_readers_find_nothing_where_the_program_has_no_recorder(
        monkeypatch):
    monkeypatch.delattr(metering, "SPANS")
    run = _score_run(_trace("bench.server_step"))
    for name in ("queue_wait_ms.score", "pack_ms.score", "plan_s",
                 "idle_unattributed_share.score"):
        assert metric_reader(name).read(run) is None


# -- the readers -----------------------------------------------------------

def _unattributed_share():
    """Per tick, device idle outside the phases: before the sweep, the
    gaps between sweep, lookup, pack and unpack, and after unpack."""
    idle = (0.014 - SLACK) + (0.1 - SLACK - 0.024)
    unnamed = (1e-5 - SLACK) + (0.002 - 0.00101) + (0.003 - 0.0025) \
        + (0.014 - 0.0135) + (0.1 - SLACK - 0.094)
    return unnamed / idle


@pytest.mark.parametrize("name,want", [
    ("queue_wait_ms.score", 36.0),          # median of 51 and 21 ms
    ("sweep_ms.score", 1.0),
    ("lookup_ms.score", 0.5),
    ("pack_ms.score", 10.0),
    ("dispatch_ms.score", 0.5),
    ("unpack_ms.score", 80.0),
    ("idle_unattributed_share.score", _unattributed_share()),
    ("plan_s", 0.5),
    ("first_run_s", 1.0),
])
def test_score_readers(use, name, want):
    use(_recorder("server.tick", TICK))
    run = _score_run(_trace("bench.server_step", ops=_score_ops()))
    assert metric_reader(name).read(run) == pytest.approx(want, rel=1e-6)


def test_host_ms_train_reads_lookup_and_dispatch_inside_each_step(use):
    use(_recorder("train.step", STEP))
    run = _train_run(_trace("bench.train_step"))
    # 0.5 ms lookup + 2 ms dispatch per step
    assert metric_reader("host_ms.train").read(run) == pytest.approx(
        2.5, rel=1e-6)
