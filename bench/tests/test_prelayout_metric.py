"""prelayout_hit_share.score on synthetic runs of the tiny score cell: the
share of dispatching ticks that laid out no stationary weight."""
from harness.manifest import metric_reader
from test_spans import TICK, _recorder, _score_run, _trace, use  # noqa: F401

# a tick whose dispatch laid out a weight: the span sits inside the dispatch
MISS = TICK + [("engine.prelayout", 0.0131, 0.0134)]


def _read():
    run = _score_run(_trace("bench.server_step"))
    return metric_reader("prelayout_hit_share.score").read(run)


def test_every_tick_hits_where_the_layouts_were_made_in_set_up(use):
    rec = _recorder("server.tick", TICK)
    rec.record("engine.prelayout", 6.1 - 100.0, 6.2 - 100.0)   # in set-up
    use(rec)
    assert _read() == 1.0


def test_a_tick_that_lays_out_a_weight_is_a_miss(use):
    use(_recorder("server.tick", MISS))
    assert _read() == 0.0


def test_nothing_where_the_program_never_lays_out_ahead(use):
    use(_recorder("server.tick", TICK))
    assert _read() is None
