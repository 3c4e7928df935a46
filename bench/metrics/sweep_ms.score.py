"""sweep_ms.score: the program's ``server.sweep`` span per dispatched
batch in the traced window (a tick dispatches one), in ms: the walk over
the queue for expired and cancelled requests."""

from harness import spans


def read(run):
    if run.serve is None:
        return None
    return spans.per_batch_ms(run, "server.sweep")
