"""dispatch_ms.score: the program's ``engine.dispatch`` span per
dispatched batch in the traced window, in ms: the compiled artifact's
call, its inputs coerced and the program enqueued."""

from harness import spans


def read(run):
    if run.serve is None:
        return None
    return spans.per_batch_ms(run, "engine.dispatch")
