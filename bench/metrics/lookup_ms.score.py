"""lookup_ms.score: the program's ``engine.lookup`` span per dispatched
batch in the traced window, in ms: the compile cache's structural key
built, ``plan_sig`` included, and looked up."""

from harness import spans


def read(run):
    if run.serve is None:
        return None
    return spans.per_batch_ms(run, "engine.lookup")
