"""host_ms.train: the program's ``engine.lookup`` and ``engine.dispatch``
spans inside each ``train.step`` of the traced window, on average, in ms:
the host's work before the step's program is enqueued, which the device
waits on between steps."""

from harness import spans


def read(run):
    if run.train is None:
        return None
    return spans.per_step_ms(run, ("engine.lookup", "engine.dispatch"))
