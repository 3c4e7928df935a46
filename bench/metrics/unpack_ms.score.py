"""unpack_ms.score: the program's ``server.unpack`` span per dispatched
batch in the traced window, in ms: the scores split per request and
copied to the host, which waits for the device to finish the batch."""

from harness import spans


def read(run):
    if run.serve is None:
        return None
    return spans.per_batch_ms(run, "server.unpack")
