"""pack_ms.score: the program's ``server.pack`` span per dispatched batch
in the traced window, in ms: the requests' features packed into one
bucket relation on the device."""

from harness import spans


def read(run):
    if run.serve is None:
        return None
    return spans.per_batch_ms(run, "server.pack")
