"""queue_wait_ms.score: the median, over the requests submitted in the
traced window, of the program's ``server.queue`` span (submit to the
tick that packed the request), in ms."""

import numpy as np

from harness import spans


def read(run):
    if run.serve is None:
        return None
    s = spans.align(run)
    if s is None:
        return None
    waits = [e.seconds for e in s.named("server.queue")]
    if not waits:
        return None
    return 1e3 * float(np.median(waits))
