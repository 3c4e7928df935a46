"""collective_ms.train: device time of the collective operations per train
step in the traced window, averaged over the cell's chips, in ms.

A device operation is a collective where its HLO instruction is one: an
``all-gather``, ``reduce-scatter``, ``all-reduce``, ``all-to-all`` or
``collective-permute`` by opcode or by instruction name, or a fusion that
calls a computation so named (the TPU's ``all-reduce-scatter``).  A
synchronous collective counts whole.  An asynchronous one counts its
``-start`` and its ``-done`` (on a TPU also the fusions named
``async-collective-start`` and ``async-collective-done``): the time the
chip waited on the exchange, not the part hidden under compute, which
runs inside other operations.  Where the window holds no collective,
nothing is read."""

import re

KIND = (r"(?:all-gather|reduce-scatter|all-reduce|all-to-all"
        r"|collective-permute|async-collective|all-reduce-scatter)")
NAME = re.compile(rf"^%{KIND}(?:-start|-done)?(?:\.\d+)?$")
CALLS = re.compile(rf"\bcalls=%{KIND}(?:\.\d+)?(?:[,\s]|$)")
INSTRUCTION = re.compile(r"^(%[\w.-]+) = ")
OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


def is_collective(event_name: str) -> bool:
    """Whether a device operation, named by its HLO instruction, is a
    collective (its wait, for an asynchronous one)."""
    m = INSTRUCTION.match(event_name)
    if m is None:
        return False
    op = OPCODE.search(event_name, m.end() - 1)
    names = [m.group(1)] + (["%" + op.group(1)] if op else [])
    return (any(NAME.match(n) for n in names)
            or CALLS.search(event_name) is not None)


def read(run):
    if run.train is None or run.trace is None or not run.train.steps:
        return None
    ids = run.device_ids
    found, seconds = False, 0.0
    for i in ids:
        for e in run.trace.window_ops(i):
            if is_collective(e.name):
                found = True
                seconds += e.seconds
    if not found:
        return None
    return 1e3 * seconds / len(ids) / run.train.steps
