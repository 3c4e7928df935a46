"""first_run_s: the program's ``engine.first_run`` spans over the whole
process, in s: each artifact's first call, which traces it and compiles
it or loads it from JAX's persistent cache."""

from harness import spans


def read(run):
    return spans.total_s("engine.first_run")
