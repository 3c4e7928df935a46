"""plan_s: the program's ``engine.plan`` spans over the whole process, in
s: optimizing, lowering and building each artifact the compile cache
missed."""

from harness import spans


def read(run):
    return spans.total_s("engine.plan")
