"""Mean host time of the program's ``engine.compute`` span, in ms: the
dispatch of a whole H."""

from chipbench.metrics._program import mean_ms


def read(run):
    return mean_ms(run, "engine.compute")
