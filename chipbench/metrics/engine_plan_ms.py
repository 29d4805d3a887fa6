"""Mean host time of the program's ``engine.plan`` span, in ms: the spec,
the corner-row union, dirty-band detection and ``plan()``."""

from chipbench.metrics._program import mean_ms


def read(run):
    return mean_ms(run, "engine.plan")
