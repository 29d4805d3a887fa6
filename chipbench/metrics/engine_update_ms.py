"""Mean host time of the program's ``engine.update`` span, in ms: the
incremental update's dispatches (``core/delta.py``)."""

from chipbench.metrics._program import mean_ms


def read(run):
    return mean_ms(run, "engine.update")
