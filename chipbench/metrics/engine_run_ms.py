"""Mean host wall time per ``HistogramEngine.run`` call, in ms.  ``run``
returns once its work is dispatched, before the device finishes."""

from chipbench.metrics._spans import mean_ms


def read(run):
    return mean_ms(run, "engine.run")
