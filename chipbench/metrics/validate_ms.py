"""Mean host wall time per ``HistogramEngine.validate`` call (the plan
and kernel proofs ``run`` makes before each dispatch), in ms."""

from chipbench.metrics._spans import mean_ms


def read(run):
    return mean_ms(run, "validate")
