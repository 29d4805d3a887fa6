"""Host time in the program's ``engine.query`` spans per ``engine.run``,
in ms: every query of a run, summed.  Runs are counted by the program's
``engine.run`` spans, which carry ``representation`` (the benchmark's
wrapper of the same name carries nothing)."""

from chipbench.metrics._program import spans


def read(run):
    runs = [s for s in spans(run, "engine.run") if "representation" in s[2]]
    if not runs:
        return None
    return 1e3 * sum(d for _, d, _ in spans(run, "engine.query")) / len(runs)
