"""Mean host wall time of one benchmark span over the window."""


def mean_ms(run, name):
    if run.rec is None:
        return None
    lo, hi = run.t0, run.t0 + run.seconds
    d = [dt for t0, dt in run.rec.span_s.get(name, ()) if lo <= t0 <= hi]
    return 1e3 * sum(d) / len(d) if d else None
