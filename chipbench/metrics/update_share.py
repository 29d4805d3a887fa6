"""Share of the window's engine runs that updated a cached predecessor H
in place (``ServiceStats.updated / engine_runs``), in percent."""


def read(run):
    d = {k: run.end["total"][k] - run.start["total"][k]
         for k in ("engine_runs", "updated")}
    if d["engine_runs"] <= 0:
        return None
    return 100.0 * d["updated"] / d["engine_runs"]
