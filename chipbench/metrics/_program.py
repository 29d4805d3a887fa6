"""The program's own spans in the traced stretch (``spans.Stretch.spans``:
name -> [(start_s, dur_s, {stat: value})]); nothing when the run's trace
holds none."""


def spans(run, name: str) -> list:
    return (getattr(run.trace, "spans", None) or {}).get(name, [])


def mean_ms(run, name: str):
    d = [dur for _, dur, _ in spans(run, name)]
    return 1e3 * sum(d) / len(d) if d else None
