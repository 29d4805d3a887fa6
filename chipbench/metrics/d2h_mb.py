"""Megabytes the program brought to the host per frame completed in the
window: the service's ``d2h_bytes`` counter (every answer it handed back
and every byte of H a query pulled to the host) over the window, divided
by the frames completed in it.  Nothing where the counters carry no
``d2h_bytes`` (a program without the counter) or no frame completed."""


def read(run):
    start, end = run.start.get("total", {}), run.end.get("total", {})
    if "d2h_bytes" not in start or "d2h_bytes" not in end:
        return None
    frames = len(run.done_in_window())
    if frames == 0:
        return None
    return (end["d2h_bytes"] - start["d2h_bytes"]) / frames / 1e6
