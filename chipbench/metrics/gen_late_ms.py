"""How late the open-loop generator submitted: the 95th percentile of
submit time minus due time over the frames due in the window, in ms."""

from chipbench.harness import percentile


def read(run):
    if run.mix["loop"] != "open":
        return None
    late = [f.submit - f.due for f in run.frames if f.submit is not None]
    return 1e3 * percentile(late, 0.95) if late else None
