"""Mean time a request waited in the service's queue, from ``submit`` to
the drain that took it, in ms: the summed ``wait_us`` of the
``service.batch`` spans over their summed ``size``."""

from chipbench.metrics._program import spans


def read(run):
    batches = [stats for _, _, stats in spans(run, "service.batch")]
    size = sum(b.get("size", 0) for b in batches)
    if not size:
        return None
    return 1e-3 * sum(b.get("wait_us", 0.0) for b in batches) / size
