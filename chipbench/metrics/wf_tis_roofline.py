"""``wf_tis``'s share of its HBM roofline in the traced stretch: the bytes
its work needs (``work.wf_tis_bytes`` of the frames it computed, at the
configuration's frame shape and bins) at peak bandwidth, over the summed
device time of its kernel events on every chip, in percent.

A frame whose bins are sharded over chips gives one event per chip, each
over its share of the bins (the second of the output's four dims): the
events of a frame add up to one frame, so a frame's work is counted
once, and the share is of the chips' combined roofline."""

from chipbench import peaks, work


def read(run):
    tr = run.trace
    events = tr.kernels.get("wf_tis", []) if tr is not None else []
    if not events:
        return None
    cfg = run.cfg
    frames = sum(dims[0] * dims[1] / cfg["bins"] if len(dims) == 4 else 1
                 for _, dims in events)
    nbytes = work.wf_tis_bytes(frames, cfg["height"], cfg["width"],
                               cfg["bins"])
    bw = peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"]
    return work.roofline_share(nbytes, sum(s for s, _ in events), bw)
