"""Share of the traced stretch in which no program ran on the device
(1 minus the union of ``XLA Modules`` intervals), mean over the chips
used, in percent."""


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
