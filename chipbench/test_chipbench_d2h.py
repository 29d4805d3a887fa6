"""``d2h_mb``: the bytes the window brought to the host per frame."""

from chipbench import cells, harness


def _run(start, end, completed):
    run = harness.Run(cell={}, cfg={}, mix={}, seconds=10.0, t0=100.0)
    run.start, run.end = {"total": start}, {"total": end}
    run.frames = [harness.FrameRec(client=0, t=i, due=100.0 + i,
                                   done=100.5 + i) for i in range(completed)]
    return run


def test_reads_megabytes_per_completed_frame():
    read = cells.reader("d2h_mb.fps")
    run = _run({"d2h_bytes": 7_000_000}, {"d2h_bytes": 47_000_000}, 8)
    assert read(run) == 5.0


def test_reads_nothing_without_a_frame_or_the_counter():
    read = cells.reader("d2h_mb.fps")
    assert read(_run({"d2h_bytes": 0}, {"d2h_bytes": 0}, 0)) is None
    assert read(_run({"engine_runs": 1}, {"engine_runs": 9}, 8)) is None
