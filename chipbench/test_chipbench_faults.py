"""The check catches what it is there to catch.

Each test drives a whole run of a cell at a small size on the CPU (the
harness's look for a chip is the only step skipped), once as the program
is and once with a fault planted under the timed path, and sees
``correct`` come out true and then false.  The control, the reference
with H held in bfloat16 put in the program's place, fails too."""

import time

import ml_dtypes
import numpy as np
import pytest

from chipbench import cells, check, faults, runner


def run_cell(small_cell, name, fault=None, seed=11, samples=None):
    cell, cfg, mix, bench = small_cell(name)
    ctx = faults.FAULTS[fault]() if fault else _nothing()
    with ctx:
        return runner.execute(cell, cfg, mix, seed=seed, seconds=1.5,
                              traced=False, devices=None,
                              t_process=time.perf_counter(), bench=bench,
                              samples_out=samples)


class _nothing:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("name,fault", [
    ("vga32.live", "answer_altered"),
    ("vga32.live", "state_unchanged"),
    ("hd32.archive", "answer_altered"),
    ("vga32.archive", "answer_altered"),
    ("vga32.fleet4", "chain_ignored"),
    ("vga32.fleet4", "answer_altered"),
    ("vga32.fleet4", "state_unchanged"),
    ("paper8k128.sharded4", "answer_altered"),
])
def test_fault_fails_the_check(small_cell, name, fault):
    out = run_cell(small_cell, name, fault)
    assert out["correct"] is False, out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", ["vga32.live", "hd32.archive",
                                  "vga32.archive", "vga32.fleet4",
                                  "paper8k128.sharded4"])
def test_sound_run_passes_and_bf16_control_fails(small_cell, name):
    kept = []
    out = run_cell(small_cell, name, samples=kept)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["checked"]["value"] > 0
    _, cfg, _, _ = small_cell(name)
    ok, _ = check.judge(dict(check.control(kept, cfg, ml_dtypes.bfloat16),
                             lost=0, chain_splits=0), cfg["limits"])
    assert ok is False
    assert check.judge(dict(check.control(kept, cfg, "int32"), lost=0,
                            chain_splits=0), cfg["limits"])[0] is True


@pytest.mark.parametrize("got,want,gap", [
    ([1.0, 2.5], [1.0, 2.0], 0.5),
    ([1.0, float("nan")], [1.0, 2.0], float("inf")),
    ([1.0, float("nan")], [1.0, float("nan")], 0.0),
    ([1.0], [1.0, 2.0], float("inf")),
])
def test_gap_counts_a_nan_or_a_wrong_shape_as_wrong(got, want, gap):
    assert check._gap(got, np.asarray(want)) == gap


def test_fleet_readers_read_a_traced_run(small_cell, monkeypatch):
    """The replica readers a later fleet cell names find their counters."""
    cell, cfg, mix, bench = small_cell("vga32.fleet4")
    kept = []
    real = runner.harness.Run

    def keep(*args, **kwargs):
        kept.append(real(*args, **kwargs))
        return kept[-1]

    monkeypatch.setattr(runner.harness, "Run", keep)
    out = runner.execute(cell, cfg, mix, seed=7, seconds=1.5, traced=True,
                         devices=None, t_process=time.perf_counter(),
                         bench=bench)
    assert out["correct"] is True, out["checks"]
    imbalance = cells.reader("replica_imbalance.fps")(kept[0])
    share = cells.reader("update_share.fps")(kept[0])
    assert 1.0 <= imbalance < 4.0 and 0.0 < share < 100.0
