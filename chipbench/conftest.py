"""Shared fixtures of the benchmark's CPU tests."""

import copy

import pytest

from chipbench import cells


#: Cells whose configuration and traffic files are here but which
#: BENCHMARK.json does not list (PERF.md, Open questions): the fused-path
#: search, the four-replica fleet, and the paper's 8192x8192x128 frame
#: with its bins sharded over four chips.  Their tests keep the harness's
#: multi-scale, replica and bin-sharded paths working, so that a later PR
#: adds any of these cells with an entry alone.
LATER = [{"name": "vga32.archive", "config": "vga32",
          "traffic": "archive_multiscale", "chips": 1},
         {"name": "vga32.fleet4", "config": "vga32", "traffic": "fleet",
          "chips": 4},
         {"name": "paper8k128.sharded4", "config": "paper8k128",
          "traffic": "archive_8k", "chips": 4}]


def bench_with_later() -> dict:
    bench = cells.benchmark()
    return dict(bench, workloads=bench["workloads"] + LATER)


def cell(name: str):
    """(cell entry, cfg, mix) of a listed cell or of one in ``LATER``."""
    return cells.cell(name, bench_with_later())


def small(name: str, **mix_overrides):
    """(cell, cfg, mix, bench) of cell ``name`` cut to a size the CPU
    runs in seconds: 120x160 frames at 8 bins, windows and objects cut
    in proportion, the same traffic otherwise."""
    bench = bench_with_later()
    cell, cfg, mix = cells.cell(name, bench)
    cfg = dict(cfg, height=120, width=160, bins=8)
    cfg["service"] = {"cache_size": 16,
                      "cache_bytes": 16 * 4 * 8 * 120 * 160}
    mix = copy.deepcopy(mix)
    mix["objects"]["size"] = [10, 30]
    if "pan" in mix:
        mix["pan"]["cols"] = 64
    for q in mix["queries"]:
        if "window" in q:
            q["window"] = [12, 12]
        if "patch" in q:
            q["patch"] = [12, 12]
        if "windows" in q:
            q["windows"] = [[12, 6], [24, 12]]
    if mix["loop"] == "open":
        mix["rate_fps"] = 20.0
    mix["clients"] = min(mix["clients"], 8)
    mix["check_every"] = 1
    mix.update(mix_overrides)
    return cell, cfg, mix, bench


@pytest.fixture
def small_cell():
    return small
