"""BENCHMARK.json is well formed, every cell loads by name, every metric
has its reader, and a cell can be added with new files alone."""

import json
import os
import re
import shutil

import pytest

from chipbench import cells, peaks, work

BENCH = cells.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_loads_by_name(cell):
    entry, cfg, mix = cells.cell(cell["name"], BENCH)
    assert entry is cell
    assert cfg["name"] == cell["config"]
    assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
    assert cell["chips"] in (1, 4)
    e2e, per_layer = cells.metrics_for(cell["name"], BENCH)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert per_layer
    assert all(m["moves"] in names for m in per_layer)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert callable(cells.reader(metric["name"]))
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(c):
    cfg = cells.load_json(os.path.join(cells.ROOT, c["file"]))
    assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    h_bytes = 4 * cfg["bins"] * cfg["height"] * cfg["width"]
    assert cfg["service"]["cache_size"] * h_bytes == \
        cfg["service"]["cache_bytes"]


def test_added_by_files_alone(tmp_path):
    """A configuration and a traffic mix copied under new names into
    another directory load there by name, with no code changed."""
    for sub in ("configs", "traffic"):
        os.makedirs(tmp_path / sub)
    shutil.copy(os.path.join(cells.HERE, "configs", "vga32.json"),
                tmp_path / "configs" / "vga32b.json")
    shutil.copy(os.path.join(cells.HERE, "traffic", "live.json"),
                tmp_path / "traffic" / "live_b.json")
    bench = {"workloads": [{"name": "vga32b.live_b", "config": "vga32b",
                            "traffic": "live_b", "chips": 1}]}
    entry, cfg, mix = cells.cell("vga32b.live_b", bench, base=str(tmp_path))
    assert cfg["height"] == 480 and mix["loop"] == "open"
    with pytest.raises(KeyError):
        cells.cell("nope", bench, base=str(tmp_path))


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_wf_tis_bytes_at_hd32_archive_shapes():
    # one 1080p uint8 frame read, its 32-bin float32 H written
    assert work.wf_tis_bytes(1, 1080, 1920, 32) == \
        1080 * 1920 + 4 * 32 * 1080 * 1920 == 267_494_400
    share = work.roofline_share(267_494_400, 0.863e-3, 819e9)
    assert share == pytest.approx(100 * 267_494_400 / 819e9 / 0.863e-3)
    assert work.roofline_share(1, 0.0, 819e9) is None


def test_fused_and_delta_bytes_at_hd32_archive_shapes():
    assert work.fused_rows_bytes(1, 1152, 1920, 32, 540) == \
        1152 * 1920 + 4 * 32 * 540 * 1920
    assert work.delta_apply_bytes(1, 1080, 1920, 32) == \
        4 * 32 * 1920 * (2 * 1080 + 1)


def test_benchmark_json_is_small():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024
    json.dumps(BENCH)
