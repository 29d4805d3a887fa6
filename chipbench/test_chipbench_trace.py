"""The reduction from trace to metrics gives known numbers."""

import os
import types

import pytest

from chipbench import cells, trace

# A trace recorded on a TPU v5e while one engine ran, in turn, a 1080p
# stride-2 likelihood (dense), a 640x480 multi-scale search (fused), and
# a dense then an incremental 640x480 request; trimmed to the lines the
# reduction reads, with the run's own annotation as the traced window.
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "engine_probe.xplane.pb")

WF = ("%_integral_histogram_jit.1 = f32[1,32,1152,1920]{3,2,1,0:T(8,128)} "
      "custom-call(s32[1,1152,1920]{2,1,0:T(8,128)S(1)} %pad)")
SLICE = ("%slice.0 = f32[32,1080,1920]{2,1,0:T(8,128)} slice(f32[32,1152,"
         "1920]{2,1,0:T(8,128)} %bitcast.1)")
GATHER = ("%fusion = f32[518400,32]{0,1:T(8,128)S(1)} fusion(f32[32,1080,"
          "1920]{2,0,1:T(8,128)} %copy.4)")


def test_opcode_and_shape_parsing():
    assert trace.opcode(WF) == "custom-call"
    assert trace.out_dims(WF) == (1, 32, 1152, 1920)
    assert trace.opcode(GATHER) == "fusion"
    tup = ("%copy-start = (f32[32,480,640]{2,1,0:T(8,128)S(1)}, u32[]{:S(2)}"
           ") copy-start(f32[32,480,640]{2,1,0:T(8,128)} %a)")
    assert trace.opcode(tup) == "copy-start"
    assert trace.program_name("jit_gather(3268713407281415093)") == \
        "jit_gather"


def test_summary_of_a_synthetic_stretch():
    # window 0..10 s; device busy 1-2 (wf_tis program) and 4-7 (gather)
    dev = {"XLA Modules": [(1.0, 2.0, "jit__integral_histogram_jit(1)"),
                           (4.0, 7.0, "jit_gather(2)")],
           "XLA Ops": [(1.0, 1.5, WF), (1.5, 2.0, SLICE),
                       (4.0, 7.0, GATHER)]}
    spans = [(0.0, 10.0, "bench.trace"), (2.0, 4.0, "engine.run"),
             (2.5, 3.5, "validate"), (7.0, 10.0, "client.wait")]
    s = trace.summarize([dev], spans)
    assert s.window_s == 10.0 and s.busy_s == pytest.approx(4.0)
    assert s.idle_gaps == [["client.wait", 3.0], ["validate", 2.0],
                           ["no span", 1.0]]
    assert s.device_ops[0] == ["jit_gather:fusion", 3.0]
    assert s.kernels == {"wf_tis": [(0.5, (1, 32, 1152, 1920))]}
    # two devices: busy is the mean over them
    s2 = trace.summarize([dev, {"XLA Modules": [], "XLA Ops": []}], spans)
    assert s2.busy_s == pytest.approx(2.0) and s2.devices == 2


def test_no_window_span_gives_nothing():
    assert trace.summarize([{"XLA Modules": [], "XLA Ops": []}], []) is None


def test_reduction_of_a_recorded_chip_trace():
    s = trace.summarize(*trace.events(FIXTURE))
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.329164798, abs=1e-9)
    assert s.busy_s == pytest.approx(0.102085937, abs=1e-9)
    assert s.device_ops[0] == ["jit_gather:fusion",
                               pytest.approx(0.084779387, abs=1e-9)]
    # the 1080p frame's wf_tis custom call lasted 862,972 ns on the chip
    assert s.kernels["wf_tis"] == [
        (pytest.approx(862.972e-6, abs=1e-9), (1, 32, 1152, 1920)),
        (pytest.approx(130.645e-6, abs=1e-9), (1, 32, 512, 640)),
        (pytest.approx(33.070e-6, abs=1e-9), (1, 32, 128, 640))]
    assert s.kernels["fused_rows"] == [
        (pytest.approx(148.046e-6, abs=1e-9), (1, 32, 64, 640))]
    assert s.kernels["delta_apply"] == [
        (pytest.approx(11.892e-6, abs=1e-9), (1, 32, 256, 640))]
    assert [label for label, _ in s.idle_gaps] == ["no span"] * 10
    assert s.idle_gaps[0][1] == pytest.approx(0.012268541, abs=1e-9)


def test_wf_tis_roofline_of_the_recorded_1080p_frame():
    s = trace.summarize(*trace.events(FIXTURE))
    s.kernels = {"wf_tis": s.kernels["wf_tis"][:1]}
    run = types.SimpleNamespace(trace=s, cfg=cells.config("hd32"),
                                device_kind="TPU v5 lite")
    share = cells.reader("wf_tis_roofline.fps")(run)
    # 1080*1920 B of frame + 32*1080*1920*4 B of H at 819 GB/s
    assert share == pytest.approx(100 * 267_494_400 / 819e9 / 862.972e-6,
                                  rel=1e-6)
    assert 37.8 < share < 37.9


def roofline(kernels, cfg):
    s = trace.Summary(window_s=1.0, busy_s=1.0, devices=1, device_ops=[],
                      idle_gaps=[], kernels=kernels)
    run = types.SimpleNamespace(trace=s, cfg=cfg, device_kind="TPU v5 lite")
    return cells.reader("wf_tis_roofline.fps")(run)


def test_wf_tis_roofline_counts_a_bin_sharded_frame_once():
    """Four chips' events, each over a quarter of the bins, read the same
    share as one chip's single event over the whole frame in the same
    summed time: the frame's work is counted once, over the chips'
    combined time."""
    cfg = cells.config("paper8k128")
    h, w, b = cfg["height"], cfg["width"], cfg["bins"]
    whole = roofline({"wf_tis": [(0.2, (1, b, h, w))] * 3}, cfg)
    quarters = roofline({"wf_tis": [(0.05, (1, b // 4, h, w))] * 12}, cfg)
    assert quarters == pytest.approx(whole, rel=1e-12)
    assert whole == pytest.approx(
        100 * 3 * (h * w + 4 * b * h * w) / 819e9 / 0.6, rel=1e-12)


def test_sharded_kernel_is_found_by_its_op_name():
    """In the program that shards bins over chips the kernel sits in no
    program of ``KERNEL_PROGRAMS``: its op's own name finds it, on every
    chip, and other custom calls stay out."""
    op = ("%wf_tis.1 = f32[1,32,512,1024]{3,2,1,0:T(8,128)} custom-call("
          "%subtract_select_fusion, %broadcast_in_dim.6)")
    other = "%cw_tis_hscan.3 = f32[1,32,512,1024]{3,2,1,0} custom-call(%a)"
    dev = {"XLA Modules": [(1.0, 2.0, "jit_shard_fn(7)")],
           "XLA Ops": [(1.0, 1.25, op), (1.25, 1.5, other)]}
    s = trace.summarize([dev] * 4, [(0.0, 3.0, "bench.trace")])
    assert s.kernels == {"wf_tis": [(0.25, (1, 32, 512, 1024))] * 4}
    assert trace.kernel_of("jit_shard_fn", "ROOT " + op) == "wf_tis"
    assert trace.kernel_of("jit__integral_histogram_jit", WF) == "wf_tis"
    assert trace.kernel_of("jit_shard_fn", other) is None
    cfg = dict(cells.config("paper8k128"), height=512, width=1024)
    assert roofline(s.kernels, cfg) == pytest.approx(
        100 * (512 * 1024 + 4 * 128 * 512 * 1024) / 819e9 / 1.0, rel=1e-12)
