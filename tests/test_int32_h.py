"""An integral histogram past 2**24 pixels is an int32 H, exact.

float32 holds every integer below 2**24 and no odd one above it, so the
paper's 8192x8192 frame (2**26 pixels) needs an integer H.  These tests
hold ``core/hdtype.h_dtype``'s decision, the int32 ``wf_tis`` (its Pallas
kernel in interpret mode and its jnp twin), the engine and the planner
to an int64 NumPy reference, and plancheck to refusing every plan that
would hold such a frame in float32.  Frames below the bound, which
include every frame the benchmark's one-chip cells serve, keep float32.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import kernelcheck as kc
from repro.analysis.plancheck import check_plan
from repro.core import bands, hdtype
from repro.core import region_query as rq
from repro.core.engine import (HistogramEngine, LikelihoodQuery, RegionQuery,
                               WorkloadSpec, plan)
from repro.kernels import ops
from repro.kernels.specs import KernelGeometry

BIG = 1 << 24


def _ref_int64(frame, bins, carry=None):
    """The plain reference: int64 running sums of the 0/1 bin masks."""
    idx = (frame.astype(np.int64) * bins) // 256
    q = (idx[None] == np.arange(bins)[:, None, None]).astype(np.int64)
    H = np.cumsum(np.cumsum(q, axis=1), axis=2)
    return H if carry is None else H + carry[:, None, :]


# ---------------------------------------------------------------------------
# the decision
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hw,want", [
    ((480, 640), np.float32),        # vga32
    ((1080, 1920), np.float32),      # hd32
    ((4096, 4095), np.float32),      # 2**24 - 4096 pixels
    ((4096, 4096), np.int32),        # exactly 2**24
    ((8192, 8192), np.int32),        # paper8k128
], ids=str)
def test_h_dtype_follows_the_pixel_count(hw, want):
    assert hdtype.h_dtype(*hw) == want
    assert WorkloadSpec(height=hw[0], width=hw[1]).h_dtype == want


@pytest.mark.parametrize("hw", [(480, 640), (1080, 1920)],
                         ids=["vga32", "hd32"])
@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_benchmarked_one_chip_frames_keep_fp32(hw, backend):
    """The geometries of the vga32 and hd32 cells get a float32 H from
    the kernel and from the plan's validation."""
    frame = jax.ShapeDtypeStruct(hw, jnp.uint8)
    out = jax.eval_shape(lambda f: ops.integral_histogram(
        f, 32, backend=backend, interpret=True), frame)
    assert out.dtype == np.float32
    e = HistogramEngine(32, backend="jnp")
    verdict = e.validate(plan(e.spec_for(hw)))
    assert verdict.ok
    assert "float32" in [c for c in verdict.checks
                         if c.name == "h-shape"][0].detail


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_wf_tis_int32_with_a_carry_past_2_24_is_exact(rng, backend):
    """A 256x256 band under a carry-in of 2**24 + k: the int32 H equals
    the int64 reference, which float32 could not hold."""
    bins = 8
    frame = rng.integers(0, 256, (256, 256), dtype=np.uint8)
    frame[:96, :200] = 37                      # a flat run in one bin
    carry = BIG + rng.integers(0, 1 << 20, (bins, 256)) * 2 + 1
    want = _ref_int64(frame, bins, carry)
    got = ops.integral_histogram(
        jnp.asarray(frame), bins, backend=backend, tile=64, bin_block=8,
        interpret=True, carry_in=jnp.asarray(carry, jnp.int32),
        dtype=np.int32)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)
    # the test is tight: float32 rounding of the same values is wrong
    assert np.any(want.astype(np.float32).astype(np.int64) != want)


def test_bands_of_a_large_frame_take_the_frame_dtype(rng):
    """Bands thread an int32 carry when the frame's H is int32, and agree
    with the monolithic H bit for bit."""
    frame = rng.integers(0, 256, (96, 80), dtype=np.uint8)
    carry = jnp.asarray(BIG + rng.integers(0, 999, (4, 80)), jnp.int32)
    whole = ops.integral_histogram(jnp.asarray(frame), 4, backend="jnp",
                                   carry_in=carry, dtype=np.int32)
    banded = bands.banded_integral_histogram(
        frame, 4, band_h=32, backend="jnp", carry_in=carry, dtype=np.int32)
    assert banded.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(banded), np.asarray(whole))


def test_only_wf_tis_accumulates_int32(rng):
    frame = jnp.asarray(rng.integers(0, 256, (64, 64), dtype=np.uint8))
    with pytest.raises(ValueError, match="only by wf_tis"):
        ops.integral_histogram(frame, 4, method="cw_tis", backend="jnp",
                               dtype=np.int32)


def test_int32_kernel_contract_proves_and_matches_the_call(monkeypatch):
    """kernelcheck proves the int32 launch, and the live pallas_call's
    output, carry and column-carry scratch are the spec's int32."""
    from jax.experimental import pallas as pl

    geom = KernelGeometry(n=1, h=256, w=256, num_bins=8, tile=64,
                          bin_block=8, dtype="int32")
    verdict = kc.check_method("wf_tis", geom)
    assert all(c.ok for c in verdict.checks), verdict
    captured = []
    real = pl.pallas_call

    def spy(kernel, **kw):
        captured.append(kw)
        return real(kernel, **kw)

    monkeypatch.setattr(pl, "pallas_call", spy)
    ops.integral_histogram(jnp.zeros((256, 256), jnp.uint8), 8,
                           backend="pallas", tile=64, interpret=True,
                           dtype=np.int32)
    (spec,) = ops.KERNEL_SPECS["wf_tis"](geom)
    (call,) = captured
    assert str(call["out_shape"].dtype) == spec.out_specs[0].dtype == "int32"
    assert [str(s.dtype) for s in call["scratch_shapes"]] \
        == [s.dtype for s in spec.scratch] == ["float32", "int32"]
    assert spec.in_specs[1].dtype == "int32"


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_regions_by_tiles_is_bit_identical_to_the_gather(rng, dtype):
    H = jnp.asarray(np.cumsum(np.cumsum(
        rng.integers(0, 3, (2, 3, 40, 150)), axis=-2), axis=-1).astype(dtype))
    r0 = rng.integers(0, 40, 25)
    c0 = rng.integers(0, 150, 25)
    rects = np.stack([r0, c0, rng.integers(r0, 40), rng.integers(c0, 150)],
                     -1).astype(np.int32)
    rects[0] = [0, 0, 39, 149]
    got = jax.jit(rq.regions_by_tiles)(H, jnp.asarray(rects))
    want = rq.region_histogram(H, rects)
    assert got.shape == want.shape == (2, 25, 3) and got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bins", [2, 4])
def test_engine_counts_a_frame_past_2_24_exactly(bins):
    """A 4097x4097 frame of one value: its one bin counts 16,785,409
    pixels, an odd number past 2**24 that float32 cannot hold."""
    h = w = 4097
    frame = np.full((h, w), 200, np.uint8)
    whole = [[0, 0, h - 1, w - 1]]
    eng = HistogramEngine(bins, backend="jnp")
    out = eng.run(frame, [RegionQuery(whole),
                          LikelihoodQuery(np.ones(bins, np.float32),
                                          (64, 64), stride=1024)])
    assert out.plan.representation == "dense"       # never fused past 2**24
    assert out.source.H.dtype == np.int32
    counts = np.asarray(out.results[0])
    assert counts.dtype == np.int32
    want = np.zeros(bins, np.int64)
    want[200 * bins // 256] = h * w
    np.testing.assert_array_equal(counts[0], want)
    assert h * w % 2 == 1 and int(np.float32(h * w)) != h * w
    assert out.results[1].dtype == np.float32


# ---------------------------------------------------------------------------
# the planner and plancheck
# ---------------------------------------------------------------------------
def _paper_plan(**spec_kw):
    return plan(WorkloadSpec(height=8192, width=8192, num_bins=128,
                             backend="jnp", **spec_kw))


def test_planner_takes_no_fp32_path_past_2_24():
    assert _paper_plan(query_rows=(63, 127)).representation == "dense"
    assert not _paper_plan(dirty_fraction=0.01).incremental
    small = plan(WorkloadSpec(height=480, width=640, backend="jnp",
                              query_rows=(63, 127)))
    assert small.representation == "fused"


@pytest.mark.parametrize("change", [
    dict(storage="float32", representation="spilled"),
    dict(representation="fused"),
    dict(incremental=True),
    dict(method="cw_tis"),
], ids=["spilled-float32", "fused", "delta", "cw_tis"])
def test_plancheck_fails_a_plan_that_would_round(change):
    p = _paper_plan(query_rows=(63, 127), dirty_fraction=0.01)
    bad = dataclasses.replace(p, **change)
    (cv,) = [c for c in check_plan(bad).checks if c.name == "count-validity"]
    assert cv.status == "fail" and "round" in cv.detail, cv


def test_plancheck_passes_the_int32_plan_and_bounds_its_tiles():
    (cv,) = [c for c in check_plan(_paper_plan()).checks
             if c.name == "count-validity"]
    assert cv.status == "ok" and "int32" in cv.detail
    # a 2**17-wide frame: one 128-row strip alone reaches 2**24
    wide = plan(WorkloadSpec(height=128, width=1 << 17, num_bins=2,
                             backend="jnp"))
    (cv,) = [c for c in check_plan(wide).checks if c.name == "count-validity"]
    assert cv.status == "fail" and "tile x padded width" in cv.detail
