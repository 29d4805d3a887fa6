"""Band streaming correctness: banded integral histograms must equal the
monolithic computation bit-exactly (all arithmetic is integer-valued fp32)
for every method, at uneven band heights, on single frames and (n, h, w)
stacks; banded O(1) queries must equal queries against the full H without
ever materializing it; storage policies enforce their count bounds."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.compat import make_mesh
from repro.core import distances, scans
from repro.core.bands import (
    BandPlan,
    banded_integral_histogram,
    iter_banded_ih,
    plan_bands,
    reduce_banded_ih,
    spill_banded_ih,
    validate_storage_policy,
)
from repro.core.integral_histogram import IntegralHistogram
from repro.core.region_query import (
    banded_likelihood_map,
    banded_region_histogram,
    banded_sliding_window_histograms,
    likelihood_map,
    region_histogram,
    sliding_window_histograms,
)
from repro.kernels.ops import integral_histogram


def _img(rng, *shape):
    return rng.integers(0, 256, shape, dtype=np.uint8)


# ---------------------------------------------------------------------------
# band planning
# ---------------------------------------------------------------------------
def test_plan_bands_from_budget():
    # 8 bins x width 100 x fp32 = 3200 B/row; 10 kB budget -> 3-row bands
    plan = plan_bands(37, 100, 8, memory_budget_bytes=10_000)
    assert plan.band_h == 3
    assert plan.spans[0] == (0, 3)
    assert plan.spans[-1] == (36, 37)          # uneven tail band
    assert sum(r1 - r0 for r0, r1 in plan.spans) == 37
    assert plan.band_bytes <= 10_000
    assert plan.full_h_bytes == 4 * 8 * 37 * 100


def test_plan_bands_explicit_and_clipped():
    plan = plan_bands(20, 10, 4, band_h=64)
    assert plan.spans == ((0, 20),)            # band_h clipped to h
    plan = plan_bands(20, 10, 4, band_h=8, row_multiple=3)
    assert plan.band_h == 6                    # rounded down to multiple
    assert isinstance(plan, BandPlan) and plan.num_bands == 4


def test_plan_bands_budget_too_small():
    with pytest.raises(ValueError, match="below one"):
        plan_bands(37, 100, 8, memory_budget_bytes=100)  # < one row
    with pytest.raises(ValueError, match="below one"):
        plan_bands(64, 100, 8, memory_budget_bytes=4000, row_multiple=4)


# ---------------------------------------------------------------------------
# banded H parity — all four methods, uneven band heights, frames + stacks
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("method", sorted(scans.METHODS))
@pytest.mark.parametrize("shape", [(37, 23), (2, 37, 23)])
def test_banded_equals_monolithic_jnp(rng, method, shape):
    img = _img(rng, *shape)
    full = integral_histogram(
        jnp.asarray(img), 8, method=method, backend="jnp")
    for band_h in (5, 16, 37):                 # 5 and 16 leave uneven tails
        banded = banded_integral_histogram(
            img, 8, band_h=band_h, method=method, backend="jnp")
        np.testing.assert_array_equal(np.asarray(banded), np.asarray(full))


@pytest.mark.parametrize("method", ["cw_tis", "wf_tis"])
def test_banded_equals_monolithic_pallas(rng, method):
    """The carry-in threads through the Pallas kernels' VMEM carry chain
    (interpret mode on CPU)."""
    img = _img(rng, 40, 48)
    kw = dict(method=method, backend="pallas", tile=16, bin_block=4,
              interpret=True)
    full = integral_histogram(jnp.asarray(img), 6, **kw)
    banded = banded_integral_histogram(img, 6, band_h=24, **kw)  # 24 + 16
    np.testing.assert_array_equal(np.asarray(banded), np.asarray(full))


def test_carry_in_manual_chain(rng):
    """Two halves chained by carry_in == the whole frame, for a native-seed
    method (wf_tis), a post-add method (cw_sts), and the Pallas kernel."""
    img = _img(rng, 30, 17)
    for kw in (dict(method="wf_tis", backend="jnp"),
               dict(method="cw_sts", backend="jnp"),
               dict(method="wf_tis", backend="pallas", tile=16,
                    interpret=True)):
        full = integral_histogram(jnp.asarray(img), 8, **kw)
        top = integral_histogram(jnp.asarray(img[:13]), 8, **kw)
        bot = integral_histogram(
            jnp.asarray(img[13:]), 8, carry_in=top[..., -1, :], **kw)
        np.testing.assert_array_equal(
            np.asarray(jnp.concatenate([top, bot], axis=-2)),
            np.asarray(full))


def test_carry_in_bad_shape_raises(rng):
    img = _img(rng, 16, 16)
    with pytest.raises(ValueError, match="carry_in shape"):
        integral_histogram(jnp.asarray(img), 8, backend="jnp",
                           carry_in=jnp.zeros((8, 15)))


def test_budget_auto_banding(rng):
    """integral_histogram(memory_budget_bytes=...) computes band-by-band
    and still matches the unbudgeted result bit-exactly."""
    img = _img(rng, 37, 23)
    full = integral_histogram(jnp.asarray(img), 8, backend="jnp")
    budget = 6 * 8 * 23 * 4                    # six rows' worth of H
    auto = integral_histogram(jnp.asarray(img), 8, backend="jnp",
                              memory_budget_bytes=budget)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(full))
    with pytest.raises(ValueError, match="below one"):
        integral_histogram(jnp.asarray(img), 8, backend="jnp",
                           memory_budget_bytes=10)


def test_band_stream_carries(rng):
    """The streamed BandH chain exposes consistent carries and spans."""
    img = _img(rng, 26, 11)
    full = integral_histogram(jnp.asarray(img), 4, backend="jnp")
    r = 0
    for band in iter_banded_ih(img, 4, band_h=7, backend="jnp"):
        assert band.r0 == r and band.frame_h == 26
        np.testing.assert_array_equal(
            np.asarray(band.carry), np.asarray(full[..., band.r1 - 1, :]))
        r = band.r1
    assert r == 26


def test_reduce_banded(rng):
    """Reduce-on-the-fly: the final carry is the full column aggregate."""
    img = _img(rng, 26, 11)
    full = integral_histogram(jnp.asarray(img), 4, backend="jnp")
    last = reduce_banded_ih(img, 4, lambda acc, band: band.carry,
                            band_h=7, backend="jnp")
    np.testing.assert_array_equal(np.asarray(last), np.asarray(full[:, -1, :]))


# ---------------------------------------------------------------------------
# banded O(1) queries — exact without materializing H
# ---------------------------------------------------------------------------
def test_banded_region_histogram(rng):
    img = _img(rng, 64, 48)
    full = integral_histogram(jnp.asarray(img), 8, backend="jnp")
    rects = np.array([[0, 0, 63, 47], [3, 4, 30, 40], [10, 0, 10, 0],
                      [16, 5, 17, 6], [63, 47, 63, 47]])
    got = banded_region_histogram(
        iter_banded_ih(img, 8, band_h=17, backend="jnp"), rects)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(region_histogram(full, rects)))


def test_banded_region_histogram_stack(rng):
    imgs = _img(rng, 2, 40, 32)
    full = integral_histogram(jnp.asarray(imgs), 6, backend="jnp")
    rects = np.array([[0, 0, 39, 31], [5, 5, 20, 20]])
    got = banded_region_histogram(
        iter_banded_ih(imgs, 6, band_h=13, backend="jnp"), rects)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(region_histogram(full, rects)))


@pytest.mark.parametrize("stride", [1, 4, 5])
def test_banded_sliding_windows(rng, stride):
    img = _img(rng, 52, 40)
    full = integral_histogram(jnp.asarray(img), 8, backend="jnp")
    mono = sliding_window_histograms(full, (12, 8), stride)
    band = banded_sliding_window_histograms(
        iter_banded_ih(img, 8, band_h=13, backend="jnp"), (12, 8), stride)
    np.testing.assert_array_equal(np.asarray(band), np.asarray(mono))


def test_banded_sliding_windows_stack_and_oversized(rng):
    imgs = _img(rng, 2, 36, 28)
    full = integral_histogram(jnp.asarray(imgs), 4, backend="jnp")
    mono = sliding_window_histograms(full, (9, 7), 3)
    band = banded_sliding_window_histograms(
        iter_banded_ih(imgs, 4, band_h=10, backend="jnp"), (9, 7), 3)
    np.testing.assert_array_equal(np.asarray(band), np.asarray(mono))
    # window taller than the frame: no positions, same as monolithic
    empty = banded_sliding_window_histograms(
        iter_banded_ih(imgs, 4, band_h=10, backend="jnp"), (50, 7), 3)
    assert empty.shape == (2, 0, 8, 4)


def test_banded_likelihood_map_budgeted(rng):
    """A budgeted run (full H bytes > budget) produces the exact
    likelihood map, and the peak-allocation proxy stays under the full-H
    footprint — the §4.6 large-frame scenario at test scale."""
    img = _img(rng, 96, 64)
    bins = 8
    full_bytes = 4 * bins * 96 * 64
    budget = full_bytes // 8
    full = integral_histogram(jnp.asarray(img), bins, backend="jnp")
    target = region_histogram(full, np.array([20, 10, 43, 33]))
    want = likelihood_map(full, target, (24, 24), distances.intersection,
                          stride=8)
    stats = {}
    got = banded_likelihood_map(
        iter_banded_ih(img, bins, memory_budget_bytes=budget, backend="jnp"),
        target, (24, 24), distances.intersection, stride=8, stats=stats)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert stats["num_bands"] >= 8
    assert stats["band_bytes"] <= budget
    assert stats["peak_bytes"] < stats["full_h_bytes"] == full_bytes


# ---------------------------------------------------------------------------
# storage policies
# ---------------------------------------------------------------------------
def test_storage_policy_validation():
    with pytest.raises(ValueError, match="unknown storage"):
        validate_storage_policy("float16", 10, 10)
    with pytest.raises(ValueError, match="2\\*\\*24"):
        validate_storage_policy("float32", 5000, 4000)   # 2e7 > 2**24
    with pytest.raises(ValueError, match="2\\*\\*24"):
        validate_storage_policy("uint16", 5000, 4000)    # compute inexact
    validate_storage_policy("uint16", 300, 300)          # wraps, but valid


@pytest.mark.parametrize("storage", ["float32", "uint32", "uint16"])
def test_spill_policies_exact(rng, storage):
    img = _img(rng, 60, 44)
    full = integral_histogram(jnp.asarray(img), 8, backend="jnp")
    sp = spill_banded_ih(img, 8, band_h=17, backend="jnp", storage=storage)
    rects = np.array([[0, 0, 59, 43], [7, 3, 41, 30], [59, 43, 59, 43]])
    got = sp.region_histogram(rects)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(region_histogram(full, rects)))
    np.testing.assert_array_equal(sp.assemble(), np.asarray(full))
    # band bytes + the retained fp32 bottom-row carries (4 bands) that
    # seed incremental video-delta updates (core/delta.py)
    assert sp.nbytes == (2 if storage == "uint16" else 4) * 8 * 60 * 44 \
        + 4 * len(sp.spans) * 8 * 44


def test_uint16_modular_wraparound_exact(rng):
    """The reduced-width accumulator trick (arXiv:1510.05142): uint16 H
    values wrap past 65535, yet any <= 65535-pixel region query is exact
    by modular arithmetic; oversized regions are rejected."""
    img = _img(rng, 300, 300)
    img[:250] = 0                       # bin 0 accumulates 75000 > 65535
    full = integral_histogram(jnp.asarray(img), 4, backend="jnp")
    assert float(full.max()) > 65535    # the wrap actually happens
    sp = spill_banded_ih(img, 4, band_h=64, backend="jnp", storage="uint16")
    assert int(max(b.max() for b in sp.bands)) <= 65535
    rects = np.array([[0, 0, 199, 299], [100, 100, 250, 250]])  # <= 60000 px
    np.testing.assert_array_equal(
        np.asarray(sp.region_histogram(rects)),
        np.asarray(region_histogram(full, rects)))
    with pytest.raises(ValueError, match="exceeds the uint16"):
        sp.region_histogram(np.array([[0, 0, 299, 299]]))   # 90000 px


# ---------------------------------------------------------------------------
# public API + prefetch + distributed composition
# ---------------------------------------------------------------------------
def test_map_bands_api_and_prefetch(rng):
    img = _img(rng, 48, 32)
    ih = IntegralHistogram(num_bins=8, backend="jnp")
    full = ih(jnp.asarray(img))
    for prefetch in (0, 2):             # 2 exercises prefetch_row_bands
        got = jnp.concatenate(
            [b.H for b in ih.map_bands(img, band_h=13, prefetch=prefetch)],
            axis=-2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(full))
    rects = np.array([[0, 0, 47, 31], [5, 5, 30, 20]])
    got = ih.banded_query(ih.map_bands(img, band_h=13), rects)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ih.query(full, rects)))


def test_banded_sharded_single_device(rng):
    """iter_banded_sharded_ih parity on a 1-device mesh (the 8-device run
    lives in test_distributed.py's subprocess tests)."""
    from repro.core.distributed import iter_banded_sharded_ih

    mesh = make_mesh((1,), ("model",))
    img = _img(rng, 24, 16)
    full = integral_histogram(jnp.asarray(img), 8, backend="jnp")
    got = jnp.concatenate(
        [b.H for b in iter_banded_sharded_ih(img, 8, mesh, sharding="bin",
                                             band_h=7)],
        axis=-2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full))
    with pytest.raises(ValueError, match="unknown sharding"):
        list(iter_banded_sharded_ih(img, 8, mesh, sharding="rows"))
