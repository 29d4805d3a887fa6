"""The async frame runtime (core/runtime.py) and its five adapters.

Tentpole of ISSUE 5: every streaming loop in the repo —
DoubleBufferedExecutor, IntegralHistogram.map_frames / map_bands,
HistogramEngine.map_frames, bands.iter_banded_ih, FragmentTracker.track
— is a thin adapter over ONE scheduler.  These tests pin:

  * frame-for-frame parity of every adapter with the direct per-item
    computation (dense, banded, tracker workloads);
  * carry threading (band bottom-row carry, tracker state) through the
    in-flight window;
  * the adaptive microbatch controller (scripted latencies -> sizing
    decisions, and output parity no matter what sizes it picks);
  * the supported paths emit NO DeprecationWarning (the ``banded_*``
    shims do, with a removal version).
"""

import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core.bands import banded_integral_histogram, iter_banded_ih
from repro.core.engine import HistogramEngine, auto_batch_size
from repro.core.integral_histogram import IntegralHistogram
from repro.core.pipeline import DoubleBufferedExecutor, prefetch_to_device
from repro.core.runtime import (
    AdaptiveMicrobatch,
    FrameRuntime,
    iter_chunks,
    stack_chunks,
)
from repro.core.tracking import FragmentTracker, TrackerConfig
from repro.kernels.ops import integral_histogram


def _frames(rng, n=7, h=24, w=20):
    return [rng.integers(0, 256, (h, w), dtype=np.uint8) for _ in range(n)]


# ---------------------------------------------------------------------------
# the scheduler core
# ---------------------------------------------------------------------------
def test_runtime_order_and_stats(rng):
    log = []

    def step(chunk, carry):
        log.append(np.shape(chunk))
        return jnp.asarray(chunk) * 2, carry

    rt = FrameRuntime(step, depth=3, microbatch=3)
    items = [np.full((2,), i, np.float32) for i in range(8)]
    outs = list(rt.map_frames(items))
    assert len(outs) == 8                      # one result per item
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o), [2 * i, 2 * i])
    assert log == [(3, 2), (3, 2), (2, 2)]     # ragged tail
    assert rt.last_stats.items == 8
    assert rt.last_stats.dispatches == 3
    assert rt.last_stats.batch_sizes == [3, 3, 2]
    assert len(rt.last_stats.latencies_s) == 3
    assert rt.last_stats.items_per_s > 0


def test_runtime_carry_threading():
    """carry rides between dispatches: running sum across chunks."""
    def step(chunk, carry):
        s = carry + jnp.sum(jnp.asarray(chunk))
        return s, s

    rt = FrameRuntime(step, depth=2, microbatch=2,
                      carry_in=jnp.asarray(0.0))
    outs, last = rt.fold(
        [np.asarray(float(i)) for i in [1, 2, 3, 4, 5]], batched=True)
    np.testing.assert_allclose([float(o) for o in outs], [3.0, 10.0, 15.0])
    assert float(last) == 15.0
    assert float(rt.last_carry) == 15.0


def test_runtime_depth_one_is_synchronous_and_valid():
    rt = FrameRuntime(FrameRuntime.stateless(lambda x: x), depth=1)
    outs = list(rt.map_frames([np.zeros(3), np.ones(3)]))
    assert len(outs) == 2
    with pytest.raises(ValueError):
        FrameRuntime(lambda c, s: (c, s), depth=0)
    with pytest.raises(ValueError):
        FrameRuntime(lambda c, s: (c, s), microbatch=0)
    with pytest.raises(ValueError):
        FrameRuntime(lambda c, s: (c, s), adaptive=True, block=False)


def test_iter_chunks_array_vs_iterable(rng):
    clip = rng.integers(0, 9, (7, 4, 4), dtype=np.uint8)
    a = list(iter_chunks(clip, 3))
    b = list(iter_chunks(iter(list(clip)), 3))
    assert [x.shape for x in a] == [(3, 4, 4), (3, 4, 4), (1, 4, 4)]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert [c.shape[0] for c in stack_chunks(iter(list(clip)), 4)] == [4, 3]


# ---------------------------------------------------------------------------
# adaptive microbatch controller (scripted latencies)
# ---------------------------------------------------------------------------
def test_adaptive_grows_when_batching_amortizes():
    """Per-dispatch latency ~constant (dispatch-bound): bigger batches
    win, controller climbs to max and locks."""
    c = AdaptiveMicrobatch(initial=1, max_size=8, settle=1)
    seen = []
    for _ in range(12):
        seen.append(c.size)
        c.observe(c.size, 0.010)       # 10 ms no matter the batch
    assert c.locked
    assert c.size == 8
    assert seen[0] == 1 and 8 in seen


def test_adaptive_backs_off_when_batching_hurts():
    """Latency superlinear in batch (cache-bound): stays small."""
    c = AdaptiveMicrobatch(initial=4, max_size=64, settle=1)
    for _ in range(12):
        c.observe(c.size, 0.001 * c.size**2)   # thr ~ 1/size: smaller wins
    assert c.locked
    assert c.size == 1


def test_adaptive_settles_at_interior_optimum():
    """Throughput peaks at 4: the probe ladder finds and locks it."""
    def latency(k):                     # thr(k) maximal at k=4
        return {1: 1.0, 2: 0.45, 4: 0.2, 8: 0.5, 16: 2.0}[k] / 10

    c = AdaptiveMicrobatch(initial=2, max_size=16, settle=1)
    for _ in range(16):
        c.observe(c.size, latency(c.size))
    assert c.locked
    assert c.size == 4


def test_adaptive_stale_samples_do_not_steer():
    """With a depth-k window, dispatches built at an old size retire
    after the controller moved; their samples are recorded under the
    size that BUILT them and never trigger a decision at the new size."""
    c = AdaptiveMicrobatch(initial=1, max_size=8, settle=1)
    c.observe(1, 0.010)                  # size 1 settles -> moves to 2
    assert c.size == 2
    # a lagged size-1 dispatch retires now: terrible throughput, but it
    # must be filed under size 1, not poison size 2's record
    c.observe(1, 10.0, size=1)
    assert not c.locked and c.size == 2  # no decision fired
    c.observe(2, 0.010)                  # genuine size-2 sample: climbs
    assert c.size == 4


def test_adaptive_runtime_output_parity(rng):
    """Whatever sizes the controller picks, results match per-frame."""
    ih = IntegralHistogram(num_bins=8, backend="jnp")
    frames = _frames(rng, n=9)
    want = [np.asarray(ih(jnp.asarray(f))) for f in frames]
    rt = FrameRuntime(FrameRuntime.stateless(ih), depth=2, microbatch=2,
                      adaptive=True, max_microbatch=4)
    got = list(rt.map_frames(frames))
    assert len(got) == 9
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, np.asarray(g))
    assert rt.controller is not None
    assert sum(rt.last_stats.batch_sizes) == 9


# ---------------------------------------------------------------------------
# adapter parity: the five legacy loops over the one runtime
# ---------------------------------------------------------------------------
def test_executor_adapter_parity(rng):
    ih = IntegralHistogram(num_bins=8, backend="jnp")
    frames = _frames(rng)
    want = [np.asarray(ih(jnp.asarray(f))) for f in frames]
    for depth, batch in [(1, 1), (2, 3), (3, 2)]:
        ex = DoubleBufferedExecutor(ih, depth=depth, batch_size=batch)
        got = list(ex.map(frames))
        assert len(got) == len(frames)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, np.asarray(g))


def test_map_frames_adapter_parity(rng):
    ih = IntegralHistogram(num_bins=8, backend="jnp")
    frames = _frames(rng)
    want = [np.asarray(ih(jnp.asarray(f))) for f in frames]
    for kw in [dict(batch_size=2), dict(batch_size="auto"),
               dict(batch_size="adaptive")]:
        got = list(ih.map_frames(frames, **kw))
        assert len(got) == len(frames)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, np.asarray(g))
    with pytest.raises(ValueError):
        list(ih.map_frames(frames, batch_size="bogus"))


def test_engine_map_frames_adapter_parity(rng):
    eng = HistogramEngine(8, backend="jnp")
    frames = _frames(rng)
    want = [np.asarray(eng.compute_dense(jnp.asarray(f))) for f in frames]
    got = list(eng.map_frames(frames))
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, np.asarray(g))
    assert eng.last_runtime is not None
    assert eng.last_runtime.last_stats.items == len(frames)
    # adaptive engine: same outputs, runtime carries a controller
    eng2 = HistogramEngine(8, backend="jnp", adaptive_microbatch=True)
    got2 = list(eng2.map_frames(frames))
    for w, g in zip(want, got2):
        np.testing.assert_array_equal(w, np.asarray(g))
    assert eng2.last_plan.microbatch_mode == "adaptive"
    assert eng2.last_runtime.controller is not None


def test_banded_adapter_parity_and_carry(rng):
    img = rng.integers(0, 256, (37, 16), dtype=np.uint8)
    full = np.asarray(integral_histogram(img, 8, backend="jnp"))
    for prefetch in (0, 2):
        np.testing.assert_array_equal(
            np.asarray(banded_integral_histogram(
                img, 8, band_h=10, backend="jnp", prefetch=prefetch)),
            full,
        )
    bands = list(iter_banded_ih(img, 8, band_h=10, backend="jnp"))
    assert [(b.r0, b.r1) for b in bands] == [
        (0, 10), (10, 20), (20, 30), (30, 37)]
    assert bands[0].num_bands == 4 and bands[-1].frame_h == 37
    for b in bands:
        np.testing.assert_array_equal(
            np.asarray(b.carry), np.asarray(b.H[..., -1, :]))
        np.testing.assert_array_equal(
            np.asarray(b.H), full[..., b.r0:b.r1, :])


def test_tracker_adapter_parity(rng):
    clip = np.stack(_frames(rng, n=6, h=32, w=32))
    tr = FragmentTracker(TrackerConfig(num_bins=8, search_radius=3))
    st0 = tr.init(jnp.asarray(clip[0]), [4, 4, 15, 15])
    want_state = dict(st0)
    want = []
    for f in clip:
        want_state = tr.step(want_state, jnp.asarray(f))
        want.append(np.asarray(want_state["bbox"]))
    for frames in (clip, iter(list(clip))):      # sliced and stacked paths
        st, boxes = tr.track(dict(st0), frames, batch_size=2)
        np.testing.assert_array_equal(np.asarray(boxes), np.stack(want))
        np.testing.assert_array_equal(
            np.asarray(st["bbox"]), np.asarray(want_state["bbox"]))
    # auto sizing comes from the planner now
    st, boxes = tr.track(dict(st0), clip)
    np.testing.assert_array_equal(np.asarray(boxes), np.stack(want))


def test_tracker_empty_and_bad_batch(rng):
    tr = FragmentTracker(TrackerConfig(num_bins=8))
    frame = rng.integers(0, 256, (16, 16), dtype=np.uint8)
    st = tr.init(jnp.asarray(frame), [2, 2, 9, 9])
    for empty in (np.zeros((0, 16, 16), np.uint8), iter(())):
        st2, boxes = tr.track(dict(st), empty)
        assert boxes.shape == (0, 4)
    with pytest.raises(ValueError):
        tr.track(dict(st), np.zeros((3, 16, 16), np.uint8), batch_size=0)


def test_prefetch_to_device_staging_window(rng):
    """Exactly `size` staged before the first yield (the PR 2 fix)."""
    staged = []

    def gen(n=5):
        for i in range(n):
            staged.append(i)
            yield np.full((2,), i, np.float32)

    it = prefetch_to_device(gen(), size=2)
    first = next(it)
    assert staged == [0, 1]                     # not size + 1
    np.testing.assert_array_equal(np.asarray(first), [0, 0])
    assert len(list(it)) == 4


def test_auto_batch_size_reexport_matches_planner():
    """Satellite: pipeline re-exports the planner's auto_batch_size."""
    from repro.core import pipeline

    assert pipeline.auto_batch_size is auto_batch_size
    assert auto_batch_size(8, 24, 20) == 16
    assert auto_batch_size(128, 2048, 2048) == 1


# ---------------------------------------------------------------------------
# deprecation hygiene
# ---------------------------------------------------------------------------
def test_runtime_adapters_emit_no_deprecation_warnings(rng):
    """The supported streaming paths are warning-free; only the
    ``banded_*`` shims warn (with a removal version)."""
    ih = IntegralHistogram(num_bins=8, backend="jnp")
    img = rng.integers(0, 256, (30, 16), dtype=np.uint8)
    frames = _frames(rng, n=4)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        list(ih.map_frames(frames, batch_size=2))
        list(ih.map_bands(img, band_h=10))
        list(DoubleBufferedExecutor(ih, depth=2).map(frames[:2]))
        list(HistogramEngine(8, backend="jnp").map_frames(frames[:2]))
        tr = FragmentTracker(TrackerConfig(num_bins=8, search_radius=2))
        st = tr.init(jnp.asarray(frames[0]), [2, 2, 9, 9])
        tr.track(st, np.stack(frames))


def test_banded_shims_name_a_removal_version(rng):
    from repro.core.region_query import banded_region_histogram

    img = rng.integers(0, 256, (20, 12), dtype=np.uint8)
    bands = iter_banded_ih(img, 4, band_h=8, backend="jnp")
    with pytest.warns(DeprecationWarning, match=r"removed in 2\.0"):
        banded_region_histogram(bands, np.array([1, 1, 8, 8]))


# ---------------------------------------------------------------------------
# Sharding-aware staging (mesh-scale serving)
# ---------------------------------------------------------------------------
def test_stage_stream_accepts_a_sharding():
    """`device=` takes any jax.device_put placement — a NamedSharding
    commits each staged item to the mesh layout instead of one device
    (what removed the sharded-plan staging carve-out in bands)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.runtime import stage_stream

    mesh = make_mesh((1,), ("data",))
    ns = NamedSharding(mesh, P())
    items = [np.full((4, 4), i, np.float32) for i in range(3)]
    staged = list(stage_stream(iter(items), size=2, device=ns))
    assert len(staged) == 3
    for i, x in enumerate(staged):
        assert x.sharding == ns
        np.testing.assert_array_equal(np.asarray(x), items[i])


def test_frame_runtime_stages_with_a_sharding(rng):
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh((1,), ("data",))
    ns = NamedSharding(mesh, P())
    seen = []

    def step(chunk, carry):
        seen.append(chunk.sharding)
        return chunk * 2, carry

    rt = FrameRuntime(step, depth=1, device=ns, stage_inputs=True)
    items = [np.full((2,), i, np.float32) for i in range(4)]
    outs = [d.out for d in rt.run(items, batched=False)]
    assert all(s == ns for s in seen)
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o), [2 * i, 2 * i])


def test_iter_banded_ih_stages_when_device_given(rng):
    """An explicit device placement turns staging on even at prefetch=0
    (the old carve-out skipped staging for sharded plans entirely)."""
    import jax

    img = rng.integers(0, 256, (24, 16), dtype=np.uint8)
    dev = jax.devices()[0]
    bands = list(iter_banded_ih(img, 8, band_h=8, backend="jnp", device=dev))
    full = np.concatenate([np.asarray(b.H) for b in bands], axis=-2)
    ref = np.asarray(integral_histogram(jnp.asarray(img), 8, backend="jnp"))
    np.testing.assert_array_equal(full, ref)
