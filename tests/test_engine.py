"""Plan/execute engine + HSource protocol correctness.

The acceptance bar (ISSUE 4): one ``HistogramEngine``/``plan()`` entry
point covers all four H representations — the parity grid below asserts
every plan-selected path is bit-exact against the monolithic jnp oracle
for dense, banded, spilled, and (single-device here; 8-device in
test_distributed.py) sharded H; ``plan.explain()`` is golden-snapshot
tested for the paper's 640x480/32-bin and 64 MB/128-bin scenarios; the
``banded_*`` analytics forks are deprecation shims over the unified
dispatch."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.core import distances
from repro.core.bands import iter_banded_ih
from repro.core.engine import (
    EngineResult,
    HistogramEngine,
    LikelihoodQuery,
    MultiScaleQuery,
    RegionQuery,
    SlidingWindowQuery,
    WorkloadSpec,
    plan,
)
from repro.core.hsource import BandedH, DenseH, ShardedH, as_hsource
from repro.core.integral_histogram import IntegralHistogram
from repro.core.pipeline import auto_batch_size
from repro.core.region_query import (
    banded_likelihood_map,
    banded_region_histogram,
    banded_sliding_window_histograms,
    likelihood_map,
    multi_scale_search,
    region_histogram,
    sliding_window_histograms,
)
from repro.kernels.ops import integral_histogram


def _img(rng, *shape):
    return rng.integers(0, 256, shape, dtype=np.uint8)


def _oracle(img, bins):
    """The monolithic jnp H — every planned path must match it bit-exactly."""
    return integral_histogram(jnp.asarray(img), bins, backend="jnp")


# ---------------------------------------------------------------------------
# planner decisions + parity grid: every selected path vs the oracle
# ---------------------------------------------------------------------------
# (h, w, bins, budget rows | None, batch, storage, expected representation)
GRID = [
    (37, 23, 8, None, 1, None, "dense"),
    (37, 23, 8, 6, 1, None, "banded"),           # 6-row bands, uneven tail
    (52, 40, 8, 52, 3, None, "dense"),           # budget fits in one band
    (52, 40, 8, 13, 1, "uint16", "spilled"),     # modular storage policy
    (40, 32, 6, 11, 2, None, "banded"),          # banded frame stack
    (30, 20, 4, None, 1, "uint32", "spilled"),   # spill without a budget
]


@pytest.mark.parametrize(
    "h, w, bins, budget_rows, batch, storage, expect", GRID
)
def test_plan_grid_parity(rng, h, w, bins, budget_rows, batch, storage,
                          expect):
    img = _img(rng, h, w) if batch == 1 else _img(rng, batch, h, w)
    budget = (
        None if budget_rows is None
        else 4 * (batch if batch > 1 else 1) * bins * w * budget_rows
    )
    eng = HistogramEngine(
        bins, backend="jnp", memory_budget_bytes=budget, storage=storage
    )
    full = _oracle(img, bins)
    rects = np.array([[0, 0, h - 1, w - 1], [3, 4, h // 2, w - 2],
                      [5, 5, 5, 5]])
    out = eng.run(img, [RegionQuery(rects), SlidingWindowQuery((9, 7), 4)])
    assert out.plan.representation == expect
    assert eng.last_plan is out.plan
    np.testing.assert_array_equal(
        np.asarray(out.results[0]), np.asarray(region_histogram(full, rects))
    )
    np.testing.assert_array_equal(
        np.asarray(out.results[1]),
        np.asarray(sliding_window_histograms(full, (9, 7), 4)),
    )


@pytest.mark.parametrize("axis, expect_kind", [("model", "bin"),
                                               ("data", "spatial")])
def test_plan_grid_parity_sharded_single_device(rng, axis, expect_kind):
    """The sharded representations on a 1-device mesh (the 8-device runs
    live in test_distributed.py's subprocess tests)."""
    mesh = make_mesh((1,), (axis,))
    img = _img(rng, 24, 16)
    eng = HistogramEngine(8, backend="jnp", mesh=mesh)
    full = _oracle(img, 8)
    rects = np.array([[0, 0, 23, 15], [3, 2, 20, 10]])
    out = eng.run(img, [RegionQuery(rects)])
    assert out.plan.representation == "sharded"
    assert out.plan.sharding == expect_kind
    np.testing.assert_array_equal(
        np.asarray(out.results[0]), np.asarray(region_histogram(full, rects))
    )
    # banded + sharded: budget forces a band plan on top of the mesh
    eng_b = HistogramEngine(8, backend="jnp", mesh=mesh,
                            memory_budget_bytes=4 * 8 * 16 * 7)
    out_b = eng_b.run(img, [RegionQuery(rects),
                            SlidingWindowQuery((9, 7), 3)])
    assert out_b.plan.representation == "sharded"
    assert out_b.plan.band_plan is not None
    np.testing.assert_array_equal(
        np.asarray(out_b.results[0]), np.asarray(region_histogram(full, rects))
    )
    np.testing.assert_array_equal(
        np.asarray(out_b.results[1]),
        np.asarray(sliding_window_histograms(full, (9, 7), 3)),
    )


def test_multi_scale_and_likelihood_unified(rng):
    """likelihood_map / multi_scale_search through every representation:
    one rows() pass serves all scales of a banded search."""
    img = _img(rng, 48, 36)
    bins = 8
    full = _oracle(img, bins)
    target = region_histogram(full, np.array([10, 8, 29, 23]))
    windows = ((20, 16), (12, 10), (50, 50))     # last exceeds the frame
    want = multi_scale_search(full, target, windows, distances.intersection,
                              stride=4)
    for source in (
        DenseH(full),
        BandedH(lambda: iter_banded_ih(img, bins, band_h=13, backend="jnp")),
        HistogramEngine(bins, backend="jnp", storage="uint16").compute(img),
    ):
        got = multi_scale_search(source, target, windows,
                                 distances.intersection, stride=4)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        for m_want, m_got in zip(want[2], got[2]):
            np.testing.assert_array_equal(
                np.asarray(m_got), np.asarray(m_want))
    lm_want = likelihood_map(full, target, (20, 16),
                             distances.intersection, 4)
    lm_got = likelihood_map(
        BandedH(lambda: iter_banded_ih(img, bins, band_h=13, backend="jnp")),
        target, (20, 16), distances.intersection, 4)
    np.testing.assert_array_equal(np.asarray(lm_got), np.asarray(lm_want))


# ---------------------------------------------------------------------------
# plan object: determinism, explain() golden snapshots, absorbed decisions
# ---------------------------------------------------------------------------
def test_plan_is_deterministic_and_inspectable():
    spec = WorkloadSpec(height=96, width=64, num_bins=8,
                        memory_budget_bytes=4 * 8 * 64 * 12, backend="jnp")
    p1, p2 = plan(spec), plan(spec)
    assert p1 == p2                      # frozen dataclasses: value equality
    assert p1.band_plan == p2.band_plan
    assert "banded" in p1.explain()


GOLDEN_640x480_32 = """\
ExecutionPlan
  workload        : 480x640 uint8 frames, 32 bins, 1 frame(s)/request
  full H          : 39321600 B/frame (37.5 MiB fp32)
  representation  : dense
  method/backend  : wf_tis / jnp
  tile/bin_block  : 128 / 8
  microbatch      : 1 frame(s)/dispatch
  bands           : none (no memory budget)
  storage         : device fp32
  sharding        : none"""

# The paper's §4.6 scale scenario: a 64 MB (8192x8192 uint8) frame at 128
# bins whose H is 32 GiB, planned under a 256 MiB budget.
GOLDEN_64MB_128 = """\
ExecutionPlan
  workload        : 8192x8192 uint8 frames, 128 bins, 1 frame(s)/request
  full H          : 34359738368 B/frame (32768.0 MiB int32)
  representation  : banded
  method/backend  : wf_tis / jnp
  tile/bin_block  : 128 / 8
  microbatch      : 1 frame(s)/dispatch
  bands           : 128 x 64 rows (268435456 B/band <= 268435456 B budget)
  storage         : device int32
  sharding        : none"""


def test_plan_explain_golden_paper_scenarios():
    p = plan(WorkloadSpec(height=480, width=640, num_bins=32, backend="jnp"))
    assert p.explain() == GOLDEN_640x480_32
    p = plan(WorkloadSpec(height=8192, width=8192, num_bins=128,
                          memory_budget_bytes=256 << 20, backend="jnp"))
    assert p.explain() == GOLDEN_64MB_128


def test_plan_absorbs_auto_batch_size():
    """The open-stream microbatch is exactly pipeline.auto_batch_size —
    map_frames' "auto" now asks the planner."""
    for h, w, bins in [(64, 64, 16), (480, 640, 32)]:
        p = plan(WorkloadSpec(height=h, width=w, num_bins=bins,
                              num_frames=None, backend="jnp"))
        assert p.microbatch == auto_batch_size(bins, h, w)
    # capped by the request arity
    p = plan(WorkloadSpec(height=64, width=64, num_bins=4, num_frames=2,
                          backend="jnp"))
    assert p.microbatch == 2


def test_plan_validation_errors():
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="storage"):
        plan(WorkloadSpec(height=16, width=16, num_bins=4, mesh=mesh,
                          storage="uint16", backend="jnp"))
    with pytest.raises(ValueError, match="unknown sharding"):
        plan(WorkloadSpec(height=16, width=16, num_bins=4, mesh=mesh,
                          sharding="rows", backend="jnp"))
    with pytest.raises(ValueError, match="unknown backend"):
        plan(WorkloadSpec(height=16, width=16, num_bins=4, backend="cuda"))
    with pytest.raises(ValueError, match="no Pallas kernel"):
        plan(WorkloadSpec(height=16, width=16, num_bins=4, method="cw_b",
                          backend="pallas"))
    # spatial sharding is single-frame: a stack must be rejected, not
    # silently row-sharded along the frame axis
    with pytest.raises(ValueError, match="single-frame"):
        plan(WorkloadSpec(height=16, width=16, num_bins=4, num_frames=3,
                          mesh=mesh, sharding="spatial", backend="jnp"))


def test_dense_budget_caps_microbatch():
    """A budget that fits one frame but not the auto microbatch shrinks
    the dispatch instead of overrunning the budget."""
    # 64x64x4 bins: per-frame H = 64 KiB, auto microbatch would be 16
    per_frame = 4 * 4 * 64 * 64
    p = plan(WorkloadSpec(height=64, width=64, num_bins=4, num_frames=None,
                          memory_budget_bytes=3 * per_frame, backend="jnp"))
    assert p.representation == "dense"
    assert p.microbatch == 3


def test_engine_map_frames_rejects_non_dense_plans(rng):
    """map_frames streams dense H's: a plan the engine cannot honour on
    that path (banded/spilled/sharded) must raise, not silently ignore
    the configured budget/mesh/storage."""
    frames = _img(rng, 3, 32, 24)
    tiny = HistogramEngine(8, backend="jnp",
                           memory_budget_bytes=4 * 8 * 24 * 4)   # 4-row bands
    with pytest.raises(ValueError, match="banded"):
        list(tiny.map_frames(list(frames)))
    spilled = HistogramEngine(8, backend="jnp", storage="uint16")
    with pytest.raises(ValueError, match="spilled"):
        list(spilled.map_frames(list(frames)))


def test_multi_query_run_streams_bands_once(rng):
    """engine.run with k queries on a banded plan must not recompute the
    band stream k times: the row union is prefetched in ONE pass."""
    from repro.core.engine import prefetch_rows
    from repro.core.hsource import PrefetchedRowsH

    img = _img(rng, 52, 40)
    bins = 8
    full = _oracle(img, bins)
    rects = np.array([[0, 0, 51, 39], [5, 5, 30, 30]])
    target = region_histogram(full, rects[1])
    streams = {"n": 0}

    def counting_factory():
        streams["n"] += 1
        return iter_banded_ih(img, bins, band_h=13, backend="jnp")

    src = BandedH(counting_factory)
    queries = [
        RegionQuery(rects),
        SlidingWindowQuery((12, 8), 4),
        LikelihoodQuery(target, (12, 8), distances.intersection, 4),
        MultiScaleQuery(target, ((12, 8), (20, 16)), stride=4),
    ]
    pf = prefetch_rows(src, queries)
    assert isinstance(pf, PrefetchedRowsH)
    results = [q.apply(pf) for q in queries]
    assert streams["n"] == 1                  # one stream served everything
    np.testing.assert_array_equal(
        np.asarray(results[0]), np.asarray(region_histogram(full, rects)))
    np.testing.assert_array_equal(
        np.asarray(results[1]),
        np.asarray(sliding_window_histograms(full, (12, 8), 4)))
    np.testing.assert_array_equal(
        np.asarray(results[3][0]),
        np.asarray(multi_scale_search(full, target, ((12, 8), (20, 16)),
                                      distances.intersection, 4)[0]))
    with pytest.raises(KeyError, match="not prefetched"):
        pf.rows(np.array([2]))                # not in any query's union
    # the engine wires the same path: a 2-query banded run is bit-exact
    eng = HistogramEngine(bins, backend="jnp",
                          memory_budget_bytes=4 * bins * 40 * 13)
    out = eng.run(img, queries[:2])
    assert out.plan.representation == "banded"
    np.testing.assert_array_equal(
        np.asarray(out.results[0]), np.asarray(region_histogram(full, rects)))


def test_multi_scale_oversized_window_on_spilled(rng):
    """A scale larger than the frame is skipped (empty map) on a
    policy-bounded source, exactly like the dense path — it must not trip
    the storage bound check."""
    img = _img(rng, 48, 36)
    full = _oracle(img, 8)
    target = region_histogram(full, np.array([10, 8, 29, 23]))
    sp = HistogramEngine(8, backend="jnp", storage="uint16").compute(img)
    windows = ((20, 16), (400, 400))          # second: 160000 px > 65535
    want = multi_scale_search(full, target, windows,
                              distances.intersection, 4)
    got = multi_scale_search(sp, target, windows,
                             distances.intersection, 4)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert got[2][1].shape == want[2][1].shape == (0, 0)


def test_spatial_open_stream_plans_but_map_frames_rejects(rng):
    """num_frames=None (open stream) is frames one at a time, so a
    spatial plan is legal; map_frames still rejects it with its own
    'streams dense' error rather than the stack message."""
    mesh = make_mesh((1,), ("data",))
    p = plan(WorkloadSpec(height=16, width=16, num_bins=4, num_frames=None,
                          mesh=mesh, sharding="spatial", backend="jnp"))
    assert p.representation == "sharded"
    eng = HistogramEngine(4, backend="jnp", mesh=mesh, sharding="spatial")
    with pytest.raises(ValueError, match="streams dense"):
        list(eng.map_frames([_img(rng, 16, 16)]))


def test_raw_path_fills_stats(rng):
    """The dense raw-array path populates the same stats keys as every
    HSource path (migrating callers keep reading stats['peak_bytes'])."""
    img = _img(rng, 40, 28)
    full = _oracle(img, 8)
    keys = {"num_bands", "band_bytes", "slab_bytes", "peak_bytes",
            "full_h_bytes"}
    stats_raw: dict = {}
    sliding_window_histograms(full, (9, 7), 3, stats=stats_raw)
    assert keys <= set(stats_raw) and stats_raw["num_bands"] == 1
    stats_dense: dict = {}
    DenseH(full).sliding_window_histograms((9, 7), 3, stats=stats_dense)
    assert stats_dense == stats_raw
    stats_banded: dict = {}
    sliding_window_histograms(
        BandedH(lambda: iter_banded_ih(img, 8, band_h=11, backend="jnp")),
        (9, 7), 3, stats=stats_banded)
    assert keys <= set(stats_banded) and stats_banded["num_bands"] == 4


# ---------------------------------------------------------------------------
# HSource protocol mechanics
# ---------------------------------------------------------------------------
def test_banded_single_shot_and_factory(rng):
    img = _img(rng, 26, 11)
    full = _oracle(img, 4)
    rects = np.array([[0, 0, 25, 10]])
    one_shot = BandedH(iter_banded_ih(img, 4, band_h=7, backend="jnp"))
    np.testing.assert_array_equal(
        np.asarray(one_shot.region_histogram(rects)),
        np.asarray(region_histogram(full, rects)))
    with pytest.raises(RuntimeError, match="factory"):
        one_shot.region_histogram(rects)
    # a factory replays: two queries, two streams
    fac = BandedH(lambda: iter_banded_ih(img, 4, band_h=7, backend="jnp"))
    for _ in range(2):
        np.testing.assert_array_equal(
            np.asarray(fac.region_histogram(rects)),
            np.asarray(region_histogram(full, rects)))


def test_as_hsource_coercions(rng):
    img = _img(rng, 16, 12)
    full = _oracle(img, 4)
    assert isinstance(as_hsource(full), DenseH)
    assert isinstance(
        as_hsource(iter_banded_ih(img, 4, band_h=5, backend="jnp")), BandedH)
    assert isinstance(
        as_hsource(lambda: iter_banded_ih(img, 4, band_h=5, backend="jnp")),
        BandedH)
    src = as_hsource(full)
    assert as_hsource(src) is src
    with pytest.raises(TypeError, match="cannot interpret"):
        as_hsource(42)
    with pytest.raises(ValueError, match="unknown sharding kind"):
        ShardedH(full, None, kind="rows")


def test_hsource_metadata_and_dense(rng):
    img = _img(rng, 2, 20, 14)
    full = _oracle(img, 4)
    src = BandedH(lambda: iter_banded_ih(img, 4, band_h=6, backend="jnp"))
    assert (src.num_bins, src.height, src.width, src.lead) == (4, 20, 14, (2,))
    np.testing.assert_array_equal(np.asarray(src.dense()), np.asarray(full))
    d = DenseH(full)
    assert (d.num_bins, d.height, d.width, d.lead) == (4, 20, 14, (2,))
    assert d.dense() is full


# ---------------------------------------------------------------------------
# deprecation shims (satellite 1)
# ---------------------------------------------------------------------------
def test_banded_shims_warn_and_forward(rng):
    img = _img(rng, 40, 28)
    bins = 8
    full = _oracle(img, bins)
    rects = np.array([[0, 0, 39, 27], [5, 5, 20, 20]])
    target = region_histogram(full, rects[1])

    def bands():
        return iter_banded_ih(img, bins, band_h=11, backend="jnp")

    with pytest.warns(DeprecationWarning, match="banded_region_histogram"):
        got = banded_region_histogram(bands(), rects)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(region_histogram(full, rects)))

    with pytest.warns(DeprecationWarning,
                      match="banded_sliding_window_histograms"):
        got = banded_sliding_window_histograms(bands(), (9, 7), 3)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(sliding_window_histograms(full, (9, 7), 3)))

    with pytest.warns(DeprecationWarning, match="banded_likelihood_map"):
        got = banded_likelihood_map(bands(), target, (9, 7),
                                    distances.intersection, 3)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(likelihood_map(full, target, (9, 7),
                                  distances.intersection, 3)))


# ---------------------------------------------------------------------------
# engine facade
# ---------------------------------------------------------------------------
def test_engine_run_result_shape(rng):
    img = _img(rng, 32, 24)
    eng = HistogramEngine(8, backend="jnp")
    out = eng.run(img)
    assert isinstance(out, EngineResult) and out.results == []
    full = _oracle(img, 8)
    target = region_histogram(full, np.array([4, 4, 19, 15]))
    out = eng.run(img, [
        RegionQuery(np.array([[0, 0, 31, 23]])),
        LikelihoodQuery(target, (16, 12), stride=4),
        MultiScaleQuery(target, ((16, 12), (8, 6)), stride=4),
    ])
    assert len(out.results) == 3
    want = multi_scale_search(full, target, ((16, 12), (8, 6)),
                              distances.intersection, 4)
    np.testing.assert_array_equal(
        np.asarray(out.results[2][0]), np.asarray(want[0]))


def test_engine_map_frames_matches_legacy(rng):
    frames = _img(rng, 5, 24, 20)
    ih = IntegralHistogram(num_bins=8, backend="jnp")
    eng = ih.engine()
    got = [np.asarray(H) for H in eng.map_frames(list(frames))]
    want = [np.asarray(H) for H in ih.map_frames(list(frames),
                                                 batch_size="auto")]
    assert eng.last_plan.microbatch == auto_batch_size(8, 24, 20)
    assert len(got) == len(want) == 5
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(g, w_)
    assert list(eng.map_frames(iter(()))) == []


def test_integral_histogram_engine_helper():
    ih = IntegralHistogram(num_bins=16, method="cw_sts", backend="jnp",
                           tile=64)
    eng = ih.engine(memory_budget_bytes=1 << 20)
    assert (eng.num_bins, eng.method, eng.backend, eng.tile) == (
        16, "cw_sts", "jnp", 64)
    assert eng.memory_budget_bytes == 1 << 20


def test_tracker_rides_the_engine(rng):
    """FragmentTracker accepts an engine for its H computation and an
    HSource in step_on_h — same boxes as the hand-routed path."""
    from repro.core.tracking import FragmentTracker, TrackerConfig

    frames = _img(rng, 4, 40, 32)
    cfg = TrackerConfig(num_bins=8, search_radius=4, backend="jnp")
    bbox = np.array([10, 8, 25, 23])
    legacy = FragmentTracker(cfg)
    st_l = legacy.init(jnp.asarray(frames[0]), bbox)
    eng = HistogramEngine(8, backend="jnp")
    routed = FragmentTracker(cfg, engine=eng)
    st_r = routed.init(jnp.asarray(frames[0]), bbox)
    for f in frames[1:]:
        st_l = legacy.step(st_l, jnp.asarray(f))
        st_r = routed.step_on_h(st_r, DenseH(eng.compute_dense(jnp.asarray(f))))
        np.testing.assert_array_equal(
            np.asarray(st_l["bbox"]), np.asarray(st_r["bbox"]))
    with pytest.raises(ValueError, match="num_bins"):
        FragmentTracker(cfg, engine=HistogramEngine(4, backend="jnp"))


# ---------------------------------------------------------------------------
# mesh layout (replica x shard serving layout)
# ---------------------------------------------------------------------------
def test_plan_mesh_layout_rendered_and_validated(rng):
    """Sharded plans carry the 2-D replica x shard MeshLayout: explain()
    renders it, plancheck validates it, non-mesh plans never grow one
    (the golden snapshots above pin the absence)."""
    import dataclasses

    from repro.analysis import plancheck
    from repro.core.engine import MeshLayout, choose_layout

    mesh = make_mesh((1, 1), ("data", "model"))
    p = plan(WorkloadSpec(height=24, width=16, num_bins=8, num_frames=1,
                          backend="jnp", mesh=mesh))
    assert p.sharding == "bin"
    lay = p.layout
    assert isinstance(lay, MeshLayout)
    assert lay.kind == "bin" and lay.shard_axis == "model"
    assert lay.replica_axes == ("data",)
    assert lay.num_groups * lay.shards_per_group == 1
    text = p.explain()
    assert "mesh layout     : " in text
    assert "replica group(s) over 'data'" in text
    assert "bin sharding over 'model'" in text
    verdict = plancheck.check_plan(p)
    assert verdict.ok
    assert any(c.name == "mesh-layout" and c.status == "ok"
               for c in verdict.checks)
    # spatial flips the axes: 'data' shards rows, 'model' replicates
    sp = plan(WorkloadSpec(height=24, width=16, num_bins=7, num_frames=1,
                           backend="jnp", mesh=mesh, sharding="spatial"))
    assert sp.sharding == "spatial"
    assert sp.layout.shard_axis == "data"
    assert sp.layout.replica_axes == ("model",)
    # non-mesh plans carry no layout
    assert plan(WorkloadSpec(height=24, width=16, num_bins=8,
                             backend="jnp")).layout is None
    # a corrupted layout fails the check loudly
    bad = dataclasses.replace(
        p, layout=choose_layout(mesh, "bin", bin_axis="nope"))
    v_bad = plancheck.check_plan(bad)
    assert not v_bad.ok
    assert any(c.name == "mesh-layout" and c.status == "fail"
               for c in v_bad.checks)
