"""Query-fused execution path (ISSUE 8 tentpole).

The fused kernel computes ONLY the requested corner rows of H straight
out of the WF-TiS scan — full H never reaches HBM.  Pinned here:

  * bit-exact parity vs the dense jnp oracle on uneven shapes, for both
    the jnp streaming fallback and the Pallas kernel (interpret mode);
  * the live ``pallas_call`` conforms to the declared ``fused_rows``
    KernelSpec (grid / blocks / index maps at every grid point);
  * the early exit: bands below the last requested row are never
    scanned, and the peak-memory proxy (``FusedRowsH.nbytes`` plus the
    ``rows_bytes``/``full_h_bytes`` stats) shows H was never stored;
  * the planner's compute-vs-store decision (Ehsan et al.'s tradeoff)
    and its ``explain()`` rendering, golden-snapshotted;
  * end-to-end wiring: engine.run, service cache fallback on
    ``MissingRowsError``, tracker ``step_fused``, autotuned priors,
    and the fused likelihood-map output mode.
"""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from repro.analysis import kernelcheck as kc
from repro.core import autotune, distances
from repro.core.engine import (
    HistogramEngine,
    LikelihoodQuery,
    RegionQuery,
    SlidingWindowQuery,
    WorkloadSpec,
    plan,
)
from repro.core.hsource import DenseH, FusedRowsH, MissingRowsError
from repro.kernels import ops
from repro.kernels.fused_rows import fused_geometry, slot_plan
from repro.kernels.ref import integral_histogram_ref


def _oracle_rows(frames, num_bins, rows):
    """Dense-oracle corner rows: full ref H, then slice."""
    frames = np.asarray(frames)
    if frames.ndim == 2:
        H = integral_histogram_ref(frames, num_bins)
        return np.asarray(H)[:, rows, :]
    return np.stack([
        np.asarray(integral_histogram_ref(f, num_bins))[:, rows, :]
        for f in frames
    ])


# ---------------------------------------------------------------------------
# slot plan
# ---------------------------------------------------------------------------
def test_slot_plan_round_trip():
    rows = np.array([3, 7, 8, 30])
    slots, kp, pos = slot_plan(rows, tile=8, height=32)
    assert kp % 8 == 0 and slots.shape == (4, kp)
    # pos recovers request order from the (strip, kp) output layout
    flat = np.full(slots.shape, -1, np.int64)
    for s in range(slots.shape[0]):
        for j in range(kp):
            if slots[s, j] >= 0:
                flat[s, j] = s * 8 + slots[s, j]
    np.testing.assert_array_equal(flat.reshape(-1)[pos], rows)


@pytest.mark.parametrize("bad", [[5, 3], [2, 2], [-1], [40]])
def test_slot_plan_rejects_bad_rows(bad):
    with pytest.raises(ValueError):
        slot_plan(np.array(bad), tile=8, height=32)


# ---------------------------------------------------------------------------
# numeric parity vs the dense oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,rows", [
    ((50, 70), (0, 7, 31, 49)),                  # 2D squeeze, h < tile
    ((2, 37, 53), (4, 36)),                      # batch + uneven
    ((3, 300, 41), (10, 150, 299)),              # multi-band stream
])
def test_fused_jnp_matches_dense_oracle(shape, rows, rng):
    frames = rng.integers(0, 256, shape, np.uint8)
    rows = np.asarray(rows)
    got = ops.fused_corner_rows(frames, 8, rows, backend="jnp")
    np.testing.assert_allclose(np.asarray(got), _oracle_rows(frames, 8, rows))


def test_fused_pallas_interpret_matches_dense_oracle(rng):
    frames = rng.integers(0, 256, (2, 20, 24), np.uint8)
    rows = np.asarray([1, 6, 7, 13, 19])         # crosses strip edges
    got = ops.fused_corner_rows(
        frames, 8, rows, backend="pallas", tile=8, bin_block=4,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), _oracle_rows(frames, 8, rows))


def test_fused_pallas_call_matches_spec(monkeypatch, rng):
    """The declared fused_rows KernelSpec cannot drift from the live
    pallas_call (same conformance contract as the full-H kernels)."""
    from jax.experimental import pallas as pl

    captured = []
    real = pl.pallas_call

    def spy(kernel, **kw):
        captured.append(kw)
        return real(kernel, **kw)

    monkeypatch.setattr(pl, "pallas_call", spy)
    # The Pallas path is jit'd: drop earlier traces of these shapes so
    # this call traces the kernel (and meets the spy) again.
    jax.clear_caches()

    frames = rng.integers(0, 256, (2, 20, 24), np.uint8)
    rows = np.asarray([1, 6, 7, 13, 19])
    got = ops.fused_corner_rows(
        frames, 8, rows, backend="pallas", tile=8, bin_block=4,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), _oracle_rows(frames, 8, rows))

    # h_cut covers every band up to the last requested row (19 -> 24)
    geom = fused_geometry(rows, n=2, h=24, w=24, num_bins=8,
                          tile=8, bin_block=4)
    (spec,) = ops.KERNEL_SPECS["fused_rows"](geom)
    assert len(captured) == 1
    call = captured[0]
    assert tuple(call["grid"]) == spec.grid_sizes
    outs = call["out_specs"]
    if not isinstance(outs, (list, tuple)):
        outs = [outs]
    live = list(call["in_specs"]) + list(outs)
    declared = spec.in_specs + spec.out_specs
    assert len(live) == len(declared)
    for op, bs in zip(declared, live):
        assert tuple(bs.block_shape) == op.block, f"{op.name} block"
        for g in kc.iter_grid(spec):
            key = tuple(g[d] for d in spec.dim_names)
            assert tuple(bs.index_map(*key)) == tuple(op.index_map(*key)), \
                f"{op.name} index map at {g}"
    assert tuple(call["out_shape"].shape) == spec.out_specs[0].shape
    live_scratch = [tuple(s.shape) for s in call["scratch_shapes"]]
    assert live_scratch == [s.shape for s in spec.scratch]


@pytest.mark.parametrize("geom", [
    fused_geometry((7, 100, 333), n=2, h=384, w=640, num_bins=32),
    fused_geometry((0,), n=1, h=128, w=128, num_bins=8),
])
def test_fused_spec_proves_all_four_properties(geom):
    verdict = kc.check_method("fused_rows", geom)
    assert verdict.ok, verdict.render()


# ---------------------------------------------------------------------------
# early exit + peak-memory proxy: H is never materialized
# ---------------------------------------------------------------------------
def test_early_exit_skips_bands_below_last_row(rng):
    frames = rng.integers(0, 256, (1, 1024, 64), np.uint8)
    stats: dict = {}
    rows = np.asarray([10, 100])                 # both in band 0 (tile 128)
    got = ops.fused_corner_rows(frames, 4, rows, backend="jnp", stats=stats)
    assert stats["bands_computed"] == 1
    assert stats["bands_total"] == 8
    # the rows slab is a tiny fraction of full H
    assert stats["rows_bytes"] * 100 < stats["full_h_bytes"]
    np.testing.assert_allclose(np.asarray(got), _oracle_rows(frames, 4, rows))


def test_fused_source_never_holds_full_h(rng):
    eng = HistogramEngine(8, backend="jnp")
    frame = rng.integers(0, 256, (256, 256), np.uint8)
    out = eng.run(frame, [RegionQuery([10, 10, 40, 40])])
    assert out.plan.representation == "fused"
    src = out.source
    assert isinstance(src, FusedRowsH)
    full_h = 4 * 8 * 256 * 256
    assert src.nbytes * 10 < full_h             # peak-memory proxy
    assert src.last_fused_stats["bands_computed"] \
        < src.last_fused_stats["bands_total"]


# ---------------------------------------------------------------------------
# FusedRowsH guards
# ---------------------------------------------------------------------------
def test_fused_rows_h_serves_only_its_rows(rng):
    R = rng.random((8, 3, 24), np.float32)
    src = FusedRowsH((2, 9, 15), R, height=32, width=24)
    np.testing.assert_array_equal(np.asarray(src.rows([9, 15])),
                                  np.asarray(R[:, 1:, :]))
    with pytest.raises(MissingRowsError):
        src.rows([2, 3])
    with pytest.raises(MissingRowsError):
        src.dense()
    with pytest.raises(ValueError):
        FusedRowsH((2, 9), R, height=32, width=24)   # 2 ids, 3 rows


# ---------------------------------------------------------------------------
# planner decision + golden explain
# ---------------------------------------------------------------------------
def _spec(**kw):
    base = dict(height=480, width=640, num_bins=32, num_frames=2,
                backend="jnp")
    base.update(kw)
    return WorkloadSpec(**base)


def test_plan_fuses_small_row_unions_only():
    assert plan(_spec(query_rows=(99, 239, 300))).representation == "fused"
    many = tuple(range(0, 480, 3))               # 160 > 480 // 4
    assert plan(_spec(query_rows=many)).representation == "dense"
    # a pinned storage policy or a too-small budget vetoes fusion
    pinned = plan(_spec(query_rows=(99,), storage="uint16"))
    assert pinned.representation != "fused"
    # 3-row slab is 491520 B; a budget below that (but above one band
    # row) forces the store path instead
    tight = plan(_spec(query_rows=(99, 239, 300),
                       memory_budget_bytes=200_000))
    assert tight.representation == "banded"
    with pytest.raises(ValueError):
        plan(_spec(query_rows=(300, 99)))        # unsorted


GOLDEN_FUSE = """\
ExecutionPlan
  workload        : 480x640 uint8 frames, 32 bins, 2 frame(s)/request
  full H          : 39321600 B/frame (37.5 MiB fp32)
  representation  : fused
  query fusion    : fuse — 3 corner row(s) (491520 B) << full H 39321600 B; H never stored
  method/backend  : wf_tis / jnp
  tile/bin_block  : 128 / 8
  microbatch      : 2 frame(s)/dispatch
  bands           : none (no memory budget)
  storage         : device fp32
  sharding        : none"""

GOLDEN_STORE_LINE = (
    "  query fusion    : store — 160 corner row(s) exceed the fuse "
    "bound (120 rows); fall back to dense"
)


def test_explain_golden_snapshots():
    assert plan(_spec(query_rows=(99, 239, 300))).explain() == GOLDEN_FUSE
    store = plan(_spec(query_rows=tuple(range(0, 480, 3)))).explain()
    assert GOLDEN_STORE_LINE in store.splitlines()
    # plans with no declared rows render no fusion line at all
    assert "query fusion" not in plan(_spec()).explain()


# ---------------------------------------------------------------------------
# end-to-end: engine, service, tracker, likelihood map
# ---------------------------------------------------------------------------
def test_engine_run_fused_bit_exact_vs_dense(rng):
    frame = rng.integers(0, 256, (64, 48), np.uint8)
    qs = [RegionQuery([[4, 4, 20, 20], [10, 2, 30, 40]]),
          SlidingWindowQuery((16, 16), 16)]
    fused_eng = HistogramEngine(8, backend="jnp")
    out = fused_eng.run(frame, qs)
    assert out.plan.representation == "fused"
    dense = DenseH(ops.integral_histogram(frame, 8, backend="jnp"))
    for got, q in zip(out.results, qs):
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(q.apply(dense)))


def test_service_fused_cache_falls_back_on_foreign_rows(rng):
    from repro.serve import AnalyticsService

    store = {0: rng.integers(0, 256, (64, 48), np.uint8)}
    eng = HistogramEngine(8, backend="jnp")
    svc = AnalyticsService(eng, store, cache_size=2)
    q1 = RegionQuery([4, 4, 20, 20])
    svc.process([(0, q1)])
    assert eng.last_plan.representation == "fused"
    # a hit inside the fused rows answers from the cache
    svc.process([(0, q1)])
    assert svc.stats.cache_hits == 1 and svc.stats.engine_runs == 1
    # a hit OUTSIDE them can't — MissingRowsError triggers a re-run
    q2 = RegionQuery([30, 8, 50, 40])
    res = svc.process([(0, q2)])
    assert svc.stats.engine_runs == 2
    dense = DenseH(ops.integral_histogram(store[0], 8, backend="jnp"))
    np.testing.assert_array_equal(np.asarray(res[0]),
                                  np.asarray(q2.apply(dense)))


def test_tracker_step_fused_bit_exact(rng):
    from repro.core.tracking import FragmentTracker, TrackerConfig

    frames = rng.integers(0, 256, (3, 96, 120), np.uint8)
    tr = FragmentTracker(TrackerConfig(num_bins=8, search_radius=2))
    state = tr.init(frames[0], np.array([20, 30, 43, 53]))
    ref = dict(state)
    for f in frames[1:]:
        state = tr.step_fused(state, f)
        ref = tr.step(ref, f)
        np.testing.assert_array_equal(np.asarray(state["bbox"]),
                                      np.asarray(ref["bbox"]))
    assert tr._step_engine.last_plan.representation == "fused"


def test_fused_likelihood_map_matches_dense(rng):
    frame = rng.integers(0, 256, (40, 56), np.uint8)
    model = np.ones(8, np.float32) * 3.0
    got = ops.fused_likelihood_map(
        frame, model, distances.intersection, window=(8, 8), stride=4,
        backend="jnp")
    dense = DenseH(ops.integral_histogram(frame, 8, backend="jnp"))
    want = dense.likelihood_map(model, (8, 8), distances.intersection, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_likelihood_query_rides_the_fused_plan(rng):
    frame = rng.integers(0, 256, (64, 48), np.uint8)
    eng = HistogramEngine(8, backend="jnp")
    q = LikelihoodQuery(np.ones(8, np.float32), (16, 16),
                        distances.intersection, 16)
    out = eng.run(frame, [q])
    assert out.plan.representation == "fused"
    dense = DenseH(ops.integral_histogram(frame, 8, backend="jnp"))
    np.testing.assert_allclose(np.asarray(out.results[0]),
                               np.asarray(q.apply(dense)))


# ---------------------------------------------------------------------------
# autotuned priors
# ---------------------------------------------------------------------------
def test_priors_roundtrip_and_plan_pickup(tmp_path, monkeypatch):
    path = tmp_path / "tuned.json"
    key = autotune.config_key(480, 640, 32)
    autotune.save_priors(str(path), {
        key: {"tile": 256, "bin_block": 16, "seconds": 1e-3, "gbps": 40.0},
    })
    assert json.loads(path.read_text())["version"] == 1

    monkeypatch.setenv(autotune.ENV_VAR, str(path))
    p = plan(_spec(query_rows=(99, 239, 300)))
    assert (p.tile, p.bin_block, p.tuned) == (256, 16, key)
    assert f"(tuned prior {key})" in p.explain()
    # an explicit tile is a user decision the prior must not override
    q = plan(_spec(tile=64))
    assert (q.tile, q.tuned) == (64, None)
    # other geometries are untouched
    assert plan(_spec(height=240)).tuned is None


def test_autotune_measures_and_returns_winner():
    entry = autotune.autotune(
        64, 64, 8, backend="jnp", tiles=(64,), bin_blocks=(4, 8),
        repeats=1, memory_budget_bytes=4 * 8 * 16 * 64)
    assert entry["tile"] == 64 and entry["bin_block"] in (4, 8)
    assert entry["seconds"] > 0 and entry["gbps"] > 0
    assert 1 <= entry["band_h"] <= 16
