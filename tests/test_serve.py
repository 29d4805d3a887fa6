"""AnalyticsService (repro/serve): coalescing, caching, backpressure.

Acceptance (ISSUE 5): >= 2 same-frame queries coalesce into ONE engine
run (compute-count probe), results are bit-exact vs direct engine runs,
the HSource LRU behaves, and a full submit queue rejects loudly.
"""

import threading

import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import distances
from repro.core.engine import (
    HistogramEngine,
    LikelihoodQuery,
    RegionQuery,
    SlidingWindowQuery,
)
from repro.serve import AnalyticsService, ServiceOverloaded


@pytest.fixture()
def store(rng):
    return {i: rng.integers(0, 256, (32, 24), dtype=np.uint8)
            for i in range(6)}


def _probed_engine(**kw):
    """Engine + a counter incremented on every H computation."""
    eng = HistogramEngine(8, backend="jnp", **kw)
    calls = []
    orig = eng.compute

    def probe(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    eng.compute = probe
    return eng, calls


RECTS = np.array([2, 2, 10, 10])


def test_same_frame_queries_coalesce_into_one_run(store):
    eng, calls = _probed_engine()
    svc = AnalyticsService(eng, store)
    res = svc.process([
        (0, RegionQuery(RECTS)),
        (0, SlidingWindowQuery((8, 8), 4)),
        (0, LikelihoodQuery(np.ones(8, np.float32), (8, 8),
                            distances.intersection, 4)),
        (1, RegionQuery(RECTS)),
    ])
    assert len(calls) == 2              # frame 0 ONE run for 3 queries
    assert svc.stats.engine_runs == 2
    assert svc.stats.coalesced == 2
    # bit-exact vs direct engine runs
    direct0 = eng.run(store[0], [RegionQuery(RECTS),
                                 SlidingWindowQuery((8, 8), 4)])
    np.testing.assert_array_equal(np.asarray(res[0]),
                                  np.asarray(direct0.results[0]))
    np.testing.assert_array_equal(np.asarray(res[1]),
                                  np.asarray(direct0.results[1]))
    direct1 = eng.run(store[1], [RegionQuery(RECTS)])
    np.testing.assert_array_equal(np.asarray(res[3]),
                                  np.asarray(direct1.results[0]))


def test_cache_hit_skips_compute_and_lru_evicts(store):
    eng, calls = _probed_engine()
    svc = AnalyticsService(eng, store, cache_size=2)
    svc.process([(0, RegionQuery(RECTS))])
    svc.process([(0, RegionQuery(RECTS))])          # hit
    assert len(calls) == 1
    assert svc.stats.cache_hits == 1
    svc.process([(1, RegionQuery(RECTS))])
    svc.process([(2, RegionQuery(RECTS))])          # evicts 0 (LRU)
    assert svc.cached_frames == (1, 2)
    svc.process([(0, RegionQuery(RECTS))])          # miss again
    assert len(calls) == 4
    # hit results identical to miss results
    a = svc.process([(2, RegionQuery(RECTS))])[0]   # hit
    b = eng.run(store[2], [RegionQuery(RECTS)]).results[0]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cache_disabled(store):
    eng, calls = _probed_engine()
    svc = AnalyticsService(eng, store, cache_size=0)
    svc.process([(0, RegionQuery(RECTS))])
    svc.process([(0, RegionQuery(RECTS))])
    assert len(calls) == 2 and svc.cached_frames == ()
    assert svc.stats.cache_hits == 0


def test_banded_engine_cache_hits_replay_the_stream(store):
    """A banded plan caches the replayable BandedH; hits re-stream with
    the multi-query corner-row union, results bit-exact vs dense."""
    budget = 4 * 8 * 24 * 8             # 8-row bands for 32x24 @ 8 bins
    eng, calls = _probed_engine(memory_budget_bytes=budget)
    svc = AnalyticsService(eng, store, cache_size=2)
    # stride 4 keeps the corner-row union above the query-fusion bound
    # (h // 4 rows) so the planner stays banded rather than fusing.
    qs = [RegionQuery(RECTS), SlidingWindowQuery((8, 8), 4)]
    first = svc.process([(3, q) for q in qs])
    assert eng.last_plan.representation == "banded"
    again = svc.process([(3, q) for q in qs])       # cache hit, 2 queries
    assert len(calls) == 1
    dense = HistogramEngine(8, backend="jnp").run(store[3], qs).results
    for got in (first, again):
        for g, want in zip(got, dense):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(want))


def test_threaded_submit_and_futures(store):
    eng, calls = _probed_engine()
    with AnalyticsService(eng, store, cache_size=4) as svc:
        futs = [svc.submit(i % 2, RegionQuery(RECTS), block=True)
                for i in range(10)]
        outs = [f.result(timeout=60) for f in futs]
    assert len(outs) == 10
    assert len(calls) <= 2              # 2 distinct frames
    want = eng.run(store[0], [RegionQuery(RECTS)]).results[0]
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(want))
    snap = svc.stats.snapshot()
    assert snap["completed"] == 10
    assert snap["requests"] == 10
    assert snap["requests_per_s"] > 0
    assert snap["latency_p95_s"] >= snap["latency_p50_s"] >= 0


def test_backpressure_rejects_when_queue_full(store):
    eng, _ = _probed_engine()
    svc = AnalyticsService(eng, store, max_pending=2)
    # not started: submit refuses outright
    with pytest.raises(RuntimeError, match="not started"):
        svc.submit(0, RegionQuery(RECTS))
    # fill the queue while the worker is blocked on a slow resolver
    gate = threading.Event()

    def slow_resolve(ref):
        gate.wait(timeout=60)
        return store[ref]

    svc2 = AnalyticsService(eng, slow_resolve, max_pending=2,
                            max_coalesce=1).start()
    try:
        futs = [svc2.submit(0, RegionQuery(RECTS))]   # worker takes this
        import time
        deadline = time.time() + 5
        overloaded = False
        while time.time() < deadline and not overloaded:
            try:
                futs.append(svc2.submit(1, RegionQuery(RECTS)))
            except ServiceOverloaded:
                overloaded = True
        assert overloaded
        assert svc2.stats.rejected >= 1
    finally:
        gate.set()
        svc2.close()
    for f in futs:
        f.result(timeout=60)


def test_close_fails_requests_that_raced_past_the_worker(store):
    """A submit landing on the queue after the worker's final drain must
    not hang forever — close() fails its future."""
    from repro.serve.service import _Pending
    from concurrent.futures import Future

    eng, _ = _probed_engine()
    svc = AnalyticsService(eng, store).start()
    svc.close()
    p = _Pending(0, RegionQuery(RECTS), 0.0, Future())
    svc._queue.put_nowait(p)             # the race, made deterministic
    svc.close()
    with pytest.raises(RuntimeError, match="closed before"):
        p.future.result(timeout=1)


def test_worker_failure_lands_on_the_future(store):
    eng, _ = _probed_engine()

    def resolve(ref):
        raise KeyError(f"no frame {ref}")

    with AnalyticsService(eng, resolve) as svc:
        fut = svc.submit(99, RegionQuery(RECTS), block=True)
        with pytest.raises(KeyError):
            fut.result(timeout=60)


def test_bad_config_rejected(store):
    eng, _ = _probed_engine()
    for kw in (dict(cache_size=-1), dict(max_pending=0),
               dict(max_coalesce=0), dict(cache_bytes=-1)):
        with pytest.raises(ValueError):
            AnalyticsService(eng, store, **kw)


# ---------------------------------------------------------------------------
# video-delta chaining + byte-aware cache bound (ISSUE 9)
# ---------------------------------------------------------------------------
def _video_store(rng, n=5, h=32, w=24):
    """Low-motion stream keyed by frame number: each frame rewrites a
    few rows of its predecessor."""
    frames = [rng.integers(0, 256, (h, w), dtype=np.uint8)]
    for _ in range(n - 1):
        nxt = frames[-1].copy()
        r = int(rng.integers(0, h - 3))
        nxt[r:r + 3] = rng.integers(0, 256, (3, w), dtype=np.uint8)
        frames.append(nxt)
    return {i: f for i, f in enumerate(frames)}


# 6 rects at distinct rows -> 12 corner rows > 32/4, so plans stay
# dense (a fused plan never stores H and cannot seed the chain).
DENSE_RECTS = np.array([[3 * i, 2, 3 * i + 1, 10] for i in range(6)])


def test_video_chain_updates_cached_h(rng):
    store = _video_store(rng)
    eng, calls = _probed_engine()
    svc = AnalyticsService(eng, store)
    res = svc.process([(i, RegionQuery(DENSE_RECTS))
                       for i in range(len(store))])
    snap = svc.stats.snapshot()
    # frame 0 recomputes; every successor updates its predecessor's H
    assert snap["recomputed"] == 1
    assert snap["updated"] == len(store) - 1
    assert snap["update_ratio"] == pytest.approx(
        (len(store) - 1) / len(store))
    assert len(calls) == 1              # compute() ran once; rest updated
    # bit-exact vs fresh engine runs per frame
    for i in range(len(store)):
        want = HistogramEngine(8, backend="jnp").run(
            store[i], [RegionQuery(DENSE_RECTS)]).results[0]
        np.testing.assert_array_equal(np.asarray(res[i]),
                                      np.asarray(want))


def test_video_chain_disabled_by_predecessor_resolver(rng):
    store = _video_store(rng, n=3)
    eng, _ = _probed_engine()
    svc = AnalyticsService(eng, store, predecessor=lambda ref: None)
    svc.process([(i, RegionQuery(DENSE_RECTS)) for i in range(3)])
    snap = svc.stats.snapshot()
    assert snap["updated"] == 0 and snap["recomputed"] == 3


def test_video_chain_survives_missing_predecessor_frame(rng):
    """Predecessor H cached but its frame gone from the store: the miss
    recomputes instead of failing."""
    store = _video_store(rng, n=2)
    eng, _ = _probed_engine()
    svc = AnalyticsService(eng, store)
    svc.process([(0, RegionQuery(DENSE_RECTS))])
    del store[0]
    out = svc.process([(1, RegionQuery(DENSE_RECTS))])
    snap = svc.stats.snapshot()
    assert snap["updated"] == 0 and snap["recomputed"] == 2
    want = HistogramEngine(8, backend="jnp").run(
        svc._resolve(1), [RegionQuery(DENSE_RECTS)]).results[0]
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(want))


def test_cache_bytes_bound_evicts_by_size(rng):
    store = _video_store(rng)
    one = 4 * 8 * 32 * 24               # dense H bytes per frame
    eng, _ = _probed_engine()
    svc = AnalyticsService(eng, store, cache_bytes=2 * one)
    svc.process([(i, RegionQuery(DENSE_RECTS)) for i in range(5)])
    assert svc.cached_frames == (3, 4)  # LRU-evicted down to 2 entries
    # an entry alone over the bound cannot stay cached
    svc2 = AnalyticsService(eng, store, cache_bytes=one - 1)
    svc2.process([(0, RegionQuery(DENSE_RECTS))])
    assert svc2.cached_frames == ()


def test_snapshot_counts_hits_beside_update_split(rng):
    store = _video_store(rng, n=2)
    eng, _ = _probed_engine()
    svc = AnalyticsService(eng, store)
    svc.process([(0, RegionQuery(DENSE_RECTS))])
    svc.process([(0, RegionQuery(DENSE_RECTS))])    # cache hit
    svc.process([(1, RegionQuery(DENSE_RECTS))])    # chained update
    snap = svc.stats.snapshot()
    assert snap["hit"] == 1 == snap["cache_hits"]
    assert snap["recomputed"] == 1 and snap["updated"] == 1


def test_latency_samples_stay_bounded():
    """A long-running service keeps the latencies of its most recent
    requests only; the counters still count every request."""
    from repro.serve.service import LATENCY_WINDOW, ServiceStats

    stats = ServiceStats()
    for i in range(LATENCY_WINDOW + 10):
        stats.observe(1e-3 * i)
    assert len(stats.latencies_s) == LATENCY_WINDOW
    snap = stats.snapshot()
    assert snap["completed"] == LATENCY_WINDOW + 10
    # the 10 oldest samples are gone: the median is over 10 .. 4105 ms
    assert snap["latency_p50_s"] == pytest.approx(
        1e-3 * (10 + (LATENCY_WINDOW - 1) // 2))


# ---------------------------------------------------------------------------
# DistributedAnalyticsService (mesh-scale serving; 8-device runs live in
# test_distributed.py's subprocess tests)
# ---------------------------------------------------------------------------
def _dist_factory():
    from repro.serve import sharded_engine_factory

    return sharded_engine_factory(8, backend="jnp")


def test_distributed_service_parity_and_chain_pinning(rng):
    """Routed traffic is bit-exact vs a single service on the same trace,
    and a PR 9 video chain routes to ONE replica so every incremental
    update stays local."""
    from repro.serve import DistributedAnalyticsService

    store = _video_store(rng)
    trace = [(i, RegionQuery(DENSE_RECTS)) for i in range(5)]
    trace += [(2, RegionQuery(DENSE_RECTS)), (4, SlidingWindowQuery((8, 8), 4))]
    dist = DistributedAnalyticsService(_dist_factory(), store, num_replicas=3)
    single = AnalyticsService(HistogramEngine(8, backend="jnp"), store)
    got = dist.process(list(trace))
    want = single.process(list(trace))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    routes = [dist.replica_for(i) for i in range(5)]
    assert len(set(routes)) == 1
    snap = dist.snapshot()
    assert snap["requests"] == len(trace)
    assert snap["num_replicas"] == 3 and len(snap["replicas"]) == 3
    # the whole chain updated on one replica; the others ran nothing
    per_updated = [p["updated"] for p in snap["replicas"]]
    assert sum(per_updated) == 4
    assert sum(1 for u in per_updated if u) == 1


def test_distributed_routing_is_deterministic_across_instances(rng):
    """Consistent hashing: two independently built services route every
    ref identically (no salted/process-local hashing)."""
    from repro.serve import DistributedAnalyticsService

    store = _video_store(rng)
    kw = dict(num_replicas=4, predecessor=lambda r: None)
    a = DistributedAnalyticsService(_dist_factory(), store, **kw)
    b = DistributedAnalyticsService(_dist_factory(), store, **kw)
    refs = list(range(32)) + ["cam0/17", "cam1/17"]
    assert [a.replica_for(r) for r in refs] == [b.replica_for(r) for r in refs]
    # and the ring spreads refs over more than one replica
    assert len({a.replica_for(r) for r in refs}) > 1


def test_replica_workers_are_named_by_index(rng):
    from repro.serve import DistributedAnalyticsService

    with DistributedAnalyticsService(_dist_factory(), _video_store(rng),
                                     num_replicas=3) as dist:
        names = [r._worker.name for r in dist.replicas]
    assert names == [f"analytics-service-{i}" for i in range(3)]


def test_distributed_aggregate_backpressure(rng):
    """max_pending bounds TOTAL outstanding submits across replicas."""
    from repro.serve import DistributedAnalyticsService, ServiceOverloaded

    gate = threading.Event()
    frame = rng.integers(0, 256, (32, 24), dtype=np.uint8)

    def resolve(ref):
        gate.wait(timeout=10)
        return frame

    svc = DistributedAnalyticsService(
        _dist_factory(), resolve, num_replicas=2, max_pending=3,
        predecessor=lambda r: None)
    q = RegionQuery(RECTS)
    with svc:
        futs = [svc.submit(i, q) for i in range(3)]
        with pytest.raises(ServiceOverloaded):
            svc.submit(99, q)
        gate.set()
        outs = [f.result(timeout=30) for f in futs]
    assert all(o is not None for o in outs)
    snap = svc.snapshot()
    assert snap["rejected"] == 1 and snap["completed"] == 3
    # the in-flight window drained back to zero after the futures resolved
    assert svc._inflight == 0


def test_distributed_aggregate_cache_bytes_split(rng):
    """The aggregate byte budget splits across replicas, so the total
    cache residency stays bounded no matter how traffic skews."""
    from repro.serve import DistributedAnalyticsService

    store = _video_store(rng)
    one = 4 * 8 * 32 * 24               # dense H bytes per frame
    svc = DistributedAnalyticsService(
        _dist_factory(), store, num_replicas=2, cache_bytes=2 * one,
        predecessor=lambda r: None)
    svc.process([(i, RegionQuery(DENSE_RECTS)) for i in range(5)])
    assert all(r.cache_bytes == one for r in svc.replicas)
    cached = sum(len(c) for c in svc.cached_frames)
    assert cached <= 2                  # one H per replica fits the split


def test_sharded_h_nbytes_tracks_storage_dtype():
    """Satellite: ShardedH.nbytes reports the real array footprint (the
    inherited planner estimate assumed 4-byte elements, so byte-aware
    cache eviction mis-charged sharded sources)."""
    import jax.numpy as jnp

    from repro.core.hsource import ShardedH

    mesh = make_mesh((1,), ("model",))
    f32 = ShardedH(jnp.zeros((8, 16, 12), jnp.float32), mesh, kind="bin")
    assert f32.nbytes == 8 * 16 * 12 * 4
    u16 = ShardedH(jnp.zeros((8, 16, 12), jnp.uint16), mesh, kind="bin")
    assert u16.nbytes == 8 * 16 * 12 * 2
