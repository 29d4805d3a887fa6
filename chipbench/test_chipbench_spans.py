"""The program's spans: every name the reduction reads is emitted, they
nest as the readers assume, and the reduction and the readers give
known numbers."""

import glob
import os
import types

import numpy as np
import pytest

from chipbench import cells, spans, trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "program_spans.xplane.pb")

# 6 rects at distinct rows: 12 corner rows of 32, so the plan stays dense
# and a chained frame updates its predecessor's H.
RECTS = np.array([[3 * i, 2, 3 * i + 1, 10] for i in range(6)])


def _chains(rng, cameras=8, n=4, h=32, w=24):
    """Per camera a low-motion stream keyed (camera, t)."""
    store = {}
    for c in range(cameras):
        frame = rng.integers(0, 256, (h, w), dtype=np.uint8)
        for t in range(n):
            store[(c, t)] = frame
            frame = frame.copy()
            r = int(rng.integers(0, h - 3))
            frame[r:r + 3] = rng.integers(0, 256, (3, w), dtype=np.uint8)
    return store


def _prev(ref):
    return (ref[0], ref[1] - 1) if ref[1] > 0 else None


def _drive(svc, cameras, n=4):
    """Each camera's frames in order, one in flight per camera, two
    queries a frame; then the first camera's last frame again (a hit)."""
    from repro.core.engine import RegionQuery, SlidingWindowQuery

    for t in range(n):
        futs = [svc.submit((c, t), q)
                for c in cameras
                for q in (RegionQuery(RECTS), SlidingWindowQuery((8, 8), 8))]
        for f in futs:
            f.result(timeout=60)
    svc.submit((cameras[0], n - 1), RegionQuery(RECTS)).result(timeout=60)


@pytest.fixture(scope="module")
def emitted(tmp_path_factory):
    """The program spans of a profiled one-engine service and a
    two-replica distributed service, each serving chained frames."""
    import jax

    from repro.core.engine import HistogramEngine
    from repro.serve import (AnalyticsService, DistributedAnalyticsService,
                             sharded_engine_factory)

    store = _chains(np.random.default_rng(3))
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        with AnalyticsService(HistogramEngine(8, backend="jnp"), store,
                              predecessor=_prev) as svc:
            _drive(svc, [0, 1])
        with DistributedAnalyticsService(
                sharded_engine_factory(8, backend="jnp"), store,
                num_replicas=2, predecessor=_prev) as dist:
            # a camera routed to each replica
            first = {dist.replica_for((c, 0)): c for c in range(7, -1, -1)}
            _drive(dist, [first[0], first[1]])
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return spans.program_spans(path)


def _inside(child, parents):
    return any(p.line == child.line and p.start <= child.start
               and child.end <= p.end for p in parents)


def test_every_program_span_is_emitted(emitted):
    assert {s.name for s in emitted} == set(spans.PROGRAM_SPANS)


def test_spans_nest_by_thread(emitted):
    by = {n: [s for s in emitted if s.name == n] for n in spans.PROGRAM_SPANS}
    runs = [s for s in by["engine.run"] if spans.is_program_run(s)]
    assert len(runs) == len(by["engine.run"]) >= 8
    for s in emitted:
        if s.name.startswith("engine.") and s.name != "engine.run":
            assert _inside(s, runs), s
    for s in runs + by["service.resolve"]:
        assert _inside(s, by["service.group"]), s
    for s in by["service.group"]:
        assert _inside(s, by["service.batch"]), s
    # the two replicas serve at once, each on a line of its own
    assert len({s.line for s in by["service.batch"]}) >= 2


def test_span_attributes(emitted):
    batches = [s for s in emitted if s.name == "service.batch"]
    assert all(s.stats["size"] >= 1 and s.stats["wait_us"] >= 0
               and s.stats["device"] == -1 for s in batches)
    # 2 services x (2 cameras x 4 frames x 2 queries + 1 hit)
    assert sum(s.stats["size"] for s in batches) == 34
    outcomes = [s.stats["outcome"] for s in emitted
                if s.name == "service.group"]
    assert set(outcomes) == {"recomputed", "updated", "hit"}
    assert outcomes.count("recomputed") == 4     # each camera's first frame
    assert outcomes.count("hit") == 2
    assert all(s.stats["frame"].startswith("(") for s in emitted
               if s.name in ("service.group", "service.resolve"))
    kinds = {s.stats["kind"] for s in emitted if s.name == "engine.query"}
    assert kinds == {"RegionQuery", "SlidingWindowQuery"}
    runs = [s for s in emitted if spans.is_program_run(s)]
    assert {(s.stats["representation"], s.stats["incremental"])
            for s in runs} == {("dense", 0), ("dense", 1)}


def test_engine_split_covers_the_run(emitted):
    split = spans.engine_split(emitted)
    assert split["all"]["runs"] == 16
    assert split["dense+incremental"]["runs"] == 12
    assert split["dense"]["runs"] == 4
    assert "update_ms" in split["dense+incremental"]
    assert "compute_ms" in split["dense"]
    assert 0.5 < split["all"]["covered"] <= 1.0


# -- the reduction, on a synthetic stretch ---------------------------------
def _span(a, b, name, line=1, **stats):
    return spans.Span(a, b, name, stats, line)


def test_program_spans_label_only_unlabelled_gaps():
    # window 0..10 s; device busy 1-2 and 4-7: gaps 0-1, 2-4, 7-10
    dev = {"XLA Modules": [(1.0, 2.0, "jit_a(1)"), (4.0, 7.0, "jit_b(2)")],
           "XLA Ops": []}
    bench = [(0.0, 10.0, "bench.trace"), (7.0, 10.0, "client.wait")]
    prog = [_span(0.0, 1.0, "service.wait"),
            _span(1.0, 4.0, "service.batch", size=2, wait_us=3000.0,
                  device=0),
            _span(2.0, 4.0, "engine.run", representation="dense",
                  incremental=1),
            _span(2.0, 3.0, "engine.update"),
            _span(3.0, 3.5, "engine.query", kind="LikelihoodQuery"),
            _span(3.6, 3.9, "engine.plan", line=2),   # another thread
            _span(7.0, 10.0, "service.wait"),
            _span(12.0, 13.0, "service.wait")]          # after the window
    s = spans.summarize([dev], bench, prog)
    assert s.window_s == 10.0
    # the benchmark's label stays; its unlabelled gaps take the program's
    # most specific span open at their middle (3.0: engine.update)
    assert s.idle_gaps == [["client.wait", 3.0], ["engine.update", 2.0],
                           ["service.wait", 1.0]]
    # what the benchmark alone labels them
    assert [g[0] for g in trace.summarize([dev], bench).idle_gaps] == \
        ["client.wait", "no span", "no span"]
    assert s.spans["service.batch"] == [
        (1.0, 3.0, {"size": 2, "wait_us": 3000.0, "device": 0})]
    assert len(s.spans["service.wait"]) == 2
    assert s.engine["all"] == pytest.approx(
        {"runs": 1, "run_ms": 2000.0, "update_ms": 1000.0,
         "query_ms": 500.0, "self_ms": 500.0, "covered": 0.75})
    t = s.threads["1"]
    assert t["device"] == 0 and t["batches"] == 1 and t["requests"] == 2
    assert t["queue_wait_ms"] == pytest.approx(1.5)
    assert t["wait_s"] == pytest.approx(4.0)


def test_no_window_gives_nothing():
    assert spans.summarize([], [], [_span(0, 1, "service.wait")]) is None


# -- the readers -----------------------------------------------------------
SPANS = {
    "service.batch": [(0.0, 0.1, {"size": 2, "wait_us": 3000.0}),
                      (0.2, 0.1, {"size": 1, "wait_us": 1500.0})],
    "engine.run": [(0.0, 0.05, {"representation": "dense",
                                "incremental": 1}),
                   (0.0, 0.05, {}),                    # the benchmark's
                   (0.2, 0.05, {"representation": "dense",
                                "incremental": 0})],
    "engine.plan": [(0.0, 0.001, {}), (0.2, 0.003, {})],
    "engine.update": [(0.01, 0.004, {})],
    "engine.compute": [(0.21, 0.006, {}), (0.3, 0.002, {})],
    "engine.query": [(0.02, 0.004, {"kind": "RegionQuery"}),
                     (0.03, 0.002, {"kind": "LikelihoodQuery"}),
                     (0.22, 0.004, {"kind": "LikelihoodQuery"})],
}


@pytest.mark.parametrize("metric,value", [
    ("queue_wait_ms.p50", 1.5),         # 4500 us over 3 requests
    ("engine_plan_ms.p50", 2.0),
    ("engine_update_ms.p50", 4.0),
    ("engine_compute_ms.fps", 4.0),
    ("engine_query_ms.p50", 5.0),       # 10 ms of queries, 2 program runs
])
def test_reader_known_number(metric, value):
    run = types.SimpleNamespace(trace=types.SimpleNamespace(spans=SPANS))
    assert cells.reader(metric)(run) == pytest.approx(value)


@pytest.mark.parametrize("metric", [
    "queue_wait_ms.p50", "engine_plan_ms.p50", "engine_update_ms.p50",
    "engine_compute_ms.fps", "engine_query_ms.p50"])
def test_reader_reads_nothing_without_program_spans(metric):
    for tr in (None, types.SimpleNamespace(window_s=5.0),
               types.SimpleNamespace(spans={})):
        assert cells.reader(metric)(types.SimpleNamespace(trace=tr)) is None


# -- a recorded chip trace -------------------------------------------------
def test_reduction_of_a_recorded_live_stretch():
    """One second of a traced ``vga32.live`` run on a TPU v5e: nine frames,
    eight of them chained updates, trimmed to the lines the reductions
    read."""
    s = spans.read(FIXTURE)
    bench = trace.summarize(*trace.events(FIXTURE))
    assert s.window_s == bench.window_s == pytest.approx(1.0, abs=1e-9)
    # the gaps the benchmark leaves unlabelled are the worker waiting for
    # the next frame; the rest keep the benchmark's label
    assert [g[0] for g in bench.idle_gaps[:9]] == ["no span"] * 9
    assert [g[0] for g in s.idle_gaps[:9]] == ["service.wait"] * 9
    assert s.idle_gaps[9] == bench.idle_gaps[9] == \
        ["query.apply", pytest.approx(0.001638823, abs=1e-9)]
    assert s.idle_gaps[0][1] == pytest.approx(0.030166, abs=1e-9)
    assert {n: len(v) for n, v in s.spans.items()} == {
        "service.wait": 8, "service.batch": 9, "service.group": 9,
        "service.resolve": 18, "engine.run": 18, "engine.plan": 9,
        "engine.validate": 9, "engine.update": 8, "engine.compute": 1,
        "engine.query": 18}
    assert s.spans["service.group"][0][2] == {"frame": "(4, 11)",
                                              "outcome": "updated"}
    e = s.engine
    assert e["all"]["runs"] == 9 and e["dense+incremental"]["runs"] == 8
    assert e["all"]["run_ms"] == pytest.approx(92.541707, abs=1e-6)
    assert e["all"]["query_ms"] == pytest.approx(82.092462, abs=1e-6)
    assert e["all"]["covered"] > 0.9998
    run = types.SimpleNamespace(trace=s)
    for metric, value in [("queue_wait_ms.p50", 0.152903),
                          ("engine_plan_ms.p50", 1.700740),
                          ("engine_update_ms.p50", 8.786777),
                          ("engine_compute_ms.fps", 0.369631),
                          ("engine_query_ms.p50", 82.092462)]:
        assert cells.reader(metric)(run) == pytest.approx(value, abs=1e-6)
