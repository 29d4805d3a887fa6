"""End-to-end real-time video analytics driver (the paper's use case),
driven through the plan/execute engine (core/engine.py).

Pipeline per frame (all on-accelerator once the frame is staged):
  1. WF-TiS integral histograms streamed by `HistogramEngine.map_frames`
     — the planner sizes the microbatch (arXiv:1011.0235 adaptive
     batching) and keeps dispatches in flight (paper §4.4 dual-buffering)
  2. multi-target fragments tracker update (paper ref. [13]) riding the
     same engine via `step_on_h` — the frame's integral histogram is
     computed ONCE and shared by every target's O(1) candidate queries
  3. batched likelihood maps (abstract: "feature likelihood maps ... play
     a critical role"): the last `--map-frames` H's are stacked and ONE
     rank-polymorphic `likelihood_map` call scores every window of every
     frame
  4. the large-frame regime (paper §4.6): a frame `--large-scale`x the
     stream size is scored under a memory budget an eighth of its full H
     footprint.  A second engine plans it — `plan.explain()` shows the
     banded representation it picked — and the exact likelihood map is
     computed without the (b, h, w) H ever existing.
  5. serving (`repro/serve`): an `AnalyticsService` over the same engine
     answers a burst of concurrent `(frame, query)` requests — same-frame
     queries coalesce into one engine run, hot frames answer from the
     HSource LRU cache, and the stats line shows the requests/sec the
     front-end adds on top of raw engine throughput.

Every stage goes through ONE entry point (`engine.run` / `map_frames`);
the dense / banded / spilled / sharded representation behind a request
is the planner's choice, not hand-routed (the pre-engine forks survive
as deprecation shims; see README "Migration").

    PYTHONPATH=src python examples/video_analytics.py [--frames 40]
                   [--batch auto|N] [--targets 2] [--large-scale 2]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import distances
from repro.core.engine import HistogramEngine, LikelihoodQuery
from repro.core.region_query import likelihood_map, region_histogram
from repro.core.tracking import FragmentTracker, TrackerConfig
from repro.data import video_frames


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=40)
    ap.add_argument("--hw", type=int, nargs=2, default=(480, 640))
    ap.add_argument("--bins", type=int, default=16)
    ap.add_argument("--batch", default="auto",
                    help='frames per dispatch: "auto" (planner) or an int')
    ap.add_argument("--depth", type=int, default=2,
                    help="dispatches kept in flight (1 = synchronous)")
    ap.add_argument("--targets", type=int, default=2,
                    help="simultaneously tracked targets")
    ap.add_argument("--map-frames", type=int, default=4,
                    help="trailing frames scored by one batched "
                         "likelihood_map call")
    ap.add_argument("--large-scale", type=int, default=2,
                    help="stage-4 frame is this multiple of --hw "
                         "(0 skips the banded large-frame demo)")
    ap.add_argument("--serve-requests", type=int, default=60,
                    help="stage-5 query burst against the AnalyticsService "
                         "(0 skips the serving demo)")
    args = ap.parse_args(argv)
    use_compile_cache()
    h, w = args.hw

    frames = video_frames(h, w, args.frames, seed=3)
    print(f"{args.frames} frames of {h}x{w}, {args.bins} bins, "
          f"batch={args.batch}, depth={args.depth}, "
          f"targets={args.targets}")

    # --- stage 1: one engine plans + streams the integral histograms ------
    engine = HistogramEngine(args.bins, method="wf_tis", backend="auto")

    # --- stage 2: multi-target tracker rides the same engine ---------------
    tracker = FragmentTracker(
        TrackerConfig(num_bins=args.bins, search_radius=10), engine=engine)
    size = 48
    bboxes = np.stack([
        [r, c, r + size - 1, c + size - 1]
        for r, c in zip(
            np.linspace(h // 4, 3 * h // 4 - size, args.targets).astype(int),
            np.linspace(w // 4, 3 * w // 4 - size, args.targets).astype(int))
    ])
    state = tracker.init(jnp.asarray(frames[0]), bboxes)
    target_hists = region_histogram(
        engine.compute_dense(jnp.asarray(frames[0])), state["bbox"])

    t0 = time.perf_counter()
    boxes, tail_H = [], []
    if args.batch == "auto":
        stream = engine.map_frames(frames, depth=args.depth)
    else:
        # explicit microbatch: bypass the planner's choice for comparison
        # (map_frames is eager — it plans off the first frame — so only
        # ONE of the two streams may ever be constructed)
        from repro.core.integral_histogram import IntegralHistogram

        stream = IntegralHistogram(
            num_bins=args.bins, method="wf_tis", backend="auto"
        ).map_frames(frames, batch_size=int(args.batch), depth=args.depth)
    for H in stream:
        state = tracker.step_on_h(state, H)     # H shared across targets
        boxes.append(np.asarray(state["bbox"]))
        tail_H.append(H)
        if len(tail_H) > args.map_frames:
            tail_H.pop(0)
    dt = time.perf_counter() - t0
    if args.batch == "auto" and engine.last_plan is not None:
        print(f"planned microbatch: {engine.last_plan.microbatch} "
              f"frame(s)/dispatch ({engine.last_plan.representation})")

    # --- stage 3: one batched likelihood_map over the trailing frames ----
    Hs = jnp.stack(tail_H)                      # (k, bins, h, w)
    lmap = likelihood_map(Hs, target_hists[0], (size, size),
                          distances.intersection, stride=16)
    jax.block_until_ready(lmap)

    print(f"pipeline: {args.frames/dt:.2f} frames/sec "
          f"({dt/args.frames*1e3:.1f} ms/frame) on {jax.devices()[0]}")
    for t in range(args.targets):
        print(f"track[{t}]: start {boxes[0][t][:2]} -> end {boxes[-1][t][:2]}")
    peak = tuple(
        int(i) for i in np.unravel_index(int(jnp.argmax(lmap[-1])),
                                         lmap.shape[1:]))
    print(f"likelihood maps {lmap.shape} (batched over {lmap.shape[0]} "
          f"frames), last-frame peak={float(lmap[-1].max()):.3f} at {peak}")

    # --- stage 4: the large-frame regime, planned under a budget ----------
    if args.large_scale:
        big_h, big_w = h * args.large_scale, w * args.large_scale
        big = np.tile(frames[-1], (args.large_scale, args.large_scale))
        full_bytes = 4 * args.bins * big_h * big_w
        budget = full_bytes // 8
        big_engine = HistogramEngine(args.bins, method="wf_tis",
                                     backend="auto",
                                     memory_budget_bytes=budget)
        t0 = time.perf_counter()
        out = big_engine.run(big, [LikelihoodQuery(
            target_hists[0], (size, size), distances.intersection,
            stride=16)])
        blmap = jax.block_until_ready(out.results[0])
        dt = time.perf_counter() - t0
        print(f"\nlarge-frame plan ({big_h}x{big_w}, budget "
              f"{budget / 2**20:.0f} MB vs full H "
              f"{full_bytes / 2**20:.0f} MB):")
        print(out.plan.explain())
        print(f"banded likelihood map {tuple(blmap.shape)} in {dt:.2f}s — "
              "full H never materialized")

    # --- stage 5: serving front-end over the engine -----------------------
    if args.serve_requests:
        from repro.core.engine import RegionQuery
        from repro.serve import AnalyticsService

        store = {i: f for i, f in enumerate(frames)}
        svc = AnalyticsService(engine, store, cache_size=8,
                               max_pending=args.serve_requests)
        rng = np.random.default_rng(11)
        burst = []
        hot = min(4, args.frames)
        for i in range(args.serve_requests):
            # hot-set traffic: most queries land on the newest `hot` frames
            ref = (args.frames - 1 - int(rng.integers(0, hot))
                   if rng.random() < 0.8
                   else int(rng.integers(0, args.frames)))
            if i % 2:
                burst.append((ref, RegionQuery(state["bbox"])))
            else:
                burst.append((ref, LikelihoodQuery(
                    target_hists[0], (size, size), distances.intersection,
                    stride=32)))
        t0 = time.perf_counter()
        with svc:
            # two waves: the first computes (coalescing same-frame
            # queries), the second mostly answers from the HSource cache
            half = len(burst) // 2
            for wave in (burst[:half], burst[half:]):
                futs = [svc.submit(ref, q, block=True) for ref, q in wave]
                for f in futs:
                    f.result()
        dt = time.perf_counter() - t0
        s = svc.stats.snapshot()
        print(f"\nserving: {len(burst)} concurrent requests in {dt:.2f}s "
              f"({len(burst) / dt:.1f} req/s)")
        print(f"  engine runs {s['engine_runs']} "
              f"(coalesced {s['coalesced']}, "
              f"cache hit rate {100 * s['cache_hit_rate']:.0f}%), "
              f"p95 latency {1e3 * s['latency_p95_s']:.1f} ms")


if __name__ == "__main__":
    main()
