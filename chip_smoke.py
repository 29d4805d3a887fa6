"""Chip smoke check: the integral-histogram engine and the analytics
service, run once on a TPU through their normal entry points.

    python3 chip_smoke.py [--seed N]          # one chip
    python3 chip_smoke.py --chips 4 [--seed N]  # the multi-chip paths only

One chip (the default) runs three phases:

  (a) the Pallas kernels ``wf_tis``, ``cw_tis``, ``fused_rows`` and
      ``delta_apply`` on a 4-frame 480x640 uint8 stack with 32 bins, and
      on a 1024x8192 band with 128 bins and a non-zero carry-in;
  (b) ``AnalyticsService(HistogramEngine(32))`` answering about 50
      region, sliding-window and likelihood requests over 16 frames of
      480x640 video, including a low-motion successor (incremental plan)
      and a small row union (fused plan);
  (c) ``HistogramEngine(128, memory_budget_bytes=512 MiB)`` on a
      1024x8192 frame (banded plan).

``--chips 4`` runs only the two multi-chip paths, each compared with the
same requests on device 0 alone: the bin-sharded engine on a (1, 4) mesh
at 1024x8192x128, and ``DistributedAnalyticsService`` with four
one-chip replicas on a (4, 1) mesh serving chained video streams.

Every input is generated from ``--seed``.  Every answer is compared
bit-exact with ``kernels/ref.py`` or with a plain integer prefix sum and
four-corner difference.  Every plan must be ``pallas`` with
``interpret=False``.  Any failure raises, so the exit code is non-zero
and the JSON line is not printed.  Off a TPU the script exits 1 before
any work.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# references (host NumPy, or plain integer prefix sums on the device)
# ---------------------------------------------------------------------------
def bin_ids(frames, bins: int):
    """Bin index of each uint8 pixel (value_range 256)."""
    import numpy as np

    return (frames.astype(np.int64) * bins) // 256


def host_ih(frame, bins: int):
    """(bins, h, w) int64 integral histogram by NumPy prefix sums."""
    import numpy as np

    idx = bin_ids(frame, bins)
    q = idx[None] == np.arange(bins)[:, None, None]
    return q.cumsum(axis=1, dtype=np.int64).cumsum(axis=2)


def four_corner(H, r0, c0, r1, c1):
    """Inclusive-rectangle histogram from an integral histogram H."""
    out = H[:, r1, c1].copy()
    if r0 > 0:
        out -= H[:, r0 - 1, c1]
    if c0 > 0:
        out -= H[:, r1, c0 - 1]
    if r0 > 0 and c0 > 0:
        out += H[:, r0 - 1, c0 - 1]
    return out


def host_windows(H, window, stride):
    """(n_r, n_c, bins) histograms of every window, four-corner."""
    import numpy as np

    b, h, w = H.shape
    wh, ww = window
    n_r, n_c = (h - wh) // stride + 1, (w - ww) // stride + 1
    P = np.zeros((b, h + 1, w + 1), np.int64)
    P[:, 1:, 1:] = H
    r0 = np.arange(n_r) * stride
    c0 = np.arange(n_c) * stride
    r1, c1 = r0 + wh, c0 + ww
    out = (P[:, r1][:, :, c1] - P[:, r0][:, :, c1]
           - P[:, r1][:, :, c0] + P[:, r0][:, :, c0])
    return np.moveaxis(out, 0, -1)


def direct_region(frame, bins, r0, c0, r1, c1):
    """Histogram of an inclusive rectangle by counting its pixels."""
    import numpy as np

    return np.bincount(bin_ids(frame[r0:r1 + 1, c0:c1 + 1], bins).ravel(),
                       minlength=bins)


@functools.lru_cache(maxsize=None)
def _band_mismatch_fn(nb: int):
    """Jitted count of entries where ``nb`` bins of H from ``b0`` on
    differ from the plain int32 prefix sum of the band plus its carry-in
    and a delta."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(H, idx, carry, delta, b0):
        def take(x):
            return jax.lax.dynamic_slice_in_dim(x, b0, nb, axis=0)

        bins = b0 + jnp.arange(nb, dtype=jnp.int32)
        q = (idx[None] == bins[:, None, None]).astype(jnp.int32)
        ref = jnp.cumsum(jnp.cumsum(q, axis=-1), axis=-2)
        ref = ref + (take(carry) + take(delta))[:, None, :]
        return jnp.sum(take(H) != ref.astype(jnp.float32))

    return fn


def band_mismatches(H, idx, carry, delta, chunk: int = 16) -> int:
    """Bit-exact check of a (bins, h, w) band H, chunked over bins so the
    reference never holds more than ``chunk`` bins at once."""
    fn = _band_mismatch_fn(chunk)
    return sum(int(fn(H, idx, carry, delta, b0))
               for b0 in range(0, H.shape[0], chunk))


def timed(label: str, fn):
    """Run ``fn`` twice (cold: compile + run, then warm); log both wall
    times; return the warm result."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    cold = time.perf_counter() - t0
    del out
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    warm = time.perf_counter() - t0
    log(f"  {label}: cold {cold:.3f} s (compile + run), warm {warm:.6f} s")
    return out


# ---------------------------------------------------------------------------
# plan recording: every engine run must take the Pallas kernels
# ---------------------------------------------------------------------------
class PlanLog:
    """Wraps ``engine.run`` so every executed plan is recorded and held
    to ``backend == "pallas"`` with ``interpret=False``."""

    def __init__(self):
        self.plans = []

    def watch(self, engine):
        run = engine.run

        def recorded(*args, **kwargs):
            out = run(*args, **kwargs)
            p = out.plan
            check(p.backend == "pallas" and not p.spec.interpret,
                  f"plan ran {p.backend!r} interpret={p.spec.interpret}")
            self.plans.append(p)
            return out

        engine.run = recorded
        return engine

    def summary(self) -> str:
        kinds = [p.representation + ("+incremental" if p.incremental else "")
                 for p in self.plans]
        return ", ".join(f"{k} x{kinds.count(k)}" for k in sorted(set(kinds)))


# ---------------------------------------------------------------------------
# (a) kernels
# ---------------------------------------------------------------------------
def phase_kernels_stack(rng, n=4, h=480, w=640, bins=32):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.ref import integral_histogram_ref

    log(f"(a) kernels on a {n}x{h}x{w} uint8 stack, {bins} bins")
    frames = jnp.asarray(rng.integers(0, 256, (n, h, w), dtype=np.uint8))
    ref = jax.jit(jax.vmap(
        lambda f: integral_histogram_ref(f, bins, dtype=jnp.int32)))(frames)
    ref_f = ref.astype(jnp.float32)
    for method in ("wf_tis", "cw_tis"):
        H = timed(method, lambda m=method: ops.integral_histogram(
            frames, bins, method=m, backend="pallas"))
        check(H.shape == (n, bins, h, w), f"{method} shape {H.shape}")
        check(bool(jnp.array_equal(H, ref_f)), f"{method} != ref")
        del H
    rows = np.sort(rng.choice(h, 40, replace=False))
    R = timed("fused_rows", lambda: ops.fused_corner_rows(
        frames, bins, rows, backend="pallas"))
    check(bool(jnp.array_equal(R, ref_f[:, :, rows, :])), "fused_rows != ref")
    delta = jnp.asarray(rng.integers(-1000, 1000, (n, bins, w)), jnp.int32)
    out = timed("delta_apply", lambda: ops.delta_apply(
        ref_f, delta.astype(jnp.float32), backend="pallas"))
    check(bool(jnp.array_equal(
        out, (ref + delta[:, :, None, :]).astype(jnp.float32))),
        "delta_apply != ref + delta")
    log("  stack: all four kernels bit-exact")


def phase_kernels_band(rng, h=1024, w=8192, bins=128, above=512):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    log(f"(a) kernels on a {h}x{w} band, {bins} bins, carry-in from "
        f"{above} rows above")
    top = rng.integers(0, 256, (above, w), dtype=np.uint8)
    band = rng.integers(0, 256, (h, w), dtype=np.uint8)
    # The carry-in is the bottom row of H over the rows above the band:
    # per column, the count of each bin in that column, summed leftwards.
    col = np.arange(w)[None, :]
    counts = np.bincount((bin_ids(top, bins) * w + col).ravel(),
                         minlength=bins * w).reshape(bins, w)
    carry = np.cumsum(counts, axis=1).astype(np.int32)
    check(int(carry.max()) > 0, "carry-in is zero")
    idx = jnp.asarray(bin_ids(band, bins).astype(np.int32))
    band_d = jnp.asarray(band)
    carry_i = jnp.asarray(carry)
    carry_f = carry_i.astype(jnp.float32)
    zero = jnp.zeros((bins, w), jnp.int32)
    log(f"  H is {bins * h * w * 4 / 1e9:.2f} GB fp32")

    H = None
    for method in ("cw_tis", "wf_tis"):
        H = timed(method, lambda m=method: ops.integral_histogram(
            band_d, bins, method=m, backend="pallas", carry_in=carry_f))
        check(H.shape == (bins, h, w), f"{method} shape {H.shape}")
        bad = band_mismatches(H, idx, carry_i, zero)
        check(bad == 0, f"{method}: {bad} entries differ from the prefix sum")
        if method == "cw_tis":
            del H
    rows = np.sort(rng.choice(h, 64, replace=False))
    R = timed("fused_rows", lambda: ops.fused_corner_rows(
        band_d, bins, rows, backend="pallas", carry_in=carry_f))
    check(bool(jnp.array_equal(R, H[:, rows, :])), "fused_rows != H rows")
    delta = jnp.asarray(rng.integers(-1000, 1000, (bins, w)), jnp.int32)
    out = timed("delta_apply", lambda: ops.delta_apply(
        H, delta.astype(jnp.float32), backend="pallas"))
    del H
    bad = band_mismatches(out, idx, carry_i, delta)
    check(bad == 0, f"delta_apply: {bad} entries differ")
    log("  band: all four kernels bit-exact")


# ---------------------------------------------------------------------------
# request checking shared by (b), (c) and the four-chip service phase
# ---------------------------------------------------------------------------
class Oracle:
    """Host integral histograms of stored frames, built on demand."""

    def __init__(self, store, bins):
        self.store, self.bins, self._H = store, bins, {}

    def H(self, ref):
        if ref not in self._H:
            self._H[ref] = host_ih(self.store[ref], self.bins)
        return self._H[ref]

    def check(self, ref, query, got):
        import jax.numpy as jnp
        import numpy as np

        from repro.core import distances
        from repro.core.engine import (LikelihoodQuery, RegionQuery,
                                       SlidingWindowQuery)

        H = self.H(ref)
        got = np.asarray(got)
        if isinstance(query, RegionQuery):
            want = np.stack([four_corner(H, *r) for r in query.rects])
        elif isinstance(query, SlidingWindowQuery):
            want = host_windows(H, query.window, query.stride)
        elif isinstance(query, LikelihoodQuery):
            # Exact window histograms, scored by the same metric.
            hists = host_windows(H, query.window, query.stride)
            metric = query.metric or distances.intersection
            want = np.asarray(metric(jnp.asarray(hists.astype(np.float32)),
                                     jnp.asarray(query.target)))
        else:
            raise TypeError(type(query))
        check(got.shape == want.shape,
              f"{ref} {type(query).__name__}: shape {got.shape} != "
              f"{want.shape}")
        check(np.array_equal(got, want.astype(got.dtype)),
              f"{ref} {type(query).__name__}: answer differs from the "
              "prefix-sum reference")


def random_rects(rng, h, w, k, max_h=64, max_w=256):
    import numpy as np

    r0 = rng.integers(0, h - max_h, k)
    c0 = rng.integers(0, w - max_w, k)
    r1 = r0 + rng.integers(0, max_h, k)
    c1 = c0 + rng.integers(0, max_w, k)
    return np.stack([r0, c0, r1, c1], axis=1)


def low_motion(rng, frame, band=None):
    """``frame`` with one 16-row band re-drawn (default: starting a third
    of the way down): a fixed camera's next frame, which the engine
    updates incrementally."""
    nxt = frame.copy()
    r0, r1 = band or (frame.shape[0] // 3, frame.shape[0] // 3 + 16)
    nxt[r0:r1] = rng.integers(0, 256, (r1 - r0, frame.shape[1]),
                              dtype=frame.dtype)
    return nxt


def serve_and_check(svc, trace, oracle, label):
    """Submit ``trace`` to a started service, wait on every future (an
    exception in any of them raises here), check every answer."""
    t0 = time.perf_counter()
    futures = [svc.submit(ref, q, block=True) for ref, q in trace]
    results = [f.result(timeout=600) for f in futures]
    wall = time.perf_counter() - t0
    for (ref, q), got in zip(trace, results):
        oracle.check(ref, q, got)
    log(f"  {label}: {len(trace)} requests answered in {wall:.3f} s, "
        "all bit-exact")
    return results


# ---------------------------------------------------------------------------
# (b) the analytics service end to end
# ---------------------------------------------------------------------------
def phase_service(rng, seed, h=480, w=640, bins=32, num_frames=16):
    import numpy as np

    from repro.core.engine import (HistogramEngine, LikelihoodQuery,
                                   RegionQuery, SlidingWindowQuery)
    from repro.data import video_frames
    from repro.serve import AnalyticsService

    log(f"(b) AnalyticsService over {num_frames} frames of {h}x{w}, "
        f"{bins} bins")
    frames = video_frames(h, w, num_frames, seed=seed)
    frames[9] = low_motion(rng, frames[8])       # chained successor of 8
    store = {i: frames[i] for i in range(num_frames)}
    oracle = Oracle(store, bins)
    target = four_corner(oracle.H(0), 100, 100, 147, 147).astype(np.float32)

    def region(k=3):
        return RegionQuery(random_rects(rng, h, w, k))

    dense = SlidingWindowQuery((32, 32), 3)        # > h/4 corner rows
    like = LikelihoodQuery(target, (48, 48), stride=8)
    trace = []
    for i in range(4):                             # small unions: fused
        trace += [(i, region()), (i, region())]
    for i in range(4, 8):
        trace += [(i, dense), (i, region())]
    trace += [(8, dense), (8, like), (9, dense), (9, region())]
    for i in range(10, 16):
        trace += [(i, like), (i, region())]
    for i in (15, 14, 13, 12, 11, 9, 8, 7, 6, 5, 3, 1):  # hits, re-runs
        trace += [(i, region())]
    trace += [(9, like), (10, dense), (13, like), (14, dense)]

    plans = PlanLog()
    engine = plans.watch(HistogramEngine(bins, backend="auto"))
    with AnalyticsService(engine, store) as svc:
        serve_and_check(svc, trace, oracle, "service")
        snap = svc.stats.snapshot()
    log(f"  plans: {plans.summary()}")
    log(f"  stats: engine_runs={snap['engine_runs']} "
        f"updated={snap['updated']} hits={snap['cache_hits']} "
        f"coalesced={snap['coalesced']}")
    reps = {p.representation for p in plans.plans}
    check({"fused", "dense"} <= reps, f"representations ran: {reps}")
    check(any(p.incremental for p in plans.plans),
          "no incremental plan ran")


# ---------------------------------------------------------------------------
# (c) the banded plan on a large frame
# ---------------------------------------------------------------------------
def large_frame_queries(rng, h, w):
    from repro.core.engine import RegionQuery, SlidingWindowQuery

    # 200 rectangles: a corner-row union above h/4, so the request is
    # not fused, plus the whole frame and a coarse window grid.
    rects = random_rects(rng, h, w, 200)
    rects[0] = (0, 0, h - 1, w - 1)
    return [RegionQuery(rects), SlidingWindowQuery((128, 1024), 128)]


def check_large(frame, bins, queries, results):
    import numpy as np

    rq, sw = queries
    want = np.stack([direct_region(frame, bins, *r) for r in rq.rects])
    check(np.array_equal(np.asarray(results[0]), want.astype(np.float32)),
          "region answers differ from direct counts")
    # 128x128 block histograms; each window sums 1 x 8 blocks
    h, w = frame.shape
    blk = bin_ids(frame, bins).reshape(h // 128, 128, w // 128, 128)
    ids = (np.arange(h // 128)[:, None, None, None] * (w // 128)
           + np.arange(w // 128)[None, None, :, None]) * bins + blk
    B = np.bincount(ids.ravel(), minlength=(h // 128) * (w // 128) * bins)
    B = B.reshape(h // 128, w // 128, bins)
    n_c = (w - 1024) // 128 + 1
    want = np.stack([B[:, j:j + 8].sum(axis=1) for j in range(n_c)], axis=1)
    check(np.array_equal(np.asarray(results[1]), want.astype(np.float32)),
          "window answers differ from direct counts")


def phase_banded(rng, h=1024, w=8192, bins=128, budget=512 << 20):
    from repro.core.engine import HistogramEngine

    log(f"(c) banded engine on a {h}x{w} frame, {bins} bins, "
        f"{budget >> 20} MiB budget")
    frame = rng.integers(0, 256, (h, w), dtype="uint8")
    queries = large_frame_queries(rng, h, w)
    plans = PlanLog()
    engine = plans.watch(
        HistogramEngine(bins, memory_budget_bytes=budget))
    t0 = time.perf_counter()
    out = engine.run(frame, queries)
    wall = time.perf_counter() - t0
    log(f"  plan: {out.plan.representation}, "
        f"{out.plan.band_plan.num_bands} bands x "
        f"{out.plan.band_plan.band_h} rows; run {wall:.3f} s")
    check(out.plan.representation == "banded",
          f"plan {out.plan.representation}")
    check_large(frame, bins, queries, out.results)
    log("  banded: answers bit-exact")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def phase_bin_sharded(rng, devices, h=1024, w=8192, bins=128):
    import jax
    import numpy as np

    from repro.compat import make_mesh
    from repro.core.engine import HistogramEngine

    log(f"(4a) bin-sharded engine on a (1, {len(devices)}) mesh, "
        f"{h}x{w}, {bins} bins")
    frame = rng.integers(0, 256, (h, w), dtype="uint8")
    queries = large_frame_queries(rng, h, w)
    mesh = make_mesh((1, len(devices)), ("data", "model"), devices=devices)
    plans = PlanLog()
    engine = plans.watch(HistogramEngine(bins, mesh=mesh))
    t0 = time.perf_counter()
    out = engine.run(frame, queries)
    jax.block_until_ready(out.results)
    log(f"  sharded run {time.perf_counter() - t0:.3f} s")
    p = out.plan
    check(p.representation == "sharded" and p.sharding == "bin",
          f"plan {p.representation}/{p.sharding}")
    placed = out.source.H.sharding.device_set
    check(placed == set(devices), f"H placed on {placed}")
    log(f"  H sharded over {sorted(d.id for d in placed)}")
    check_large(frame, bins, queries, out.results)

    single = plans.watch(HistogramEngine(bins))
    with jax.default_device(devices[0]):
        t0 = time.perf_counter()
        ref = single.run(frame, queries)
        jax.block_until_ready(ref.results)
    log(f"  device-0 run ({ref.plan.representation}) "
        f"{time.perf_counter() - t0:.3f} s")
    for a, b in zip(out.results, ref.results):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              "sharded answers differ from device 0")
    log("  bin-sharded: bit-exact vs device 0 and direct counts")


def phase_replicas(rng, seed, devices, h=480, w=640, bins=32, per_cam=6):
    import numpy as np

    from repro.compat import make_mesh
    from repro.core.engine import (HistogramEngine, RegionQuery,
                                   SlidingWindowQuery)
    from repro.data import video_frames
    from repro.serve import (AnalyticsService, DistributedAnalyticsService,
                             sharded_engine_factory)

    n = len(devices)
    log(f"(4b) DistributedAnalyticsService: {n} one-chip replicas on a "
        f"({n}, 1) mesh, {n * 2} chained {h}x{w} streams")
    store = {}
    for cam in range(n * 2):
        f = video_frames(h, w, 1, seed=seed + 100 + cam)[0]
        for t in range(per_cam):
            store[(cam, t)] = f
            f = low_motion(rng, f, band=(16 * (t + 2), 16 * (t + 3)))
    oracle = Oracle(store, bins)

    def predecessor(ref):
        cam, t = ref
        return (cam, t - 1) if t > 0 else None

    dense = SlidingWindowQuery((32, 32), 3)
    trace = []
    for t in range(per_cam):
        for cam in range(n * 2):
            trace += [((cam, t), dense),
                      ((cam, t), RegionQuery(random_rects(rng, h, w, 3)))]

    mesh = make_mesh((n, 1), ("data", "model"), devices=devices)
    plans = PlanLog()
    factory = sharded_engine_factory(bins)
    dist = DistributedAnalyticsService(
        lambda sub: plans.watch(factory(sub)), store, mesh=mesh,
        replica_axis="data", predecessor=predecessor, cache_size=16,
        max_pending=len(trace))
    with dist:
        got = serve_and_check(dist, trace, oracle, "replicas")
    snap = dist.snapshot()
    # Replica i serves row i of the mesh; make_mesh orders the devices by
    # the chips' physical layout, not by the list it was given.
    placed = {r.device for r in dist.replicas}
    check(placed == set(devices), f"replicas pinned to {placed}")
    for i, (r, dev) in enumerate(zip(dist.replicas, mesh.devices.flat)):
        check(r.device == dev, f"replica {i} pinned to {r.device}")
        for ref in r.cached_frames:
            placed = r._cache[ref].H.devices()
            check(placed == {dev}, f"replica {i} holds {ref} on {placed}")
        s = snap["replicas"][i]
        log(f"  replica {i}: device {dev.id} ({dev.device_kind}), "
            f"requests={s['requests']} engine_runs={s['engine_runs']} "
            f"updated={s['updated']} cached={len(r.cached_frames)}")
    used = sum(1 for s in snap["replicas"] if s["requests"])
    check(used > 1, f"traffic reached {used} replica(s)")
    check(snap["updated"] > 0, "no chained frame updated incrementally")
    for cam in range(n * 2):
        owners = {dist.replica_for((cam, t)) for t in range(per_cam)}
        check(len(owners) == 1, f"stream {cam} split over {owners}")

    single = AnalyticsService(
        plans.watch(HistogramEngine(bins)), store, device=devices[0],
        predecessor=predecessor, cache_size=16)
    with single:
        want = serve_and_check(single, trace, oracle, "device 0 alone")
    for a, b in zip(got, want):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              "replica answers differ from device 0")
    log(f"  plans: {plans.summary()}")
    log("  replicas: bit-exact vs device 0, each on its own chip")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip paths")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    import numpy as np

    from repro.compile_cache import use_compile_cache

    cache = use_compile_cache()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}; seed {args.seed}")
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_kernels_stack(rng)
        phase_kernels_band(rng)
        phase_service(rng, args.seed)
        phase_banded(rng)
    else:
        phase_bin_sharded(rng, devices[:4])
        phase_replicas(rng, args.seed, devices[:4])
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
