"""Serving throughput: `AnalyticsService` requests/sec under query traffic.

Closed-loop load against a synthetic frame store with a skewed (hot-set)
frame popularity — the video-analytics serving shape: many queries land
on few recent frames.  Two sweeps:

  * in-flight depth — how many submits are outstanding before the caller
    blocks on a future (1 = fully synchronous request/response); the
    worker drains whatever accumulated, so depth is also the coalescing
    opportunity;
  * HSource cache on vs off — repeated queries on a hot frame skip the H
    computation entirely on a hit.

Reported: requests/sec, cache hit rate, coalesced share, engine runs per
request, the share of runs served by an incremental video-delta update
(the hot frames here are regenerated independently, so the update ratio
is 0 unless the store is a low-motion stream), p95 latency.
"""

from __future__ import annotations

import collections
import hashlib
import time

import jax
import numpy as np

from benchmarks import common
from benchmarks.common import fmt_table
from repro.compat import make_mesh
from repro.core import distances
from repro.core.engine import HistogramEngine, LikelihoodQuery, RegionQuery
from repro.data import video_frames
from repro.serve import (
    AnalyticsService,
    DistributedAnalyticsService,
    sharded_engine_factory,
)


def _requests(num_requests: int, num_frames: int, hot: int, seed: int):
    """(frame_ref, query) load: 80% of traffic on the `hot` newest frames."""
    rng = np.random.default_rng(seed)
    target = np.ones(16, np.float32)
    reqs = []
    for i in range(num_requests):
        if rng.random() < 0.8:
            ref = int(num_frames - 1 - rng.integers(0, hot))
        else:
            ref = int(rng.integers(0, num_frames))
        if i % 3 == 2:
            q = LikelihoodQuery(target, (24, 24), distances.intersection,
                                stride=8)
        else:
            r0, c0 = int(rng.integers(0, 40)), int(rng.integers(0, 40))
            q = RegionQuery(np.array([r0, c0, r0 + 23, c0 + 23]))
        reqs.append((ref, q))
    return reqs


def _drive(svc: AnalyticsService, reqs, depth: int) -> float:
    """Closed loop with `depth` submits outstanding; returns seconds.

    A resolved future may still hold lazy device arrays, so the elapsed
    time is taken only after blocking on every answer — otherwise this
    times dispatch, not compute (the host-sync/timing rule the linter
    enforces for the kernels applies to benchmarks by hand)."""
    t0 = time.perf_counter()
    inflight: collections.deque = collections.deque()
    outs = []
    with svc:
        for ref, q in reqs:
            inflight.append(svc.submit(ref, q, block=True))
            if len(inflight) >= depth:
                outs.append(inflight.popleft().result())
        while inflight:
            outs.append(inflight.popleft().result())
        jax.block_until_ready(outs)
    return time.perf_counter() - t0


# Mesh-scale curve: the same closed-loop traffic against
# DistributedAnalyticsService on the first 1/2/4/8 devices.  Every point
# runs in this process on a mesh over ``jax.devices()[:ndev]`` (a chip
# belongs to one process, so a child interpreter could not reach it), and
# the multi-device answers must hash to the single-device baseline's
# digest (bit-exactness is the acceptance bar, throughput is the curve).
# On a CPU host ``benchmarks.run`` forces eight host devices before JAX
# is imported, which rehearses the meshes.
_SCALE_SHAPES = {2: (1, 2), 4: (1, 4), 8: (2, 4)}
_SCALE_LAYOUT = {1: "single device (plain service)",
                 2: "1 group x 2-way bins",
                 4: "1 group x 4-way bins",
                 8: "2 groups x 4-way bins"}


def _scale_point(ndev: int, smoke: bool) -> tuple[float, str]:
    """(wall seconds, answer digest) of the scale traffic on ``ndev``
    devices."""
    n_req = 48 if smoke else 240
    n_cams, per_cam = (4, 4) if smoke else (8, 8)
    h, w, bins = (96, 128, 16) if smoke else (240, 320, 16)

    # Independent camera streams: string refs do not chain (no
    # predecessor), so the consistent-hash router spreads them across
    # replica groups.
    frames = {}
    for cam in range(n_cams):
        for i, f in enumerate(video_frames(h, w, per_cam, seed=100 + cam)):
            frames[f"cam{cam}/{i}"] = f
    refs = sorted(frames)
    rng = np.random.default_rng(3)
    target = np.ones(bins, np.float32)
    reqs = []
    for i in range(n_req):
        ref = refs[int(rng.integers(0, len(refs)))]
        if i % 3 == 2:
            q = LikelihoodQuery(target, (24, 24), distances.intersection,
                                stride=8)
        else:
            r0, c0 = int(rng.integers(0, 40)), int(rng.integers(0, 40))
            q = RegionQuery(np.array([r0, c0, r0 + 23, c0 + 23]))
        reqs.append((ref, q))

    if ndev == 1:
        svc = AnalyticsService(HistogramEngine(bins), frames,
                               cache_size=8, max_pending=256)
    else:
        mesh = make_mesh(_SCALE_SHAPES[ndev], ("data", "model"),
                         devices=jax.devices()[:ndev])
        svc = DistributedAnalyticsService(
            sharded_engine_factory(bins), frames,
            mesh=mesh, replica_axis="data", cache_size=8, max_pending=256)

    svc.process(reqs[:2])  # warm the XLA compile cache
    svc.clear_cache()
    t0 = time.perf_counter()
    outs = svc.process(reqs)
    jax.block_until_ready(outs)
    wall = time.perf_counter() - t0

    digest = hashlib.blake2b(digest_size=16)
    for out in outs:
        for leaf in jax.tree_util.tree_leaves(out):
            digest.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return wall, digest.hexdigest()


def _scale_curve(smoke: bool) -> str:
    """req/s vs device count; asserts answers stay bit-exact."""
    n_req = 48 if smoke else 240
    rows = []
    base = None
    for ndev in (1, 2, 4, 8):
        if ndev > len(jax.devices()):
            break
        wall, digest = _scale_point(ndev, smoke)
        base = base or digest
        if digest != base:
            raise AssertionError(
                f"ndev={ndev} answers diverge from the single-device "
                f"baseline ({digest} != {base})")
        common.TIMINGS.append({
            "median_s": wall, "min_s": wall, "iters": 1,
            "label": f"serve_scale_ndev{ndev}",
        })
        rows.append([ndev, _SCALE_LAYOUT[ndev], f"{n_req / wall:.1f}",
                     f"{wall * 1e3:.0f} ms", "yes"])
    return fmt_table(
        ["devices", "replica x shard layout", "req/s", "wall",
         "bit-exact vs 1 dev"], rows)


def run(quick: bool = False) -> str:
    n_req = 60 if (quick or common.SMOKE) else 400
    n_frames, hot = (8, 2) if (quick or common.SMOKE) else (32, 4)
    h, w, bins = (96, 128, 16) if (quick or common.SMOKE) else (240, 320, 16)
    store = {i: f for i, f in enumerate(video_frames(h, w, n_frames, seed=7))}

    rows = []
    for depth in (1, 4, 16):
        for cache in (0, 8):
            reqs = _requests(n_req, n_frames, hot, seed=depth)
            svc = AnalyticsService(
                HistogramEngine(bins), store,
                cache_size=cache, max_pending=max(depth * 2, 4),
            )
            # warm the XLA compile cache, then start the measurement
            # cold: clear the HSource cache so hit rates are earned by
            # the measured traffic, not the warm-up
            svc.process(reqs[:2])
            svc.clear_cache()
            svc.stats = type(svc.stats)()
            dt = _drive(svc, reqs, depth)
            common.TIMINGS.append({
                "median_s": dt, "min_s": dt, "iters": 1,
                "label": f"serve_depth{depth}_cache{cache}",
            })
            s = svc.stats.snapshot()
            rows.append([
                depth, "on" if cache else "off",
                f"{n_req / dt:.1f}",
                f"{100 * s['cache_hit_rate']:.0f}%",
                f"{100 * s['coalesced'] / max(s['requests'], 1):.0f}%",
                f"{s['engine_runs'] / max(s['requests'], 1):.2f}",
                f"{100 * s['update_ratio']:.0f}%",
                f"{1e3 * s['latency_p95_s']:.1f}",
            ])
    out = fmt_table(
        ["depth", "cache", "req/s", "hit rate", "coalesced",
         "runs/req", "updated", "p95 ms"],
        rows,
    )
    out += "\n\nmesh scaling"
    if jax.default_backend() == "cpu":
        out += (" (host 'devices' share the CPU cores, so req/s is "
                "about\ncorrectness of the sharded path under load, not "
                "real speedup)")
    out += ":\n"
    out += _scale_curve(quick or common.SMOKE)
    return out


if __name__ == "__main__":
    print(run())
