"""Paper Fig. 16/17: multi-device scaling (bins over devices; large
frames spatially sharded).

Every device-count point runs in this process, on a mesh over the first
``k`` of ``jax.devices()``: a chip belongs to one process, so a child
interpreter could not reach it.  On a CPU host, ``benchmarks.run`` forces
eight host devices before JAX is imported, which rehearses the same
meshes.  The "4 GTX480 + task queue" of the paper becomes a mesh axis;
the spatial sharding with cross-device carries is the beyond-paper
extension."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.common import fmt_table
from repro.compat import make_mesh
from repro.core.distributed import bin_sharded_ih, spatial_sharded_ih
from repro.kernels.ops import integral_histogram


def _timed(fn, img, label):
    fn(img).block_until_ready()
    t0 = time.perf_counter()
    fn(img).block_until_ready()
    dt = time.perf_counter() - t0
    common.TIMINGS.append(
        {"median_s": dt, "min_s": dt, "iters": 1, "label": label})
    return dt


def run(quick: bool = False) -> str:
    quick = quick or common.SMOKE
    ndev_max = len(jax.devices())
    counts = [k for k in (2, 4, 8) if k <= ndev_max]
    rows = []
    rng = np.random.default_rng(0)
    cases = [((1280, 720), 32), ((1920, 1080), 32)]
    if not quick:
        cases += [((4096, 3072), 32), ((1920, 1080), 128)]
    for (w, h), bins in cases:
        img = jnp.asarray(rng.integers(0, 256, (h, w), dtype=np.uint8))
        fn1 = jax.jit(lambda im: integral_histogram(im, bins))
        t1 = _timed(fn1, img, f"multidev_{h}x{w}_b{bins}_1dev")
        for ndev in counts:
            mesh = make_mesh((1, ndev), ("data", "model"),
                             devices=jax.devices()[:ndev])
            fnd = jax.jit(lambda im: bin_sharded_ih(im, bins, mesh,
                                                     backend="auto"))
            td = _timed(fnd, img, f"multidev_{h}x{w}_b{bins}_bins{ndev}")
            rows.append([f"{h}x{w}", bins, ndev, "bins",
                         f"{td*1e3:.1f} ms", f"{t1/td:.2f}x"])
        if counts and h % ndev_max == 0:
            mesh = make_mesh((ndev_max, 1), ("data", "model"))
            fns = jax.jit(lambda im: spatial_sharded_ih(
                im, bins, mesh, backend="auto", scan_impl="ppermute"))
            ts = _timed(fns, img, f"multidev_{h}x{w}_b{bins}_rows{ndev_max}")
            rows.append([f"{h}x{w}", bins, ndev_max, "rows+carry wavefront",
                         f"{ts*1e3:.1f} ms", f"{t1/ts:.2f}x"])
    out = fmt_table(["frame", "bins", "devices", "shard", "wall", "vs 1 dev"],
                    rows)
    if jax.default_backend() == "cpu":
        out += ("\nNOTE: host 'devices' share the CPU cores, so wall-clock "
                "speedup here\nsays nothing about chips; the table shows "
                "correct sharded execution.")
    return out


if __name__ == "__main__":
    print(run())
