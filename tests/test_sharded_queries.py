"""A bin-sharded H answers on the devices, and the service counts what
reaches the host.

The sharded checks run in a subprocess with four forced host devices
(the main pytest process keeps seeing one): ``ShardedH(kind="bin")``'s
window histograms, likelihood map, multi-scale search and tracker
fragments equal ``DenseH``'s bit for bit and the int64 NumPy reference,
with ``ShardedH.rows`` made to raise, so no answer goes through the host.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core.engine import HistogramEngine, LikelihoodQuery, RegionQuery
from repro.serve import AnalyticsService


def _run4(body: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       env=env, capture_output=True, text=True,
                       cwd=os.getcwd(), timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


_SHARDED = """
    import json
    import jax, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.compat import make_mesh
    from repro.core import distances, engine as engine_mod
    from repro.core.distributed import bin_sharded_ih
    from repro.core.hsource import DenseH, ShardedH

    def refuse(self, row_ids):
        raise AssertionError("a sharded query pulled rows to the host")

    ShardedH.rows = refuse
    rng = np.random.default_rng(5)
    h, w, bins = 96, 160, 8
    frame = rng.integers(0, 256, (h, w), dtype=np.uint8)
    frame[:40, :100] = 90                          # a flat area in one bin
    mesh = make_mesh((4,), ("model",))
    idx = (frame.astype(np.int64) * bins) // 256
    q = (idx[None] == np.arange(bins)[:, None, None]).astype(np.int64)
    ref = np.cumsum(np.cumsum(q, axis=1), axis=2)      # (b, h, w) int64
    ref_p = np.pad(ref, ((0, 0), (1, 0), (1, 0)))

    def ref_windows(wnd, s):
        wh, ww = wnd
        r = np.arange(0, h - wh + 1, s)
        c = np.arange(0, w - ww + 1, s)
        R, C = np.meshgrid(r, c, indexing="ij")
        out = (ref_p[:, R + wh, C + ww] - ref_p[:, R, C + ww]
               - ref_p[:, R + wh, C] + ref_p[:, R, C])
        return np.moveaxis(out, 0, -1)

    target = rng.random(bins).astype(np.float32)
    rects = np.array([[0, 0, h - 1, w - 1], [3, 5, 50, 70], [0, 9, 0, 9],
                      [40, 0, 95, 159]], np.int32)
    r0, c0, r1, c1 = rects.T
    ref_regions = (ref_p[:, r1 + 1, c1 + 1] - ref_p[:, r0, c1 + 1]
                   - ref_p[:, r1 + 1, c0] + ref_p[:, r0, c0]).T
    out = {}
    for name, H in {
        "fp32": bin_sharded_ih(frame, bins, mesh),
        "int32": jax.device_put(
            np.asarray(bin_sharded_ih(frame, bins, mesh)).astype(np.int32),
            NamedSharding(mesh, P("model", None, None))),
    }.items():
        dense, sharded = DenseH(np.asarray(H)), ShardedH(H, mesh)
        win = sharded.sliding_window_histograms((16, 24), 4)
        lik = sharded.likelihood_map(target, (16, 24),
                                     distances.intersection, 4)
        rect, best, maps = sharded.multi_scale_search(
            target, ((16, 24), (32, 32)), distances.intersection, 8)
        regions = sharded.region_histogram(rects)
        d_rect, d_best, d_maps = dense.multi_scale_search(
            target, ((16, 24), (32, 32)), distances.intersection, 8)
        out[name] = {
            "win_dtype": str(win.dtype), "region_dtype": str(regions.dtype),
            "win_vs_dense": bool(np.array_equal(
                win, dense.sliding_window_histograms((16, 24), 4))),
            "win_vs_ref": bool(np.array_equal(win, ref_windows((16, 24), 4))),
            "lik_vs_dense": bool(np.array_equal(lik, dense.likelihood_map(
                target, (16, 24), distances.intersection, 4))),
            "ms_vs_dense": bool(np.array_equal(rect, d_rect)
                                and np.array_equal(best, d_best)
                                and all(np.array_equal(a, b)
                                        for a, b in zip(maps, d_maps))),
            "regions_vs_dense": bool(np.array_equal(
                regions, dense.region_histogram(rects))),
            "regions_vs_ref": bool(np.array_equal(regions, ref_regions)),
        }

    # the engine on the mesh answers through the compiled programs
    paths = []
    real = engine_mod.TraceAnnotation

    class Span(real):
        def __init__(self, name, **kw):
            if name == "engine.query":
                paths.append(kw["path"])
            super().__init__(name, **kw)

    engine_mod.TraceAnnotation = Span
    eng = engine_mod.HistogramEngine(bins, mesh=mesh)
    res = eng.run(frame, [engine_mod.LikelihoodQuery(target, (16, 24), stride=4),
                          engine_mod.RegionQuery(rects)])
    answers = sum(np.asarray(a).nbytes for a in res.results)
    out["engine"] = {
        "representation": res.plan.representation,
        "sharding": res.plan.sharding, "paths": paths,
        "d2h_bytes": res.d2h_bytes, "answer_bytes": int(answers),
        "regions_vs_ref": bool(np.array_equal(res.results[1], ref_regions)),
    }
    print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded():
    return _run4(_SHARDED)


@pytest.mark.parametrize("h_type", ["fp32", "int32"])
@pytest.mark.parametrize("check", [
    "win_vs_dense", "win_vs_ref", "lik_vs_dense", "ms_vs_dense",
    "regions_vs_dense", "regions_vs_ref"])
def test_bin_sharded_queries_match_dense_and_reference(sharded, h_type,
                                                       check):
    assert sharded[h_type][check] is True


def test_sharded_answer_types(sharded):
    assert sharded["fp32"]["win_dtype"] == "float32"
    assert sharded["fp32"]["region_dtype"] == "float32"
    # an int32 H: windows cast after the four-corner sum, regions kept
    assert sharded["int32"]["win_dtype"] == "float32"
    assert sharded["int32"]["region_dtype"] == "int32"


def test_engine_on_a_mesh_labels_the_sharded_path(sharded):
    got = sharded["engine"]
    assert (got["representation"], got["sharding"]) == ("sharded", "bin")
    assert got["paths"] == ["sharded", "sharded"]
    assert got["regions_vs_ref"] is True
    # only the answers reached the host: rows() would have raised
    assert got["d2h_bytes"] == got["answer_bytes"]


# ---------------------------------------------------------------------------
# the service's d2h counter, and one H at a time
# ---------------------------------------------------------------------------
def _frame(rng, h=48, w=64):
    return rng.integers(0, 256, (h, w), dtype=np.uint8)


@pytest.mark.parametrize("budget", [None, 4 * 8 * 64 * 8],
                         ids=["dense", "banded"])
def test_service_counts_bytes_to_the_host(rng, budget):
    """The snapshot's d2h_bytes is every answer's bytes, plus the corner
    rows a banded H pulls to the host."""
    frames = {0: _frame(rng)}
    eng = HistogramEngine(8, backend="jnp", memory_budget_bytes=budget)
    svc = AnalyticsService(eng, frames, cache_size=0)
    target = np.ones(8, np.float32)
    out = svc.process([(0, LikelihoodQuery(target, (8, 8), stride=4)),
                       (0, RegionQuery([[0, 0, 9, 9], [5, 5, 40, 60]]))])
    answers = sum(np.asarray(a).nbytes for a in out)
    got = svc.stats.snapshot()["d2h_bytes"]
    if budget is None:
        assert eng.last_plan.representation == "dense"
        assert got == answers
    else:
        assert eng.last_plan.representation == "banded"
        assert got > answers            # the prefetched corner rows


def test_cache_hits_count_their_answers(rng):
    frames = {0: _frame(rng)}
    svc = AnalyticsService(HistogramEngine(8, backend="jnp"), frames,
                           cache_size=4)
    q = RegionQuery([[0, 0, 47, 63], [1, 1, 2, 2]])
    svc.process([(0, q)])
    first = svc.stats.d2h_bytes          # the fused corner rows + answers
    assert first > 2 * 8 * 4
    svc.process([(0, q)])
    assert svc.stats.cache_hits == 1
    assert svc.stats.d2h_bytes == first + 2 * 8 * 4


def test_a_large_h_is_held_one_frame_at_a_time(rng, monkeypatch):
    """Where one frame's H takes more than the set share of a device's
    memory, a run waits for the last such run's answers, keeps this
    run's, and refuses a stack of frames."""
    frame = _frame(rng)
    eng = HistogramEngine(8, backend="jnp")
    out = eng.run(frame, [RegionQuery([[0, 0, 9, 9]])])
    assert eng._last_answers is None         # the CPU reports no memory
    h_bytes = 4 * 8 * frame.size
    monkeypatch.setattr(engine_mod, "_device_memory_bytes",
                        lambda mesh: 2 * h_bytes)
    waited = []
    monkeypatch.setattr(engine_mod.jax, "block_until_ready",
                        lambda x: waited.append(x) or x)
    q = [RegionQuery([[0, 0, 47, 63], [2, 3, 30, 40]])]
    out = eng.run(frame, q)
    assert eng._last_answers is out.results and waited == [None]
    again = eng.run(frame, q)
    assert waited[-1] is out.results and eng._last_answers is again.results
    np.testing.assert_array_equal(again.results[0], out.results[0])
    with pytest.raises(ValueError, match="one at a time"):
        eng.run(np.stack([frame, frame]), q)
