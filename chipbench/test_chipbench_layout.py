"""Each cell's devices are laid out as its mix asks: the mix's replicas,
each sharding H's bins over the chips left to it, and
``harness.build_service`` builds that mesh with engines sharded over
bins."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from chipbench import cells, harness
from chipbench.conftest import bench_with_later, small


@pytest.mark.parametrize("cell", bench_with_later()["workloads"],
                         ids=lambda c: c["name"])
def test_chips_are_replicas_times_shards(cell):
    """Every replica gets the same number of chips, and that number
    divides the bins."""
    _, cfg, mix = cells.cell(cell["name"], bench_with_later())
    shards, left = divmod(cell["chips"], mix.get("replicas", 1))
    assert shards >= 1 and left == 0
    assert cfg["bins"] % shards == 0


def test_one_device_takes_the_degenerate_layout():
    """With no devices (the CPU tests), a bin-sharded configuration runs
    one unsharded engine on the default device."""
    from repro.serve import AnalyticsService

    cell, cfg, mix, _ = small("paper8k128.sharded4")
    assert cell["chips"] == 4 and mix.get("replicas", 1) == 1
    rec = harness.Recorder(False)
    svc = harness.build_service(cfg, mix, harness.FrameStore(rec, 1), rec,
                                None)
    assert isinstance(svc, AnalyticsService)
    assert svc._engine.mesh is None


def test_four_devices_shard_the_bins():
    """On four (forced CPU) devices the cell's service is one replica
    whose engine shards the bins over all four, and a whole run of the
    cell, cut small, plans every frame sharded and reads ``correct``; with
    one chip's share of the bins lost (``faults.shard_lost``), the same
    run reads not correct."""
    code = textwrap.dedent("""
        import json, time
        import jax
        from chipbench import faults, harness, runner
        from chipbench.conftest import small

        cell, cfg, mix, bench = small("paper8k128.sharded4")
        runs, services = [], []
        real_run, real_build = harness.Run, harness.build_service

        def run(*a, **k):
            runs.append(real_run(*a, **k))
            return runs[-1]

        def build(*a, **k):
            services.append(real_build(*a, **k))
            return services[-1]

        harness.Run, harness.build_service = run, build
        out = runner.execute(cell, cfg, mix, seed=2**31 + 21, seconds=1.5,
                             traced=False, devices=jax.devices(),
                             t_process=time.perf_counter(), bench=bench)
        engine = services[0].replicas[0]._engine
        with faults.shard_lost():
            lost = runner.execute(cell, cfg, mix, seed=2**31 + 22,
                                  seconds=1.5, traced=False,
                                  devices=jax.devices(),
                                  t_process=time.perf_counter(), bench=bench)
        print(json.dumps({
            "lost_correct": lost["correct"], "lost_checks": lost["checks"],
            "correct": out["correct"], "checks": out["checks"],
            "count": out["device"]["count"],
            "replicas": len(services[0].replicas),
            "mesh": dict(engine.mesh.shape), "sharding": engine.sharding,
            "plans": dict(runs[0].rec.plans)}))
    """)
    root = cells.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([root, os.path.join(root, "src")]))
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["correct"] is True, got["checks"]
    assert got["checks"]["checked"]["value"] > 0
    assert got["count"] == 4 and got["replicas"] == 1
    assert got["mesh"] == {"model": 4} and got["sharding"] == "bin"
    assert set(got["plans"]) == {"sharded"}, got["plans"]
    assert got["lost_correct"] is False, got["lost_checks"]

