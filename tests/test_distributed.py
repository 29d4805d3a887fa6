"""Multi-device correctness, run in subprocesses with 8 forced host devices
(the main pytest process must keep seeing 1 device).

Covers: bin/spatial-sharded integral histograms vs the oracle, expert-
parallel MoE vs single-device math, compressed all-reduce accuracy, and a
sharded train step vs the unsharded one."""

import os
import subprocess
import sys
import textwrap

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH="src")


def _run(body: str):
    code = textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", code], env=_ENV,
                          capture_output=True, text=True, cwd=os.getcwd(),
                          timeout=420)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def test_distributed_integral_histograms():
    out = _run("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp
        from repro.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        from repro.core.distributed import bin_sharded_ih, spatial_sharded_ih
        from repro.kernels.ref import integral_histogram_ref
        img = jnp.asarray(np.random.default_rng(1).integers(
            0, 256, (64, 128), dtype=np.uint8))
        ref = integral_histogram_ref(img, 16)
        assert np.allclose(bin_sharded_ih(img, 16, mesh), ref)
        assert np.allclose(
            spatial_sharded_ih(img, 16, mesh, scan_impl="allgather"), ref)
        assert np.allclose(
            spatial_sharded_ih(img, 16, mesh, scan_impl="ppermute"), ref)
        assert np.allclose(
            spatial_sharded_ih(img, 16, mesh, bin_axis="model"), ref)

        # batched analytics over the sharded H: (n, h, w) frame stacks and
        # rank-polymorphic distributed_region_query
        from repro.core.distributed import distributed_region_query
        from repro.core.region_query import region_histogram
        imgs = jnp.asarray(np.random.default_rng(2).integers(
            0, 256, (2, 64, 128), dtype=np.uint8))
        refs = jnp.stack([integral_histogram_ref(im, 16) for im in imgs])
        Hs = bin_sharded_ih(imgs, 16, mesh)
        assert Hs.shape == (2, 16, 64, 128)
        assert np.allclose(Hs, refs)
        rects = jnp.array([[0, 0, 63, 127], [3, 4, 30, 40]])
        got = distributed_region_query(Hs, rects, mesh)
        assert got.shape == (2, 2, 16)
        assert np.allclose(got, region_histogram(refs, rects))
        # unbatched query unchanged
        got1 = distributed_region_query(Hs[0], rects, mesh)
        assert np.allclose(got1, region_histogram(refs[0], rects))

        # band streaming composed with both sharding schemes: the band
        # carry rides on top of the intra-band device carries, bit-exact.
        # (Bands are assembled host-side: each band.H stays sharded.)
        from repro.core.distributed import iter_banded_sharded_ih
        got_bin = np.concatenate(
            [np.asarray(b.H) for b in iter_banded_sharded_ih(
                img, 16, mesh, sharding="bin", band_h=24)], axis=-2)
        assert np.array_equal(got_bin, np.asarray(ref))
        got_sp = np.concatenate(
            [np.asarray(b.H) for b in iter_banded_sharded_ih(
                img, 16, mesh, sharding="spatial", band_h=24)], axis=-2)
        assert np.array_equal(got_sp, np.asarray(ref))
        stack_bands = iter_banded_sharded_ih(imgs, 16, mesh, sharding="bin",
                                             memory_budget_bytes=2 * 16 * 16
                                             * 128 * 4 * 2)
        got_stack = np.concatenate(
            [np.asarray(b.H) for b in stack_bands], axis=-2)
        assert np.array_equal(got_stack, np.asarray(refs))
        print("dist-IH OK")
    """)
    assert "dist-IH OK" in out


def test_engine_sharded_parity_and_host_assembly():
    """The plan/execute engine on an 8-device mesh: bin- and
    spatial-sharded plans are bit-exact vs the monolithic oracle, and the
    banded+row-sharded band assembly goes through host-side np — NEVER
    ``jnp.concatenate`` over row-sharded bands, which silently
    mis-assembles on jax 0.4.37 (CHANGES.md, PR 3).

    The primary guard against a regression to device-side assembly is
    static now: the ``sharded-concat`` lint rule (repro.analysis) flags
    any ``jnp.concatenate``/``jnp.stack`` over band/shard operands in
    the core assembly paths, on every jax version, without running a
    mesh (tests/test_analysis.py pins the rule itself).  This test keeps
    the runtime parity story: sharded plans match the oracle and
    ``rows()`` hands back host arrays by construction."""
    out = _run("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.engine import HistogramEngine, RegionQuery, \\
            SlidingWindowQuery
        from repro.core.hsource import BandedH
        from repro.kernels.ops import integral_histogram

        img = np.random.default_rng(5).integers(
            0, 256, (64, 128), dtype=np.uint8)
        ref = np.asarray(integral_histogram(jnp.asarray(img), 16,
                                            backend="jnp"))
        rects = np.array([[0, 0, 63, 127], [3, 4, 30, 40]])
        from repro.core.region_query import (region_histogram,
                                             sliding_window_histograms)
        want_r = np.asarray(region_histogram(jnp.asarray(ref), rects))
        want_w = np.asarray(sliding_window_histograms(
            jnp.asarray(ref), (16, 24), 8))

        # bin-sharded plan (2x4 mesh, bins divide the model axis)
        from repro.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        eng = HistogramEngine(16, backend="jnp", mesh=mesh)
        out = eng.run(img, [RegionQuery(rects),
                            SlidingWindowQuery((16, 24), 8)])
        assert out.plan.representation == "sharded"
        assert out.plan.sharding == "bin"
        assert np.array_equal(np.asarray(out.results[0]), want_r)
        assert np.array_equal(np.asarray(out.results[1]), want_w)

        # spatial (row-strip) plan, forced explicitly
        eng_sp = HistogramEngine(16, backend="jnp", mesh=mesh,
                                 sharding="spatial")
        out_sp = eng_sp.run(img, [RegionQuery(rects)])
        assert out_sp.plan.sharding == "spatial"
        assert np.array_equal(np.asarray(out_sp.results[0]), want_r)
        assert np.array_equal(np.asarray(out_sp.source.dense()), ref)

        # banded + row-sharded: bands stream through the mesh, assembly
        # and corner-row slabs are host-side (the guard is live here)
        budget = 4 * 16 * 128 * 16                # 16-row bands
        eng_b = HistogramEngine(16, backend="jnp", mesh=mesh,
                                sharding="spatial",
                                memory_budget_bytes=budget)
        out_b = eng_b.run(img, [RegionQuery(rects),
                                SlidingWindowQuery((16, 24), 8)])
        assert out_b.plan.band_plan is not None
        assert out_b.plan.band_plan.num_bands >= 4
        assert isinstance(out_b.source, BandedH)
        assert np.array_equal(np.asarray(out_b.results[0]), want_r)
        assert np.array_equal(np.asarray(out_b.results[1]), want_w)
        rows = out_b.source.rows(np.array([0, 15, 16, 63]))
        assert type(rows) is np.ndarray          # host-side by construction
        assert np.array_equal(rows, ref[:, [0, 15, 16, 63], :])
        # banded + bin-sharded through the same engine path
        eng_bb = HistogramEngine(16, backend="jnp", mesh=mesh,
                                 memory_budget_bytes=budget)
        out_bb = eng_bb.run(img, [RegionQuery(rects)])
        assert out_bb.plan.sharding == "bin"
        assert out_bb.plan.band_plan is not None
        assert np.array_equal(np.asarray(out_bb.results[0]), want_r)
        print("engine-sharded OK")
    """)
    assert "engine-sharded OK" in out


def test_expert_parallel_moe_matches_local():
    out = _run("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp, dataclasses
        from repro.configs import smoke_config
        from repro.models.moe import moe_block, moe_params
        from repro.sharding.rules import sharding_context
        cfg = smoke_config("kimi-k2-1t-a32b")
        cfg = dataclasses.replace(cfg, dtype="float32",
                                  capacity_factor=8.0, d_model=64)
        p = moe_params(jax.random.PRNGKey(1), cfg)
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 8, 64)) * 0.1
        local, aux_l = moe_block(x, p, cfg)
        from repro.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh, sharding_context(mesh):
            shard, aux_s = jax.jit(lambda x, p: moe_block(x, p, cfg))(x, p)
        err = float(jnp.max(jnp.abs(local - shard)))
        assert err < 1e-4, err
        # aux under DP is the mean of per-shard load-balance estimates
        # (nonlinear in token partition) — close but not bit-equal.
        assert abs(float(aux_l) - float(aux_s)) < 0.05
        print("EP-MoE OK", err)
    """)
    assert "EP-MoE OK" in out


def test_compressed_psum_accuracy():
    out = _run("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp
        from repro.train.grad import compressed_psum
        from repro.compat import make_mesh
        mesh = make_mesh((8,), ("pod",))
        parts = jnp.asarray(np.random.default_rng(0).normal(
            size=(8, 128)) * 1e-3)
        exact = jnp.sum(parts, 0)
        approx = compressed_psum(parts, mesh, "pod")
        rel = float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact))
        assert rel < 0.02, rel
        print("compressed psum OK", rel)
    """)
    assert "compressed psum OK" in out


def test_sharded_train_step_matches_unsharded():
    out = _run("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp, dataclasses
        from repro.configs import smoke_config
        from repro.models import api
        from repro.sharding.rules import ShardingRules, sharding_context
        from repro.train import (init_state, make_optimizer, make_train_step,
                                 state_shardings, batch_shardings)
        cfg = smoke_config("qwen3-4b")
        cfg = dataclasses.replace(cfg, dtype="float32")
        opt = make_optimizer(cfg, peak_lr=1e-3, warmup=2, total_steps=10)
        step = make_train_step(cfg, opt)
        state = init_state(jax.random.PRNGKey(0), cfg, opt)
        batch = {"tokens": jax.random.randint(
                     jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size),
                 "labels": jax.random.randint(
                     jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab_size)}
        _, m1 = jax.jit(step)(state, batch)

        from repro.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = ShardingRules()
        with mesh, sharding_context(mesh, rules):
            st_shape = jax.eval_shape(lambda: init_state(
                jax.random.PRNGKey(0), cfg, opt))
            st_sh = state_shardings(st_shape, mesh, rules)
            b_sh = batch_shardings(jax.eval_shape(lambda: batch), mesh, rules)
            jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                             out_shardings=(st_sh, None))
            state_s = jax.device_put(state, st_sh)
            batch_s = jax.device_put(batch, b_sh)
            _, m2 = jitted(state_s, batch_s)
        d = abs(float(m1["loss"]) - float(m2["loss"]))
        assert d < 1e-4, d
        print("sharded train OK", d)
    """)
    assert "sharded train OK" in out


def test_production_mesh_shapes():
    out = _run("""
        import jax
        from repro.launch.mesh import make_host_mesh
        m = make_host_mesh((2, 4))
        assert m.shape == {"data": 2, "model": 4}
        print("mesh OK")
    """)
    assert "mesh OK" in out


def test_distributed_service_parity_8dev():
    """ISSUE 10 satellite: end-to-end mesh-serving parity on 8 fake
    devices.  A DistributedAnalyticsService answers a mixed trace —
    including a PR 9 low-motion video chain — bit-exact vs a
    single-device AnalyticsService fed the same trace, in BOTH layouts:
    8 replica groups x 1 device (chain pinned to one replica, updates
    local) and 2 replica groups x 4-way bin sharding.  Also pins the
    mesh-native plumbing underneath: explain() renders the replica x
    shard layout, sharded band slices stage with a NamedSharding and the
    between-band carry stays a device array, and ShardedH gathers corner
    rows device-side."""
    out = _run("""
        import warnings; warnings.filterwarnings("ignore")
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.core.engine import (HistogramEngine, RegionQuery,
                                       SlidingWindowQuery)
        from repro.serve import (AnalyticsService,
                                 DistributedAnalyticsService,
                                 sharded_engine_factory)

        rng = np.random.default_rng(11)
        h, w, bins = 64, 96, 16
        frames = [rng.integers(0, 256, (h, w), dtype=np.uint8)]
        for _ in range(4):                      # low-motion chain 0..4
            nxt = frames[-1].copy()
            r = int(rng.integers(0, h - 3))
            nxt[r:r + 3] = rng.integers(0, 256, (3, w), dtype=np.uint8)
            frames.append(nxt)
        for _ in range(3):                      # independent frames 5..7
            frames.append(rng.integers(0, 256, (h, w), dtype=np.uint8))
        store = {i: f for i, f in enumerate(frames)}
        # 20 corner rows > h/4: keeps plans dense (H stored) so the
        # video chain can actually update
        rects = np.array([[3 * i, 2, 3 * i + 1, 10] for i in range(10)])
        trace = [(i, RegionQuery(rects)) for i in range(5)]
        trace += [(i, RegionQuery(rects)) for i in (5, 6, 7, 2, 5)]
        trace.append((3, SlidingWindowQuery((16, 24), 8)))

        single = AnalyticsService(HistogramEngine(bins, backend="jnp"),
                                  store)
        want = single.process(list(trace))

        # layout 1: 8 replica groups x 1 device — chain-pinned updates
        from repro.compat import make_mesh
        mesh_r = make_mesh((8,), ("data",))
        dist_r = DistributedAnalyticsService(
            sharded_engine_factory(bins, backend="jnp"), store,
            mesh=mesh_r, replica_axis="data")
        got_r = dist_r.process(list(trace))
        for g, wv in zip(got_r, want):
            assert np.array_equal(np.asarray(g), np.asarray(wv))
        assert len({dist_r.replica_for(i) for i in range(5)}) == 1
        snap = dist_r.snapshot()
        assert snap["num_replicas"] == 8
        per_updated = [p["updated"] for p in snap["replicas"]]
        assert sum(per_updated) == 4            # frames 1..4 updated...
        assert sum(1 for u in per_updated if u) == 1   # ...on ONE replica
        # each one-device replica computes on its own device, not all
        # on the first
        for r, dev in zip(dist_r.replicas, mesh_r.devices.flat):
            assert r.device == dev
            for ref in r.cached_frames:
                assert r._cache[ref].H.devices() == {dev}, (ref, dev)
        print("replica-parallel parity OK", per_updated)

        # layout 2: 2 replica groups x 4-way bin shard
        from repro.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        dist_s = DistributedAnalyticsService(
            sharded_engine_factory(bins, backend="jnp"), store,
            mesh=mesh, replica_axis="data")
        got_s = dist_s.process(list(trace))
        for g, wv in zip(got_s, want):
            assert np.array_equal(np.asarray(g), np.asarray(wv))
        sub = dist_s.replicas[0]._engine.mesh
        assert dict(sub.shape) == {"model": 4}
        print("sharded-replica parity OK")

        # the planner's layout, rendered at mesh scale
        eng = HistogramEngine(bins, backend="jnp", mesh=mesh)
        out = eng.run(frames[5], [RegionQuery(rects)])
        text = eng.last_plan.explain()
        assert ("mesh layout     : 2 replica group(s) over 'data' x bin "
                "sharding over 'model' (4 device(s)/group)") in text

        # sharded carry rides the shard layout: band slices stage with a
        # NamedSharding and the between-band carry is a committed device
        # array, never a host round-trip
        from repro.core.distributed import iter_banded_sharded_ih
        from repro.kernels.ops import integral_histogram
        img = frames[5]
        ref = np.asarray(integral_histogram(jnp.asarray(img), bins,
                                            backend="jnp"))
        bands = list(iter_banded_sharded_ih(img, bins, mesh,
                                            sharding="spatial", band_h=16,
                                            prefetch=1))
        for b in bands:
            assert isinstance(b.carry.sharding, NamedSharding)
            assert isinstance(b.H.sharding, NamedSharding)
        got_b = np.concatenate([np.asarray(b.H) for b in bands], axis=-2)
        assert np.array_equal(got_b, ref)

        # ShardedH device-side corner-row gather, both kinds
        from repro.core.distributed import (bin_sharded_ih,
                                            spatial_sharded_ih)
        from repro.core.hsource import ShardedH
        rid = np.array([0, 15, 16, 63])
        for kind, H in (("bin", bin_sharded_ih(jnp.asarray(img), bins,
                                               mesh)),
                        ("spatial", spatial_sharded_ih(jnp.asarray(img),
                                                       bins, mesh))):
            src = ShardedH(H, mesh, kind=kind)
            rows = src.rows(rid)
            assert type(rows) is np.ndarray
            assert np.array_equal(rows, ref[:, rid, :]), kind
        print("mesh-serving OK")
    """)
    assert "replica-parallel parity OK" in out
    assert "sharded-replica parity OK" in out
    assert "mesh-serving OK" in out
