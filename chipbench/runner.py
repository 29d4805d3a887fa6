"""One run of one cell: set-up, warm-up, the timed window, the traced
stretch, the device's peak memory, then the correctness check, and the
result line the benchmark prints."""

from __future__ import annotations

import collections
import gc
import math
import shutil
import sys
import tempfile
import threading
import time

from chipbench import cells, check, harness, scene
from chipbench import trace as trace_mod


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def configure_jax(root: str) -> str:
    """Persistent compilation cache at a fixed path in the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every compile kept."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _trace_thread(start: float, stop: float,
                  log_dir: str) -> threading.Thread:
    """Profile the device from ``start`` to ``stop`` (perf_counter) into
    ``log_dir``, inside a ``bench.trace`` annotation."""
    import jax

    def body():
        time.sleep(max(0.0, start - time.perf_counter()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                time.sleep(max(0.0, stop - time.perf_counter()))
        finally:
            jax.profiler.stop_trace()

    th = threading.Thread(target=body, name="bench-trace", daemon=True)
    th.start()
    return th


def peak_memory(devices) -> int | None:
    peaks = []
    for d in devices or ():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def execute(cell: dict, cfg: dict, mix: dict, *, seed: int, seconds: float,
            traced: bool, devices, t_process: float, bench: dict,
            samples_out: list | None = None) -> dict:
    """Run the cell once; returns the result line as a dict.  The frames
    and answers the check compared are appended to ``samples_out``."""
    import jax

    rec = harness.Recorder(traced)
    jax.monitoring.register_event_duration_secs_listener(rec.on_compile)
    store = harness.FrameStore(rec, keep=mix["keep_frames"])
    used = devices[:cell["chips"]] if devices is not None else None
    run = harness.Run(cell, cfg, mix, seconds, rec=rec,
                      device_kind=jax.devices()[0].device_kind)
    streams = scene.streams(cfg, mix, seed)
    svc = harness.build_service(cfg, mix, store, rec, used)
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    marks = {}
    threads = []

    def on_start(t0):
        marks["plans"] = collections.Counter(rec.plans)
        marks["compiles"] = rec.compiles
        run.start = harness.counters(svc)
        stop = t0 + seconds

        def at_stop():
            time.sleep(max(0.0, stop - time.perf_counter()))
            run.end = harness.counters(svc)
            run.compiles_in_window = rec.compiles - marks["compiles"]
            marks["plans_end"] = collections.Counter(rec.plans)

        th = threading.Thread(target=at_stop, daemon=True)
        th.start()
        threads.append(th)
        if traced:
            span = min(mix["trace_seconds"], seconds / 2)
            threads.append(_trace_thread(stop - span, stop, log_dir))

    log(f"set-up: service built at {time.perf_counter() - t_process:.2f} s")
    try:
        with svc:
            gen = harness.LoadGenerator(run, svc, store, streams, seed)
            next_t = gen.warm_up()
            log(f"set-up: warm-up done at "
                f"{time.perf_counter() - t_process:.2f} s "
                f"({rec.compiles} compiles)")
            gen.window(next_t, on_start)
            for th in threads:
                th.join()
    finally:
        jax.monitoring.unregister_event_duration_listener(rec.on_compile)
    memory = peak_memory(used)
    # The reference runs after the program's state is freed.
    gen = svc = store = None
    gc.collect()
    if traced:
        run.trace = trace_mod.read(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
    window_plans = marks["plans_end"] - marks["plans"]
    log("plans in window: " + ", ".join(
        f"{k} x{n}" for k, n in sorted(window_plans.items())))
    log("counters in window: " + ", ".join(
        f"{k}={run.end['total'][k] - run.start['total'][k]}"
        for k in run.end["total"]) + f"; compiles={run.compiles_in_window}")

    e2e, per_layer = cells.metrics_for(cell["name"], bench)
    metrics = {}
    if traced:
        for m in per_layer:
            value = cells.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = end_to_end(run, t_process)
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    frames = in_window(run)
    failed = sum(1 for f in frames if f.rejected or f.error is not None)
    t_check = time.perf_counter()
    readings = check.compare(run.samples, cfg)
    if samples_out is not None:
        samples_out.extend(run.samples)
    readings["lost"] = sum(1 for f in frames
                           if not f.rejected and f.done is None)
    if mix.get("replicas", 1) > 1:
        readings["chain_splits"] = check.chain_splits(rec.touched)
    correct, checks = check.judge(readings, cfg["limits"])
    log(f"check of {readings['checked']} frames took "
        f"{time.perf_counter() - t_check:.1f} s")
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(used) if used is not None else 1,
              "memory_peak_bytes": memory}
    out = {"correct": correct, "attempted": len(frames), "failed": failed,
           "metrics": metrics, "device": device}
    if traced and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.device_ops,
                            "idle_gaps": run.trace.idle_gaps}
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    out["checks"] = checks
    return out


def in_window(run: harness.Run) -> list:
    """The frames the window is judged by: those due in it (open loop),
    or those submitted in it (closed loop)."""
    lo, hi = run.t0, run.t0 + run.seconds
    return [f for f in run.frames if lo <= f.due < hi]


def end_to_end(run: harness.Run, t_process: float) -> dict:
    frames = in_window(run)
    lat = [f.done - f.due if f.done is not None else math.inf
           for f in frames]
    return {
        "setup_s": run.t0 - t_process,
        "frames_per_s": len(run.done_in_window()) / run.seconds,
        "latency_p50_ms": 1e3 * harness.percentile(lat, 0.50),
        "latency_p95_ms": 1e3 * harness.percentile(lat, 0.95),
    }
