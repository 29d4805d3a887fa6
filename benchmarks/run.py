"""Benchmark driver: one bench per paper table/figure + the roofline
aggregation.  `python -m benchmarks.run [--quick|--smoke] [--only NAME]
[--json PATH] [--list]`.

`--list` prints every bench name with its one-line description and
exits 0 (the CLI's discovery surface; tested in tests/test_bench_run.py).

`--smoke` is the CI mode: quick sizes AND single-iteration timing
(benchmarks.common.SMOKE), so every bench script still executes end to
end — numbers are meaningless, rot is caught.

`--json PATH` serializes every bench's `time_fn` records (keyed by bench
name, in call order) plus the failure list — CI uploads it as the
per-commit perf-trajectory artifact."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCHES = [
    ("methods", "benchmarks.bench_methods",
     "paper Fig. 7/8 — four methods, kernel time"),
    ("tiles", "benchmarks.bench_tiles",
     "paper Fig. 9/10 — tile/block configuration sweep"),
    ("pipeline", "benchmarks.bench_pipeline",
     "paper Fig. 13/15 — dual-buffering frame rate"),
    ("batched", "benchmarks.bench_batched",
     "paper §4.4 + arXiv:1011.0235 — frame-batched throughput"),
    ("analytics", "benchmarks.bench_analytics",
     "paper abstract — O(1) sliding-window queries + tracker fps"),
    ("bands", "benchmarks.bench_bands",
     "paper §4.6 + arXiv:1510.05142 — band streaming under a "
     "memory budget"),
    ("engine", "benchmarks.bench_engine",
     "ISSUE 4 — plan/execute engine overhead vs hand-routed calls"),
    ("serve", "benchmarks.bench_serve",
     "ISSUE 5 — AnalyticsService requests/sec vs in-flight depth and "
     "cache"),
    ("fused", "benchmarks.bench_fused",
     "ISSUE 8 — query-fused corner rows vs banded streaming"),
    ("delta", "benchmarks.bench_delta",
     "ISSUE 9 — incremental video-delta H updates vs full recompute"),
    ("multidevice", "benchmarks.bench_multidevice",
     "paper Fig. 16/17 — multi-device bin/spatial sharding"),
    ("speedup", "benchmarks.bench_speedup",
     "paper Fig. 19/20 — speedup vs sequential CPU"),
    ("roofline", "benchmarks.bench_roofline",
     "assignment §Roofline — dry-run derived terms"),
]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes/iterations")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: --quick sizes + 1 timing iteration")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write per-bench time_fn records as JSON")
    ap.add_argument("--list", action="store_true",
                    help="print bench names with descriptions and exit 0")
    args = ap.parse_args(argv)

    if args.list:
        width = max(len(name) for name, _, _ in BENCHES)
        for name, _, desc in BENCHES:
            print(f"{name.ljust(width)}  {desc}")
        return

    valid = [name for name, _, _ in BENCHES]
    only = None
    if args.only:
        only = {n.strip() for n in args.only.split(",") if n.strip()}
        unknown = sorted(only - set(valid))
        if unknown or not only:
            # An unknown name must fail loudly: silently selecting nothing
            # and reporting "all benches complete" hid typos from CI.
            print(f"unknown bench name(s): {unknown or '(none given)'}\n"
                  f"valid names: {valid}", file=sys.stderr)
            sys.exit(2)

    from benchmarks import common

    if args.smoke:
        common.SMOKE = True
        args.quick = True

    failures = []
    records: dict = {}
    for name, module, desc in BENCHES:
        if only and name not in only:
            continue
        print(f"\n{'='*72}\n[{name}] {desc}\n{'='*72}")
        t0 = time.perf_counter()
        start = len(common.TIMINGS)
        try:
            mod = __import__(module, fromlist=["run"])
            print(mod.run(quick=args.quick))
            print(f"-- {name} done in {time.perf_counter()-t0:.1f}s")
        except Exception as e:  # keep the suite going
            failures.append(name)
            print(f"-- {name} FAILED: {type(e).__name__}: {e}")
        records[name] = common.TIMINGS[start:]

    if args.json:
        payload = {
            "smoke": args.smoke,
            "quick": args.quick,
            "failures": failures,
            "benches": records,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"\nwrote {sum(map(len, records.values()))} timing records "
              f"to {args.json}")

    if failures:
        print(f"\nFAILED benches: {failures}")
        sys.exit(1)
    print("\nall benches complete")


def _rehearse_meshes_on_cpu() -> None:
    """On a CPU host, give the multi-device benches eight host devices.

    Must run before JAX is imported: the flag is read once, at backend
    start-up.  On an accelerator the real devices are used as they are."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


if __name__ == "__main__":
    _rehearse_meshes_on_cpu()
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
