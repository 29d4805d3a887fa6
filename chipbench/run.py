"""The chip benchmark's command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``:
each number the correctness check compared, beside its limit.  Without a
TPU, or with fewer chips than the cell asks for, it exits 2 and prints
no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import cells

    bench = cells.benchmark(ROOT)
    cell, cfg, mix = cells.cell(args.workload, bench)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: no program under test at {ROOT}/src/repro; "
              "nothing run", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"chipbench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              "device(s); nothing run", file=sys.stderr)
        return 2

    from chipbench import runner

    cache = runner.configure_jax(ROOT)
    runner.log(f"cell {cell['name']} seed {args.seed} {args.seconds:g} s "
               f"trace {args.trace}; {devices[0].device_kind} "
               f"x{len(devices)}; compile cache {cache}")
    out = runner.execute(cell, cfg, mix, seed=args.seed,
                         seconds=args.seconds, traced=bool(args.trace),
                         devices=devices, t_process=T_PROCESS, bench=bench)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
