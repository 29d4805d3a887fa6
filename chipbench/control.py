"""Readings for the limits of the correctness check, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> [--fault <name> | --control]

Runs the cell once per seed, in one process, and prints one JSON line per
seed with every number the check compared and the verdict ``correct`` of
``check.judge`` on them:

* with neither option: the program as it is (sound runs: the lower
  readings of each limit);
* ``--control``: the reference with H held in the precision below the
  configuration's counts (``check.control_dtype``: bfloat16, or
  float32 for frames past 2**24 pixels), put in the
  program's place for the same frames and queries (the upper readings;
  the verdict has to be false);
* ``--fault <name>``: the program with a fault of ``faults.py`` planted
  (the verdict has to be false).

The benchmark's own runs never run this.  The last line sums up, per
number, the largest reading over the seeds.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    import argparse
    import contextlib

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--fault")
    mode.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from chipbench import cells, check, faults, runner

    bench = cells.benchmark(ROOT)
    cell, cfg, mix = cells.cell(args.workload, bench)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("control: no TPU with enough chips", file=sys.stderr)
        return 2
    runner.configure_jax(ROOT)
    worst = {}
    plant = (faults.FAULTS[args.fault] if args.fault
             else contextlib.nullcontext)
    with plant():
        for seed in (int(s) for s in args.seeds.split(",")):
            kept = []
            out = runner.execute(cell, cfg, mix, seed=seed,
                                 seconds=args.seconds, traced=False,
                                 devices=devices,
                                 t_process=time.perf_counter(), bench=bench,
                                 samples_out=kept)
            readings = {k: v["value"] for k, v in out["checks"].items()}
            correct = out["correct"]
            if args.control:
                readings.update(check.control(kept, cfg,
                                              check.control_dtype(cfg)))
                correct, _ = check.judge(readings, cfg["limits"])
            for k, v in readings.items():
                worst[k] = max(worst.get(k, v), v)
            print(json.dumps({"seed": seed, "mode": args.fault or (
                "control" if args.control else "program"),
                "correct": correct, "readings": readings,
                "metrics": out["metrics"]}), flush=True)
    print(json.dumps({"worst": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
