"""Readings that the limits of ``correct`` are set from: the numbers the
program's own runs compare, over many seeds in one process, and the same
numbers for the control (the program's ``control()``: its plain
reference put in its place, computed in the precision below the
configuration's).

    python3 bench/readings.py --workload jacobi.batch --seconds 10 \
        --seeds 101,102,103 [--control]

One JSON line per seed: the numbers compared and the harness's verdict.
With ``--control`` every run goes through the harness inside the
program's ``control()``, and its verdict has to read false.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    run.use_checkout_cache()
    spec = run.load_spec()
    cell, _, _ = run.resolve(spec, args.workload, 0, args.seconds)
    program = run.load_module(
        os.path.join(BENCH, "programs", f"{cell.config['program']}.py"),
        "readings_program")
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        with program.control() if args.control else contextlib.nullcontext():
            result, outcome = run.run_cell(args.workload, seed, args.seconds,
                                           False, spec=spec, t_start=t)
        line = {"seed": seed, "control": args.control,
                "correct": result["correct"],
                "checks": {k: v["value"] for k, v in result["checks"].items()},
                "metrics": {k: v["value"] for k, v in result["metrics"].items()},
                "info": {k: (float(v) if hasattr(v, "dtype") else v)
                         for k, v in outcome.info.items()},
                "run_s": time.perf_counter() - t}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
