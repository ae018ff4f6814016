"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload jacobi.batch --seed 7 --seconds 30 --trace 0

Everything a cell is made of is data, found by name from
``BENCHMARK.json`` at the root of the checkout:

* the cell's configuration, ``configs[].file``, names the program that
  drives it, ``bench/programs/<program>.py``;
* its traffic mix is ``bench/traffic/<traffic>.json``;
* each per-layer metric is read by ``bench/metrics/<metric>.py``.

A later cell, mix or metric is new files plus new entries, and no edit
here.  The program runs the system under test from ``src/``, opens the
measured window through :class:`Hooks`, and after the window compares
what the timed path produced with its plain reference.

With ``--trace 0`` the result line carries the cell's end-to-end
metrics; with ``--trace 1`` the profiler records the window and the line
carries the per-layer metrics, the device's busy and window seconds and
a breakdown.  The run fails, and prints no result, where JAX finds no TPU
or fewer chips than the cell asks for.  The last lines on stderr, and the
last key of the result line, give each number compared beside its limit.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")  # fixed: part of the cache key
OUT_DIR = os.path.join(ROOT, ".bench_out")  # traces, removed once read

for _path in (BENCH, os.path.join(ROOT, "src")):  # the yardstick, the program
    if _path not in sys.path:
        sys.path.insert(0, _path)

import trace_reduce  # noqa: E402
from harness import Cell, Hooks, NoAccelerator  # noqa: E402
from peaks import peaks_for  # noqa: E402


def use_checkout_cache() -> None:
    """Before JAX is imported: keep the persistent compilation cache in
    the checkout, cache every program however short its compilation (the
    runtime compiles many small per-block programs), and turn off the
    cache's eviction, whose bookkeeping races when several worker
    threads write entries at once.  The TPU compiler writes no logs to a
    fixed path."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports ``metric``: every cell its ``workloads``
    lists, and every cell where it has no such key (``setup_s``)."""
    return cell in metric.get("workloads", (cell,))


def resolve(spec: dict, workload: str, seed: int, seconds: float,
            root: str = ROOT) -> tuple:
    """``(Cell, end-to-end metric entries, per-layer metric entries)``
    of ``workload``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} (have: "
                       f"{', '.join(sorted(cells))})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    layer = [m for m in spec["per_layer"] if _reports(m, workload)]
    cell = Cell(workload, config, traffic, seed, seconds, w["chips"])
    return cell, e2e, layer


def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform {platform!r}); "
                            f"the benchmark has no CPU fallback")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX reports "
                            f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def read_layers(layer_metrics: list, run: dict) -> dict:
    """Each per-layer metric from its reader, ``bench/metrics/<name>.py``.
    A reader that finds nothing returns None: the metric is left out of
    the line, and stderr names it, since the cell lists it."""
    out = {}
    for m in layer_metrics:
        reader = load_module(os.path.join(BENCH, "metrics",
                                          f"{m['name']}.py"),
                             f"bench_metric_{m['name']}")
        value = reader.read(run)
        if value is None:
            print(f"missing {m['name']}: its reader found nothing",
                  file=sys.stderr)
        else:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             spec: Optional[dict] = None, t_start: float = T_PROCESS) -> tuple:
    """Run one cell once; return the result line, as a dict, and the
    program's :class:`~harness.Outcome`."""
    spec = load_spec() if spec is None else spec
    cell, e2e, layer = resolve(spec, workload, seed, seconds)
    device = device_info(cell.chips)
    t_device = time.perf_counter()
    program = load_module(os.path.join(BENCH, "programs",
                                       f"{cell.config['program']}.py"),
                          f"bench_program_{cell.config['program']}")
    hooks = Hooks(trace, os.path.join(OUT_DIR, "trace"))
    outcome = program.run(cell, hooks)
    if hooks.window_open is None:
        raise RuntimeError("the program never opened its window")
    correct = bool(outcome.checks) and all(
        v <= lim for _, v, lim in outcome.checks)
    device["memory_peak_bytes"] = outcome.memory_peak_bytes
    outcome.info["compiles_in_window"] = hooks.compiles_in_window
    outcome.info["setup_to_device_s"] = t_device - t_start

    result: dict = {"correct": correct, "attempted": outcome.attempted,
                    "failed": outcome.failed}
    if trace:
        reduced = trace_reduce.reduce_trace(hooks.trace_dir)
        shutil.rmtree(hooks.trace_dir, ignore_errors=True)
        run = {"e2e": outcome.e2e, "counters": outcome.counters,
               "trace": reduced,
               "peaks": peaks_for(device["kind"]), "config": cell.config,
               "traffic": cell.traffic}
        result["metrics"] = read_layers(layer, run)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        values = dict(outcome.e2e, setup_s=hooks.window_open - t_start)
        result["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                         "unit": m["unit"]} for m in e2e}
        for name in sorted(set(values) - set(result["metrics"])):
            outcome.info[name] = values[name]  # on stderr, not judged
        result["device"] = device
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in outcome.checks}
    return result, outcome


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    use_checkout_cache()
    try:
        result, outcome = run_cell(args.workload, args.seed, args.seconds,
                                   bool(args.trace))
    except NoAccelerator as exc:
        print(f"bench: {exc}", file=sys.stderr, flush=True)
        return 3
    for key, value in outcome.info.items():
        print(f"info {key} {value!r}", file=sys.stderr)
    for name, value, limit in outcome.checks:
        verdict = "ok" if value <= limit else "FAIL"
        print(f"check {name} {value!r} limit {limit!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
