"""The paper's Jacobi stencil (arXiv:1201.3804 §6, figs. 10/18; the
``jacobi_stencil`` of the DistNumPy/Bohrium benchpress suite), driven
through the runtime, and its plain float64 reference.

The program is sequential NumPy over a ``repro`` array: a 5-point sweep
of the interior with the boundary rows and columns held fixed, and every
``check_every`` sweeps the residual ``np.sum(np.abs(work - center))``
read back on the host, as upstream's ``while delta > eps`` loop does.
The window runs whole chunks of ``check_every`` sweeps until its time is
up; the chunk in flight is finished and counted.

The reference is the same arithmetic in float64 NumPy on the host,
independent of the runtime, on row bands in a few threads.
"""
from __future__ import annotations

import contextlib
import math
import time
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import numpy as np

from measure import run_window, sweep_ms

REFERENCE_THREADS = 8


def initial_grid(seed: int, n: int, dtype) -> np.ndarray:
    """An ``n`` x ``n`` grid: interior uniform in [0, 1) from the seed,
    top row and left column 1, bottom row and right column 0."""
    grid = np.zeros((n, n), dtype=dtype)
    rng = np.random.default_rng(seed)
    grid[1:-1, 1:-1] = rng.random((n - 2, n - 2), dtype=np.float32)
    grid[0, :] = 1.0
    grid[:, 0] = 1.0
    return grid


def stencil_views(grid):
    """center, north, south, west, east, as upstream names them."""
    return (grid[1:-1, 1:-1], grid[:-2, 1:-1], grid[2:, 1:-1],
            grid[1:-1, :-2], grid[1:-1, 2:])


def sweep(views, check: bool):
    """One sweep; returns the lazy residual where ``check``."""
    c, n, s, w, e = views
    if not check:
        c[:] = 0.2 * ((((c + n) + s) + w) + e)
        return None
    work = 0.2 * ((((c + n) + s) + w) + e)
    delta = np.sum(np.abs(work - c))
    c[:] = work
    return delta


def chunk_schedule(k: int) -> list:
    """A chunk of ``k`` sweeps: only the last checks the residual."""
    return [False] * (k - 1) + [True]


def reference(init: np.ndarray, schedule: list,
              threads: int = REFERENCE_THREADS):
    """The sweeps of ``schedule`` (one bool per sweep: check or not) on
    ``init`` in float64; returns ``(residuals, final grid)``.  Each
    residual is summed per row band, and the bands in Python floats."""
    a = init.astype(np.float64)
    b = a.copy()
    rows = a.shape[0] - 2
    step = max(1, math.ceil(rows / threads))
    bands = [(r, min(r + step, rows + 1)) for r in range(1, rows + 1, step)]

    def band(r0, r1, check):
        out = b[r0:r1, 1:-1]
        np.add(a[r0:r1, 1:-1], a[r0 - 1:r1 - 1, 1:-1], out=out)
        out += a[r0 + 1:r1 + 1, 1:-1]
        out += a[r0:r1, :-2]
        out += a[r0:r1, 2:]
        out *= 0.2
        if check:
            return float(np.abs(out - a[r0:r1, 1:-1]).sum())
        return 0.0

    residuals = []
    with ThreadPoolExecutor(max_workers=threads) as pool:
        for check in schedule:
            parts = list(pool.map(lambda rr: band(rr[0], rr[1], check),
                                  bands))
            a, b = b, a
            if check:
                residuals.append(math.fsum(parts))
    return residuals, a


def compare(got_residuals, got_grid, want_residuals, want_grid) -> dict:
    """The numbers compared: the largest relative gap of a residual and
    the largest absolute gap of a grid value."""
    if len(got_residuals) != len(want_residuals):
        rel = math.inf
    else:
        rel = max((abs(g - w) / abs(w) if w else abs(g)
                   for g, w in zip(got_residuals, want_residuals)),
                  default=math.inf)
    if got_grid.shape != want_grid.shape:
        err = math.inf
    else:
        err = float(np.max(np.abs(got_grid.astype(np.float64)
                                  - want_grid.astype(np.float64))))
    if not (math.isfinite(rel) and math.isfinite(err)):
        rel = err = math.inf
    return {"residual_max_rel_err": rel, "grid_max_abs_err": err}


def run(cell, hooks):
    import repro
    from repro.api import ExecutionPolicy, RuntimeConfig

    from harness import Outcome, payload_delta

    cfg, traffic = cell.config, cell.traffic
    dtype = np.dtype(cfg["dtype"])
    host = initial_grid(cell.seed, cfg["grid"], dtype)
    k = int(traffic["check_every"])
    config = RuntimeConfig(nprocs=cfg["nprocs"], block_size=cfg["block_size"],
                           fusion=cfg["fusion"])
    policy = ExecutionPolicy(**cfg["policy"])

    schedule: list = []  # one bool per sweep run, warm-up included
    residuals: list = []
    record_s = [0.0]
    with repro.runtime(config, policy) as rt:
        grid = repro.array(host)
        views = stencil_views(grid)

        def chunk(plan):
            t = time.perf_counter()
            with hooks.span("record"):
                delta = None
                for check in plan:
                    d = sweep(views, check)
                    delta = d if d is not None else delta
            record_s[0] += time.perf_counter() - t
            with hooks.span("drain"):
                residuals.append(float(delta))
            schedule.extend(plan)
            return len(plan)

        def finish():
            with hooks.span("drain"):
                repro.wait(grid)

        # set-up: every shape the window uses, a plain and a checked sweep
        chunk(chunk_schedule(min(k, 2)))
        finish()
        n_warm = len(schedule)
        stats0, wait0 = rt.backend_stats(), rt.stats()
        busy0, elapsed0 = wait0.total_compute, wait0.elapsed
        record_s[0] = 0.0
        with hooks.window():
            sweeps, t0, t_end = run_window(
                cell.seconds, lambda: chunk(chunk_schedule(k)), finish)
        stats1, wait1 = rt.backend_stats(), rt.stats()
        memory = hooks.memory_peak_bytes()
        got = np.asarray(grid)
    paths = payload_delta(stats0, stats1)
    # the window's drains, from the runtime's accumulated WaitStats
    busy = wait1.total_compute - busy0
    elapsed = wait1.elapsed - elapsed0
    counters = {
        "sweeps": sweeps,
        "window_s": t_end - t0,
        "record_s": record_s[0],
        "payloads": sum(paths.values()),
        "worker_busy_s": busy,
        "worker_elapsed_s": elapsed,
        "nworkers": wait1.nworkers,
        "grid": cfg["grid"],
        "itemsize": dtype.itemsize,
    }
    want_res, want_grid = reference(host, schedule)
    gaps = compare(residuals, got, want_res, want_grid)
    limits = cfg["limits"]
    checks = [(name, gaps[name], limits[name]) for name in sorted(limits)]
    info = {"sweeps_warm": n_warm, "sweeps_window": sweeps,
            "checks_window": len(residuals) - 1,
            "payload_paths": paths}
    return Outcome(e2e={"sweep_ms": sweep_ms(sweeps, t0, t_end)},
                   counters=counters, checks=checks, attempted=sweeps,
                   failed=0, memory_peak_bytes=memory, info=info)


class _PlainRuntime:
    """What the program reads of a runtime, for plain NumPy arrays."""

    @staticmethod
    def backend_stats() -> dict:
        return {}

    @staticmethod
    def stats():
        return SimpleNamespace(total_compute=0.0, elapsed=0.0, nworkers=0)


@contextlib.contextmanager
def control():
    """The control: within this block the program's own text runs on
    plain NumPy arrays in bfloat16, the precision below the
    configuration's float32, in the runtime's place.  A run of the cell
    inside it goes through the harness as any run does, and has to come
    out not correct."""
    import ml_dtypes
    import repro

    saved = {k: getattr(repro, k) for k in ("array", "runtime", "wait")}
    repro.array = lambda host: np.array(host, dtype=ml_dtypes.bfloat16)
    repro.runtime = lambda *_: contextlib.nullcontext(_PlainRuntime())
    repro.wait = lambda *_: None
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(repro, k, v)
