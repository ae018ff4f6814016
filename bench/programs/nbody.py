"""The paper's direct-summation N-body (arXiv:1201.3804 §6, fig. 13; the
``nbody`` of ``benchmarks/paper_apps.py``), driven through the runtime,
and its plain float64 reference.

The program is sequential NumPy over ``repro`` arrays: all-pairs
gravity in two dimensions, the pairwise differences built by rank-1
outer products (``matmul`` with a column of ones), the forces summed
along each row, and the ``(n, 1)`` velocities and positions updated.
Every ``snapshot_every`` steps it reads a snapshot back on the host: the
four state vectors and the energy ``np.sum(m * (vx*vx + vy*vy))``, as a
simulation that writes restart files and logs its energy does.  The
window runs whole chunks of ``snapshot_every`` steps until its time is
up; the chunk in flight is finished and counted.

The reference is the same arithmetic in float64 NumPy on the host,
independent of the runtime, on row bands in a few threads.  It restarts
every chunk from the state the program read at that chunk's start, so a
chunk's gap is that chunk's own.
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
REFERENCE_BAND = 128  # rows of the pairwise terms at a time
VECTORS = ("px", "py", "vx", "vy")


def initial_state(seed: int, n: int, dtype) -> dict:
    """``n`` bodies in N-body units, host ``(n, 1)`` arrays by name:
    masses uniform from the seed and normalised to total 1, positions
    uniform in the unit square, at rest."""
    rng = np.random.default_rng(seed)
    m = rng.random((n, 1))
    m /= m.sum()
    px = rng.random((n, 1))
    py = rng.random((n, 1))
    zeros = np.zeros((n, 1))
    return {k: v.astype(dtype) for k, v in
            dict(m=m, px=px, py=py, vx=zeros, vy=zeros).items()}


def arrays(host: dict, dtype) -> dict:
    """The state as the program's arrays: the masses as a column and as
    a row, positions from ``host``, velocities zero, and the column of
    ones the outer products use, every one of ``dtype``."""
    import repro

    n = host["m"].shape[0]
    return dict(m=repro.array(host["m"]),
                m_row=repro.array(host["m"].reshape(1, n)),
                px=repro.array(host["px"]), py=repro.array(host["py"]),
                vx=repro.zeros((n, 1), dtype=dtype),
                vy=repro.zeros((n, 1), dtype=dtype),
                ones=repro.ones((n, 1), dtype=dtype))


def step(s: dict, G: float, dt: float, eps: float) -> None:
    """One step of the text, in place on the arrays of ``s``."""
    from repro import api

    m, m_row, ones = s["m"], s["m_row"], s["ones"]
    px, py, vx, vy = s["px"], s["py"], s["vx"], s["vy"]

    def pairwise(a):
        A = api.matmul(a, ones, trans_b=True)  # [i, j] = a[i]
        At = api.matmul(ones, a, trans_b=True)  # [i, j] = a[j]
        return At - A

    dx = pairwise(px)
    dy = pairwise(py)
    r2 = dx * dx + dy * dy + eps
    inv_r3 = r2 ** -1.5
    fx = G * m * (dx * inv_r3 * m_row).sum(axis=1, keepdims=True)
    fy = G * m * (dy * inv_r3 * m_row).sum(axis=1, keepdims=True)
    vx += dt * fx / m
    vy += dt * fy / m
    px += dt * vx
    py += dt * vy


def energy(s: dict):
    """The energy the program logs (lazy): ``sum(m * |v|^2)``."""
    return np.sum(s["m"] * (s["vx"] * s["vx"] + s["vy"] * s["vy"]))


def reference(m: np.ndarray, start: dict, steps: int, params: dict,
              pool: ThreadPoolExecutor) -> dict:
    """``steps`` steps from ``start`` (the four vectors by name) with
    masses ``m``, in float64; the pairwise terms of each band of
    :data:`REFERENCE_BAND` rows on ``pool``.  Returns the four vectors
    and the energy."""
    G, dt, eps = params["G"], params["dt"], params["eps"]
    mass = m.astype(np.float64)[:, 0]
    px, py, vx, vy = (start[k].astype(np.float64)[:, 0] for k in VECTORS)
    n = mass.size
    bands = [slice(i, min(i + REFERENCE_BAND, n))
             for i in range(0, n, REFERENCE_BAND)]
    fx, fy = np.empty(n), np.empty(n)

    def band(rows):
        dx = px[None, :] - px[rows, None]
        dy = py[None, :] - py[rows, None]
        inv_r3 = (dx * dx + dy * dy + eps) ** -1.5
        fx[rows] = G * mass[rows] * (dx * inv_r3 * mass).sum(axis=1)
        fy[rows] = G * mass[rows] * (dy * inv_r3 * mass).sum(axis=1)

    for _ in range(steps):
        list(pool.map(band, bands))
        vx = vx + dt * fx / mass
        vy = vy + dt * fy / mass
        px = px + dt * vx
        py = py + dt * vy
    out = {k: v[:, None] for k, v in zip(VECTORS, (px, py, vx, vy))}
    out["energy"] = math.fsum(mass * (vx * vx + vy * vy))
    return out


def compare(got: list, want: list) -> dict:
    """The numbers compared, each the worst over the snapshots: the
    largest velocity gap over the largest reference speed component,
    the largest position gap, and the largest relative energy gap."""
    if len(got) != len(want) or not got:
        return dict.fromkeys(("velocity_max_rel_err", "position_max_abs_err",
                              "energy_max_rel_err"), math.inf)
    vel = pos = ene = 0.0

    def gap(g, w, keys):
        return max(float(np.max(np.abs(g[k].astype(np.float64) - w[k])))
                   for k in keys)

    for g, w in zip(got, want):
        vmax = max(float(np.max(np.abs(w[k]))) for k in ("vx", "vy"))
        vel = max(vel, gap(g, w, ("vx", "vy")) / vmax if vmax else math.inf)
        pos = max(pos, gap(g, w, ("px", "py")))
        ene = max(ene, abs(g["energy"] - w["energy"]) / abs(w["energy"])
                  if w["energy"] else math.inf)
    out = {"velocity_max_rel_err": vel, "position_max_abs_err": pos,
           "energy_max_rel_err": ene}
    if not all(math.isfinite(v) for v in out.values()):
        out = dict.fromkeys(out, math.inf)
    return out


def run(cell, hooks):
    import repro
    from repro.api import ExecutionPolicy, RuntimeConfig

    from harness import Outcome, payload_delta

    cfg, traffic = cell.config, cell.traffic
    if cfg["dims"] != 2:
        raise ValueError("the text is two-dimensional (dims 2)")
    dtype = np.dtype(cfg["dtype"])
    n = cfg["bodies"]
    params = {k: float(cfg[k]) for k in ("G", "dt", "eps")}
    host = initial_state(cell.seed, n, dtype)
    k = int(traffic["snapshot_every"])
    config = RuntimeConfig(nprocs=cfg["nprocs"], block_size=cfg["block_size"],
                           fusion=cfg["fusion"])
    policy = ExecutionPolicy(**cfg["policy"])

    starts = [{v: host[v] for v in VECTORS}]  # each chunk's first state
    snapshots: list = []  # what the program read after each chunk
    record_s = [0.0]
    with repro.runtime(config, policy) as rt:
        s = arrays(host, dtype)

        def chunk():
            t = time.perf_counter()
            with hooks.span("record"):
                for _ in range(k):
                    step(s, **params)
                e = energy(s)
            record_s[0] += time.perf_counter() - t
            with hooks.span("drain"):
                repro.wait(e, *(s[v] for v in VECTORS))
                snap = {v: np.array(s[v]) for v in VECTORS}  # a copy
                snap["energy"] = float(e)
            snapshots.append(snap)
            starts.append({v: snap[v] for v in VECTORS})
            return k

        chunk()  # set-up: every shape the window uses
        n_warm = len(snapshots) * k
        stats0, wait0 = rt.backend_stats(), rt.stats()
        busy0, elapsed0 = wait0.total_compute, wait0.elapsed
        record_s[0] = 0.0
        with hooks.window():
            steps, t0, t_end = run_window(cell.seconds, chunk)
        stats1, wait1 = rt.backend_stats(), rt.stats()
        memory = hooks.memory_peak_bytes()
    paths = payload_delta(stats0, stats1)
    counters = {
        "sweeps": steps,
        "window_s": t_end - t0,
        "record_s": record_s[0],
        "payloads": sum(paths.values()),
        "worker_busy_s": wait1.total_compute - busy0,
        "worker_elapsed_s": wait1.elapsed - elapsed0,
        "nworkers": wait1.nworkers,
        "bodies": n,
        "itemsize": dtype.itemsize,
    }
    t = time.perf_counter()
    with ThreadPoolExecutor(max_workers=REFERENCE_THREADS) as pool:
        want = [reference(host["m"], start, k, params, pool)
                for start in starts[:len(snapshots)]]
    gaps = compare(snapshots, want)
    limits = cfg["limits"]
    checks = [(name, gaps[name], limits[name]) for name in sorted(limits)]
    info = {"steps_warm": n_warm, "steps_window": steps,
            "snapshots": len(snapshots),
            "reference_s": time.perf_counter() - t,
            "payload_paths": paths}
    return Outcome(e2e={"sweep_ms": sweep_ms(steps, t0, t_end)},
                   counters=counters, checks=checks, attempted=steps,
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
    from repro import api

    bf16 = ml_dtypes.bfloat16

    def matmul(a, b, trans_a=False, trans_b=False):
        return np.matmul(a.T if trans_a else a,
                         b.T if trans_b else b).astype(bf16)

    saved = {k: getattr(repro, k)
             for k in ("array", "runtime", "wait", "zeros", "ones")}
    saved_matmul = api.matmul
    repro.array = lambda host: np.array(host, dtype=bf16)
    repro.runtime = lambda *_: contextlib.nullcontext(_PlainRuntime())
    repro.wait = lambda *_: None
    repro.zeros = lambda shape, dtype=None: np.zeros(shape, bf16)
    repro.ones = lambda shape, dtype=None: np.ones(shape, bf16)
    api.matmul = matmul
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(repro, k, v)
        api.matmul = saved_matmul
