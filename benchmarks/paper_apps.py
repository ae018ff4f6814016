"""The paper's eight benchmark applications (§6, figs. 11–18) on the
DistNumPy-style runtime — each measured with latency-hiding vs blocking
communication, reporting the paper's two metrics: waiting-time share and
speedup vs sequential.

Every app is written in the DistArray API exactly the way the paper's
NumPy code is written (fig. 9/10) — no manual parallelism.  Sizes are
scaled to run the *real* block computation on one CPU in seconds; the
communication/computation timeline is accounted by the α–β cluster model
calibrated to the paper's testbed (16 nodes, GbE — core/timeline.py).
"""
from __future__ import annotations

import numpy as np

from repro.api import ExecutionPolicy, RuntimeConfig
from repro.core import Runtime
from repro.core import darray as dnp
from repro.core.timeline import GIGE_2012

__all__ = ["APPS", "run_app", "run_all"]


# ---------------------------------------------------------------------------
# applications
# ---------------------------------------------------------------------------


def fractal(n=1024, iters=20):
    """Mandelbrot set (fig. 11) — embarrassingly parallel."""
    xs = np.linspace(-2.0, 0.5, n)
    ys = np.linspace(-1.25, 1.25, n)
    cr = dnp.array(np.repeat(xs[None, :], n, axis=0))
    ci = dnp.array(np.repeat(ys[:, None], n, axis=1))
    zr = dnp.zeros((n, n))
    zi = dnp.zeros((n, n))
    count = dnp.zeros((n, n))
    for _ in range(iters):
        zr2 = zr * zr
        zi2 = zi * zi
        inside = dnp.less(zr2 + zi2, 4.0)
        count += inside
        nzr = zr2 - zi2 + cr
        nzi = 2.0 * (zr * zi) + ci
        zr = dnp.where(inside, nzr, zr)
        zi = dnp.where(inside, nzi, zi)
    return count


def black_scholes(n=2_000_000, iters=8):
    """Black–Scholes pricing (figs. 9/12) — embarrassingly parallel."""
    rng = np.random.default_rng(0)
    S = dnp.array(rng.uniform(5, 65, n))
    X = dnp.array(rng.uniform(5, 65, n))
    r, v = 0.08, 0.3

    def cnd(d):  # logistic approximation (same comm pattern as A&S poly)
        e = dnp.exp(-1.702 * d)
        return 1.0 / (1.0 + e)

    total = dnp.zeros(1)
    for i in range(1, iters + 1):
        T = i / iters
        d1 = (dnp.log(S / X) + (r + v * v / 2.0) * T) / (v * np.sqrt(T))
        d2 = d1 - v * np.sqrt(T)
        call = S * cnd(d1) - X * np.exp(-r * T) * cnd(d2)
        total += call.sum(keepdims=True) / n
    return total


def nbody_state(n):
    """The text's initial state, in its SI units: masses of 1e5 to 1e6
    kg and positions in a 1 km square, at rest.  Host float64 ``(n, 1)``
    arrays by name (``m``, ``px``, ``py``, ``vx``, ``vy``)."""
    rng = np.random.default_rng(1)
    m = rng.uniform(1e5, 1e6, (n, 1))
    px = rng.uniform(0, 1e3, (n, 1))
    py = rng.uniform(0, 1e3, (n, 1))
    return dict(m=m, px=px, py=py, vx=np.zeros((n, 1)), vy=np.zeros((n, 1)))


def nbody_arrays(state, dtype=np.float64):
    """An N-body state (host arrays by name, as :func:`nbody_state`
    gives) as runtime arrays of ``dtype``: the four ``(n, 1)`` vectors,
    the masses as a column and as a row, and the column of ones the
    outer products use."""
    n = state["m"].shape[0]
    out = {k: dnp.array(np.asarray(state[k], dtype=dtype))
           for k in ("m", "px", "py", "vx", "vy")}
    out["m_row"] = dnp.array(np.asarray(state["m"], dtype=dtype).reshape(1, n))
    out["ones"] = dnp.ones((n, 1), dtype=dtype)
    return out


def nbody_steps(s, steps, G, dt, eps):
    """``steps`` steps of the text on the runtime arrays ``s``
    (:func:`nbody_arrays`), updating its vectors in place.

    The pairwise matrices are built with SUMMA outer products; the force
    reduction uses broadcast-multiply + axis-sum, which the runtime
    executes as partial-reduce-at-owner + tiny partial transfers — the
    communication-avoiding form of the matvec (paper §6.1.1: the N-body
    matmuls are 'specialized operations')."""
    m, m_row, ones = s["m"], s["m_row"], s["ones"]
    px, py, vx, vy = s["px"], s["py"], s["vx"], s["vy"]

    def pairwise(a):
        A = dnp.matmul(a, ones, trans_b=True)  # [i, j] = a[i]
        At = dnp.matmul(ones, a, trans_b=True)  # [i, j] = a[j]
        return At - A

    for _ in range(steps):
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


def nbody(n=2048, steps=4):
    """Naive O(n²) Newtonian N-body (fig. 13) in two dimensions: the
    text's initial state, float64, stepped in its SI units; returns the
    x positions."""
    s = nbody_arrays(nbody_state(n))
    nbody_steps(s, steps, G=6.674e-11, dt=0.1, eps=1e-2)
    return s["px"]


NBODY_REFERENCE_BAND = 256  # rows of the reference's pairwise terms at a time


def nbody_reference(state, steps, params):
    """The steps of :func:`nbody_steps` on ``state`` (host arrays by
    name) in float64 NumPy, without the runtime: the pairwise terms of
    :data:`NBODY_REFERENCE_BAND` rows at a time.  ``params`` holds
    ``G``, ``dt`` and ``eps``; returns the state after ``steps``
    steps."""
    G, dt, eps = params["G"], params["dt"], params["eps"]
    m, px, py, vx, vy = (np.asarray(state[k], np.float64)[:, 0]
                         for k in ("m", "px", "py", "vx", "vy"))
    n = m.size
    for _ in range(steps):
        fx, fy = np.empty(n), np.empty(n)
        for i0 in range(0, n, NBODY_REFERENCE_BAND):
            rows = slice(i0, min(i0 + NBODY_REFERENCE_BAND, n))
            dx = px[None, :] - px[rows, None]
            dy = py[None, :] - py[rows, None]
            inv_r3 = (dx * dx + dy * dy + eps) ** -1.5
            fx[rows] = G * m[rows] * (dx * inv_r3 * m).sum(axis=1)
            fy[rows] = G * m[rows] * (dy * inv_r3 * m).sum(axis=1)
        vx = vx + dt * fx / m
        vy = vy + dt * fy / m
        px = px + dt * vx
        py = py + dt * vy
    return {k: v[:, None] for k, v in
            dict(m=m, px=px, py=py, vx=vx, vy=vy).items()}


def knn(n=4096, d=64):
    """Naive nearest-neighbour search (fig. 14) — O(n²) distances."""
    rng = np.random.default_rng(2)
    X = dnp.array(rng.random((n, d)))
    ones = dnp.ones((n, 1))
    G = dnp.matmul(X, X, trans_b=True)  # [n, n]
    sq = (X * X).sum(axis=1, keepdims=True)  # [n, 1]
    SQ = dnp.matmul(sq, ones, trans_b=True)  # row broadcast
    SQT = dnp.matmul(ones, sq, trans_b=True)  # col broadcast
    D = SQ + SQT - 2.0 * G
    big = dnp.ones((n, n)) * 1e18
    eye_mask = dnp.array(np.eye(n))
    D = dnp.where(eye_mask, big, D)
    return D.min(axis=1)


_D2Q9 = [(0, 0), (1, 0), (0, 1), (-1, 0), (0, -1), (1, 1), (-1, 1), (-1, -1), (1, -1)]
_W2 = [4 / 9] + [1 / 9] * 4 + [1 / 36] * 4


def lbm2d(h=512, w=512, steps=6):
    """D2Q9 lattice-Boltzmann channel flow (fig. 15)."""
    omega = 1.0
    f = [dnp.ones((h, w)) * wgt for wgt in _W2]
    for _ in range(steps):
        # streaming: roll each population along its lattice vector
        f = [
            dnp.roll(dnp.roll(fi, cy, axis=0), cx, axis=1)
            for fi, (cx, cy) in zip(f, _D2Q9)
        ]
        rho = f[0]
        for fi in f[1:]:
            rho = rho + fi
        ux = dnp.zeros((h, w))
        uy = dnp.zeros((h, w))
        for fi, (cx, cy) in zip(f, _D2Q9):
            if cx:
                ux = ux + float(cx) * fi
            if cy:
                uy = uy + float(cy) * fi
        ux = ux / rho
        uy = uy / rho
        usq = 1.5 * (ux * ux + uy * uy)
        for i, (cx, cy) in enumerate(_D2Q9):
            cu = 3.0 * (cx * ux + cy * uy)
            feq = _W2[i] * rho * (1.0 + cu + 0.5 * cu * cu - usq)
            f[i] = f[i] + omega * (feq - f[i])
    return f[0]


_D3Q19 = (
    [(0, 0, 0)]
    + [(s * a, s * b, s * c)
       for (a, b, c) in [(1, 0, 0), (0, 1, 0), (0, 0, 1)] for s in (1, -1)]
    + [(s1 * a1 + 0, 0, 0) for s1, a1 in []]  # placeholder
)
# full D3Q19 velocity set
_D3Q19 = [(0, 0, 0),
          (1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0), (0, 0, 1), (0, 0, -1),
          (1, 1, 0), (-1, -1, 0), (1, -1, 0), (-1, 1, 0),
          (1, 0, 1), (-1, 0, -1), (1, 0, -1), (-1, 0, 1),
          (0, 1, 1), (0, -1, -1), (0, 1, -1), (0, -1, 1)]
_W3 = [1 / 3] + [1 / 18] * 6 + [1 / 36] * 12


def lbm3d(d=64, h=64, w=64, steps=4):
    """D3Q19 lattice-Boltzmann fluid (fig. 16)."""
    omega = 1.0
    f = [dnp.ones((d, h, w)) * wgt for wgt in _W3]
    for _ in range(steps):
        f = [
            dnp.roll(dnp.roll(dnp.roll(fi, cz, 0), cy, 1), cx, 2)
            for fi, (cx, cy, cz) in zip(f, _D3Q19)
        ]
        rho = f[0]
        for fi in f[1:]:
            rho = rho + fi
        ux = dnp.zeros((d, h, w))
        uy = dnp.zeros((d, h, w))
        uz = dnp.zeros((d, h, w))
        for fi, (cx, cy, cz) in zip(f, _D3Q19):
            if cx:
                ux = ux + float(cx) * fi
            if cy:
                uy = uy + float(cy) * fi
            if cz:
                uz = uz + float(cz) * fi
        ux, uy, uz = ux / rho, uy / rho, uz / rho
        usq = 1.5 * (ux * ux + uy * uy + uz * uz)
        for i, (cx, cy, cz) in enumerate(_D3Q19):
            cu = 3.0 * (cx * ux + cy * uy + cz * uz)
            feq = _W3[i] * rho * (1.0 + cu + 0.5 * cu * cu - usq)
            f[i] = f[i] + omega * (feq - f[i])
    return f[0]


def jacobi(n=2048, nrhs=2048, iters=6):
    """Jacobi iteration on systemS of linear equations (fig. 17): one
    [n,n] matmul per sweep over the nrhs right-hand sides (SUMMA)."""
    rng = np.random.default_rng(3)
    A = rng.random((n, n)) + n * np.eye(n)
    R_np = A - np.diag(np.diag(A))
    inv_d = (1.0 / np.diag(A)).reshape(n, 1)
    R = dnp.array(R_np)
    b = dnp.array(rng.random((n, nrhs)))
    invd = dnp.array(inv_d)
    x = dnp.zeros((n, nrhs))
    for _ in range(iters):
        x = (b - dnp.matmul(R, x)) * invd
    return x


def jacobi_stencil(n=4096, iters=6):
    """Jacobi with stencil views (figs. 10/18) — the paper's flagship."""
    full = dnp.zeros((n + 2, n + 2))
    full[0, :] = 1.0
    full[:, 0] = 1.0
    for _ in range(iters):
        work = 0.2 * (
            full[1:-1, 1:-1]
            + full[0:-2, 1:-1]
            + full[2:, 1:-1]
            + full[1:-1, 0:-2]
            + full[1:-1, 2:]
        )
        full[1:-1, 1:-1] = work
    return full


# app -> (fn, default kwargs, distribution block size).  Block sizes follow
# the paper: the array is split so there are ~4-16× more blocks than the
# 16 processes (strong scaling, §6.1.2); problem sizes chosen so the
# per-block compute sits in the paper's regime (ms-scale blocks).
APPS = {
    "fractal": (fractal, {}, 128),
    "black_scholes": (black_scholes, {}, 65536),
    "nbody": (nbody, {}, 256),
    "knn": (knn, {}, 512),
    "lbm2d": (lbm2d, {}, 64),
    "lbm3d": (lbm3d, {}, 16),
    "jacobi": (jacobi, {}, 256),
    "jacobi_stencil": (jacobi_stencil, {}, 512),
}


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------


_UNSET = object()


def run_app(
    name: str,
    *,
    mode=_UNSET,
    nprocs=_UNSET,
    block_size=_UNSET,
    execute=_UNSET,
    fusion=_UNSET,
    cluster=_UNSET,
    flush_backend=_UNSET,
    exec_backend=_UNSET,
    exec_channel=_UNSET,
    exec_latency=_UNSET,
    passes=_UNSET,
    config: RuntimeConfig = None,
    policy: ExecutionPolicy = None,
    **kw,
):
    """Run one paper app and return ``(stats, result)``.

    Preferred invocation passes a :class:`RuntimeConfig` /
    :class:`ExecutionPolicy` pair; the individual keyword arguments
    remain as shorthand and are folded into the config objects when no
    explicit object is given.  Mixing an explicit object with its
    shorthand kwargs is refused (the kwarg would be silently ignored).
    """
    fn, defaults, default_bs = APPS[name]
    kwargs = {**defaults, **kw}
    cfg_kw = dict(nprocs=nprocs, block_size=block_size, execute=execute,
                  fusion=fusion)
    pol_kw = dict(mode=mode, cluster=cluster, flush_backend=flush_backend,
                  exec_backend=exec_backend, exec_channel=exec_channel,
                  exec_latency=exec_latency, passes=passes)
    if config is None:
        bs = cfg_kw["block_size"]
        config = RuntimeConfig(
            nprocs=16 if cfg_kw["nprocs"] is _UNSET else cfg_kw["nprocs"],
            block_size=default_bs if bs in (_UNSET, None) else bs,
            fusion=False if cfg_kw["fusion"] is _UNSET else cfg_kw["fusion"],
            execute=True if cfg_kw["execute"] is _UNSET else cfg_kw["execute"],
        )
    else:
        clash = [k for k, v in cfg_kw.items() if v is not _UNSET]
        if clash:
            raise TypeError(
                f"run_app: got both config= and shorthand kwarg(s) {clash} — "
                f"put them on the RuntimeConfig"
            )
    if policy is None:
        policy = ExecutionPolicy(
            scheduler="latency_hiding" if mode is _UNSET else mode,
            flush="sim" if flush_backend is _UNSET else flush_backend,
            backend="numpy" if exec_backend is _UNSET else exec_backend,
            channel=None if exec_channel is _UNSET else exec_channel,
            latency=0.0 if exec_latency is _UNSET else exec_latency,
            cluster=GIGE_2012 if cluster is _UNSET else cluster,
            passes="auto" if passes is _UNSET else passes,
        )
    else:
        clash = [k for k, v in pol_kw.items() if v is not _UNSET]
        if clash:
            raise TypeError(
                f"run_app: got both policy= and shorthand kwarg(s) {clash} — "
                f"use policy.replace(...) instead"
            )
    with Runtime.from_config(config, policy) as rt:
        out = fn(**kwargs)
        result = np.asarray(out) if config.execute else None
        stats = rt.stats()
    return stats, result


def run_all(nprocs: int = 16, execute: bool = True, block_size=None):
    rows = []
    for name in APPS:
        st_lh, res_lh = run_app(name, mode="latency_hiding", nprocs=nprocs,
                                execute=execute, block_size=block_size)
        st_bl, res_bl = run_app(name, mode="blocking", nprocs=nprocs,
                                execute=execute, block_size=block_size)
        if execute and res_lh is not None:
            assert np.allclose(res_lh, res_bl, equal_nan=True), f"{name}: mode changes result!"
        rows.append(
            dict(
                app=name,
                wait_lh=st_lh.wait_fraction,
                wait_blocking=st_bl.wait_fraction,
                speedup_lh=st_lh.speedup,
                speedup_blocking=st_bl.speedup,
                makespan_lh=st_lh.makespan,
                makespan_blocking=st_bl.makespan,
                comm_mb=st_lh.comm_bytes / 1e6,
            )
        )
    return rows
