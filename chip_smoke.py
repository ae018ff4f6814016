"""Bring-up smoke test: the runtime's main path on a TPU, end to end.

    python chip_smoke.py            # one chip: device, kernels, runtime, serve
    python chip_smoke.py --chips 4  # four chips: the sharded Jacobi sweep only

Phases, in order; the first failure exits non-zero:

* device  — JAX must report a TPU; there is no CPU fallback.
* kernels — the fused ``jacobi_sweep`` on an 8192x8192 f32 grid against
  ``jacobi_sweep_ref``, and ``stencil5_block`` at a full runtime block
  and at the ragged fragment shapes the runtime phase produces, against
  a plain ``jnp`` sum.  Both compiled, never interpreted.
* runtime — the paper's Jacobi stencil written as plain NumPy over
  ``repro.zeros(..., dtype=np.float32)``, 8192x8192, 16 ranks, 1024
  blocks, 3 sweeps, drained by ``repro.runtime(flush="async",
  backend="jax")``; read back with ``np.asarray`` and compared with a
  float64 NumPy loop (max abs error <= 1e-5).  The stencil blocks must
  run through the compiled Pallas kernel, and no map or matmul payload
  may fall back to the host.
* serve   — ``repro.Server(backend="jax")``, 2 tenant threads x 4
  stencil requests over private 2048x2048 f32 arrays, every answer
  checked against its float64 closed form (rtol = atol = 1e-5).

``--chips 4`` runs only ``repro.comm.collectives.jacobi_step_sharded``
(overlap "ring" and "none") under ``jax.shard_map`` on a 4-device mesh
over a row-sharded 8192x8192 f32 grid, against a one-device reference.

Everything runs in this one process, which holds the chip(s); nothing
starts a child.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

GRID = 8192  # full grid side of the kernel, runtime and sharded phases
RUNTIME_ATOL = 1e-5  # f32 sweeps against the float64 NumPy loop
SERVE_TOL = 1e-5  # rtol and atol of every served answer
KERNEL_ATOL = 1e-6  # f32 kernels against f32 jnp in the same order


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def phase_device(min_count: int) -> dict:
    import jax

    from repro.kernels import resolve_interpret

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"JAX found no TPU (platform {dev.platform!r}); this smoke "
            f"test has no CPU fallback"
        )
    check(len(devices) >= min_count,
          f"{min_count} chips needed, JAX reports {len(devices)}")
    check(resolve_interpret(None) is False,
          "kernel wrappers would run the Pallas interpreter here")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    log("device", f"platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    return info


def phase_kernels(n: int = GRID, block: int = 1024) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.stencil import jacobi_sweep, jacobi_sweep_ref, stencil5_block

    t0 = time.perf_counter()
    x = jax.random.uniform(jax.random.key(0), (n, n), jnp.float32)
    got = jacobi_sweep(x)
    want = jax.jit(jacobi_sweep_ref)(x)
    err = float(jnp.max(jnp.abs(got - want)))
    log("kernels", f"jacobi_sweep {n}x{n} f32: max abs err {err!r} "
        f"(limit {KERNEL_ATOL}), {time.perf_counter() - t0:.3f} s")
    check(err <= KERNEL_ATOL, f"jacobi_sweep error {err!r}")

    shapes = [(block, block), (block - 1, block), (1, block),
              (block - 1, block - 1), (block, 1), (1, 1)]
    for shape in shapes:
        t0 = time.perf_counter()
        keys = jax.random.split(jax.random.key(1), 5)
        xs = [jax.random.uniform(k, shape, jnp.float32) for k in keys]
        got = stencil5_block(*xs, weight=0.2)
        want = 0.2 * ((((xs[0] + xs[1]) + xs[2]) + xs[3]) + xs[4])
        err = float(jnp.max(jnp.abs(got - want)))
        log("kernels", f"stencil5_block {shape[0]}x{shape[1]}: max abs err "
            f"{err!r}, {time.perf_counter() - t0:.3f} s")
        check(got.shape == shape and err <= KERNEL_ATOL,
              f"stencil5_block {shape} error {err!r}")


def jacobi_stencil(n: int, iters: int) -> np.ndarray:
    """The paper's figs. 10/18 stencil, written like sequential NumPy."""
    import repro

    full = repro.zeros((n + 2, n + 2), dtype=np.float32)
    full[0, :] = 1.0
    full[:, 0] = 1.0
    for _ in range(iters):
        full[1:-1, 1:-1] = 0.2 * (
            full[1:-1, 1:-1]
            + full[0:-2, 1:-1]
            + full[2:, 1:-1]
            + full[1:-1, 0:-2]
            + full[1:-1, 2:]
        )
    return np.asarray(full)  # readback triggers the flush


def jacobi_reference(n: int, iters: int) -> np.ndarray:
    """The same sweeps as a float64 NumPy loop."""
    full = np.zeros((n + 2, n + 2))
    full[0, :] = 1.0
    full[:, 0] = 1.0
    for _ in range(iters):
        full[1:-1, 1:-1] = 0.2 * (
            full[1:-1, 1:-1] + full[0:-2, 1:-1] + full[2:, 1:-1]
            + full[1:-1, 0:-2] + full[1:-1, 2:]
        )
    return full


def phase_runtime(n: int = GRID - 2, nprocs: int = 16, block: int = 1024,
                  iters: int = 3) -> dict:
    import repro
    from repro.api import ExecutionPolicy, RuntimeConfig

    config = RuntimeConfig(nprocs=nprocs, block_size=block, fusion=True)
    policy = ExecutionPolicy(flush="async", backend="jax", channel="async",
                             latency=0.0)
    t0 = time.perf_counter()
    with repro.runtime(config, policy) as rt:
        got = jacobi_stencil(n, iters)
        counts = rt.backend_stats()
        summary = rt.stats().summary()
    elapsed = time.perf_counter() - t0
    want = jacobi_reference(n, iters)
    err = float(np.max(np.abs(got.astype(np.float64) - want)))
    log("runtime", f"jacobi {n + 2}x{n + 2} f32, {iters} sweeps, {nprocs} "
        f"ranks, block {block}: {elapsed:.3f} s")
    log("runtime", f"max abs err vs float64 NumPy {err!r} "
        f"(limit {RUNTIME_ATOL})")
    log("runtime", f"payloads: {counts}")
    print(summary, flush=True)
    check(got.shape == want.shape and got.dtype == np.float32,
          f"readback {got.shape} {got.dtype}")
    check(err <= RUNTIME_ATOL, f"runtime error {err!r}")
    check(counts.get("n_pallas", 0) >= 1,
          f"no stencil block ran through the Pallas kernel: {counts}")
    check(counts.get("n_host_untranslated") == 0,
          f"map payloads fell back to the host: {counts}")
    return counts


def phase_serve(tenants: int = 2, requests: int = 4, n: int = 2048,
                nprocs: int = 4, block: int = 512) -> dict:
    import repro
    from repro.launch.serve import tenant_workload

    errors: list = []
    failures: list = []
    lock = threading.Lock()

    def client(srv, idx: int) -> None:
        try:
            fn, expect = tenant_workload(idx, n, dtype=np.float32)
            sess = srv.session(f"tenant-{idx}")
            for _ in range(requests):
                got = sess.request(fn).result()
                ok = got.shape == expect.shape and np.allclose(
                    got, expect, rtol=SERVE_TOL, atol=SERVE_TOL)
                err = float(np.max(np.abs(got - expect)))
                with lock:
                    errors.append(err)
                    if not ok:
                        failures.append((idx, err))
        except Exception as exc:  # reported by the main thread
            with lock:
                failures.append((idx, repr(exc)))

    t0 = time.perf_counter()
    srv = repro.Server(nprocs=nprocs, block_size=block, backend="jax",
                       latency=0.0)
    with srv:
        threads = [threading.Thread(target=client, args=(srv, i))
                   for i in range(tenants)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        check(not any(t.is_alive() for t in threads), "a tenant hung")
        counts = srv.runtime.backend_stats()
    elapsed = time.perf_counter() - t0
    log("serve", f"{tenants} tenants x {requests} requests, {n}x{n} f32: "
        f"{elapsed:.3f} s, max abs err {max(errors, default=float('nan'))!r} "
        f"(rtol=atol={SERVE_TOL})")
    log("serve", f"payloads: {counts}")
    check(not failures, f"wrong or failed answers: {failures}")
    check(len(errors) == tenants * requests,
          f"{len(errors)} of {tenants * requests} answers checked")
    check(counts.get("n_host_untranslated") == 0,
          f"map payloads fell back to the host: {counts}")
    return counts


def phase_sharded(n: int = GRID, chips: int = 4, steps: int = 3) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.comm.collectives import jacobi_step_sharded
    from repro.kernels.stencil import jacobi_sweep_ref

    devices = jax.devices()[:chips]
    mesh = Mesh(np.array(devices), ("x",))
    rows = NamedSharding(mesh, P("x", None))
    x0 = jax.random.uniform(jax.random.key(2), (n, n), jnp.float32)

    t0 = time.perf_counter()
    ref_step = jax.jit(jacobi_sweep_ref)
    want = x0
    for _ in range(steps):
        want = ref_step(want)
    want = np.asarray(want)
    log("sharded", f"one-device reference, {steps} sweeps: "
        f"{time.perf_counter() - t0:.3f} s")

    for overlap in ("ring", "none"):
        step = jax.jit(jax.shard_map(
            lambda a, o=overlap: jacobi_step_sharded(a, "x", overlap=o),
            mesh=mesh, in_specs=P("x", None), out_specs=P("x", None),
            check_vma=False,
        ))
        t0 = time.perf_counter()
        out = jax.device_put(x0, rows)
        for _ in range(steps):
            out = step(out)
        out.block_until_ready()
        elapsed = time.perf_counter() - t0
        placed = {s.device for s in out.addressable_shards}
        shard_shapes = {s.data.shape for s in out.addressable_shards}
        err = float(np.max(np.abs(np.asarray(out) - want)))
        log("sharded", f"overlap={overlap}: {steps} sweeps {elapsed:.3f} s, "
            f"max abs err {err!r} (limit {KERNEL_ATOL}), shards on "
            f"{sorted(d.id for d in placed)}, shard shapes {shard_shapes}")
        check(placed == set(devices),
              f"output on {sorted(d.id for d in placed)}, not all {chips}")
        check(shard_shapes == {(n // chips, n)}, f"shards {shard_shapes}")
        check(err <= KERNEL_ATOL, f"sharded overlap={overlap} error {err!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded Jacobi sweep on 4 chips")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()  # before the first compilation
    info = phase_device(args.chips)
    log("device", f"compile cache: {cache}")
    if args.chips == 4:
        phases = [("sharded", phase_sharded)]
    else:
        phases = [("kernels", phase_kernels), ("runtime", phase_runtime),
                  ("serve", phase_serve)]
    for name, fn in phases:
        t0 = time.perf_counter()
        counts = fn()
        if counts is not None:  # the payload paths of a runtime phase
            check(counts["interpret"] is False,
                  f"{name}: the Pallas kernel ran interpreted: {counts}")
        log(name, f"phase ok in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr, flush=True)
        sys.exit(1)
