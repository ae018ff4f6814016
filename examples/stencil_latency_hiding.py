"""The paper's flagship experiment (figs. 10/18), runnable end-to-end:
the Jacobi stencil with latency-hiding vs blocking communication, plus
the beyond-paper fused (§7) variant and the TPU shard_map mapping.

The stencil is written against the plain NumPy programming model — the
paper's whole point: slicing, arithmetic and ``np.asarray`` readback on
DistArrays, no repro-specific operation names.  Execution is swept
declaratively through ``ExecutionPolicy`` objects, with compute
backends and transfer channels resolved exclusively through the plugin
registry (``repro.available_backends()``).

    PYTHONPATH=src python examples/stencil_latency_hiding.py

Readback sync is demand-driven by default under the measured backend
(every ``np.asarray`` forces only its dependency cone);
``REPRO_SYNC=demand|barrier`` pins it for every policy below.
"""
import os

import jax
import numpy as np

# float64 end to end, so on the CPU the jitted JAX backend is
# bit-identical to the eager NumPy interpreter on this elementwise program
jax.config.update("jax_enable_x64", True)

import repro
from repro.api import ExecutionPolicy, RuntimeConfig, format_stats

SYNC = os.environ.get("REPRO_SYNC", "auto")
N, ITERS = 1024, 6
# backends must agree bit-for-bit on the CPU, where both compute in
# float64 with the same IEEE ops; an accelerator's XLA may emulate or
# narrow float64, so there they must agree to this absolute tolerance
BACKEND_ATOL = 1e-6


def jacobi_stencil(n: int, iters: int) -> np.ndarray:
    """Figs. 10/18 written exactly like the sequential NumPy code."""
    full = repro.zeros((n + 2, n + 2))
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


def run(config: repro.RuntimeConfig, policy: ExecutionPolicy, n: int, iters: int):
    with repro.runtime(config, policy) as rt:
        result = jacobi_stencil(n, iters)
        return rt.stats(), result


# --- simulated: the paper's table (16 processes, GbE cluster model) ------
print(f"Jacobi stencil {N}x{N}, {ITERS} sweeps, 16 processes "
      f"(paper fig. 18 setup)\n")

cfg = RuntimeConfig(nprocs=16, block_size=128)
lh = ExecutionPolicy(scheduler="latency_hiding", sync=SYNC)

st_lh, r_lh = run(cfg, lh, N, ITERS)
st_bl, r_bl = run(cfg, lh.replace(scheduler="blocking"), N, ITERS)
st_fu, r_fu = run(cfg.replace(fusion=True), lh, N, ITERS)
np.testing.assert_array_equal(r_lh, r_bl)
np.testing.assert_allclose(r_lh, r_fu)

print(format_stats([
    ("blocking (baseline)", st_bl),
    ("latency-hiding (paper)", st_lh),
    ("LH + fusion (§7, ours)", st_fu),
]))
print(f"\nlatency-hiding wall-clock win: {st_bl.makespan/st_lh.makespan:.2f}x "
      f"(paper: 18.4/7.7 = 2.4x at 16 cores)")

# --- the same program, executed for real (repro.exec) -------------------
# flush="async" drains the identical dependency graphs on worker
# threads: transfers go through a non-blocking progress engine (overlap
# on) or a synchronous channel (overlap off), with 10 ms of wire latency
# injected per message so there is real latency to hide.  The wait%
# here is MEASURED on the wall clock; the simulated rows model the same
# α, rendered in the same table by format_stats.  Both registered
# compute backends drain the same graphs and must agree bit-for-bit on
# the CPU (float64 everywhere, elementwise IEEE ops), and to
# BACKEND_ATOL on an accelerator.
#
# The async flush runs the record→plan→execute pipeline: with the
# default passes="auto", transfers are coalesced into fewer, larger
# messages and worker handoffs are batched — visible in the dispatch:
# lines below (handoffs/flush, msgs/flush), and bit-identical to the
# passes-off drain by the plan-stage ordering contract.
MN, MITERS, MPROCS, ALPHA = 256, 4, 8, 10e-3
mcfg = RuntimeConfig(nprocs=MPROCS, block_size=64)
measured = ExecutionPolicy(flush="async", channel="async", latency=ALPHA,
                           sync=SYNC)
sim_alpha = ExecutionPolicy(
    cluster=repro.GIGE_2012.replace(alpha=ALPHA, name="gige-alpha-10ms"),
    sync=SYNC,
)

st_sim_on, _ = run(mcfg, sim_alpha, MN, MITERS)
st_sim_off, _ = run(mcfg, sim_alpha.replace(scheduler="blocking"), MN, MITERS)

backends = [b for b in repro.available_backends() if b in ("numpy", "jax")]
reference = None
for backend in backends:
    st_on, r_on = run(mcfg, measured.replace(backend=backend), MN, MITERS)
    st_off, r_off = run(
        mcfg, measured.replace(backend=backend, channel="blocking"), MN, MITERS
    )
    np.testing.assert_array_equal(r_on, r_off)
    if reference is None:
        reference = r_on
    if jax.default_backend() == "cpu":
        np.testing.assert_array_equal(r_on, reference)
    else:
        np.testing.assert_allclose(r_on, reference, rtol=0, atol=BACKEND_ATOL)

    print(f"\nmeasured vs simulated ({MN}x{MN}, {MPROCS} workers, "
          f"backend={backend!r}):")
    print(format_stats([
        ("overlap ON  (async)", st_on),
        ("overlap OFF (blocking)", st_off),
        ("latency-hiding (model)", st_sim_on),
        ("blocking (model)", st_sim_off),
    ]))
    print(f"measured overlap win: {st_off.makespan/st_on.makespan:.2f}x")

# plan-stage sweep: the same drain without any graph pass must be
# bit-identical — the passes only change WHEN data moves, never what it is
st_plan, r_plan = run(mcfg, measured, MN, MITERS)
st_nop, r_nop = run(mcfg, measured.replace(passes=()), MN, MITERS)
np.testing.assert_array_equal(r_plan, r_nop)
print(f"\nplan-stage dispatch win (passes='auto' vs none, bit-identical): "
      f"handoffs {st_nop.n_handoffs} -> {st_plan.n_handoffs}, "
      f"messages {st_nop.n_messages} -> {st_plan.n_messages}")

# --- traced run: Perfetto export + wait attribution ----------------------
# REPRO_TRACE=1 re-runs the flagship measured config under a live
# collector, exports Chrome-trace JSON (load it at https://ui.perfetto.dev),
# and cross-checks the trace against the measured stats: the
# trace-derived wait fraction must agree with WaitStats.wait_fraction
# within 2 points, and attribution must name the halo-exchange
# transfers as the top worker-wait source.  REPRO_TRACE=<path> picks the
# export path (default stencil_trace.json).
TRACE = os.environ.get("REPRO_TRACE", "")
if TRACE not in ("", "0", "false", "False"):
    from repro.obs import attribution, export_trace, validate_trace

    with repro.trace() as tr:
        st_tr, r_tr = run(mcfg, measured, MN, MITERS)
    np.testing.assert_array_equal(r_tr, reference)
    path = TRACE if TRACE not in ("1", "true", "True") else "stencil_trace.json"
    export_trace(tr, path)
    info = validate_trace(path)
    print(f"\ntrace: {info['n_events']} events -> {path} "
          f"(open in https://ui.perfetto.dev)")

    rep = attribution(tr)
    print(rep.format(5))
    delta = abs(rep.wait_fraction - st_tr.wait_fraction)
    print(f"wait fraction: trace {rep.wait_fraction * 100:.1f}% vs "
          f"measured {st_tr.wait_fraction * 100:.1f}% (|delta| "
          f"{delta * 100:.2f} points)")
    assert delta < 0.02, (
        f"trace-derived wait fraction diverged {delta * 100:.2f} points "
        f"from the measured WaitStats"
    )
    worker_offenders = [
        o for o in rep.offenders
        if not o["group"].startswith("flush#") and o["group"] != "(end of trace)"
    ]
    assert worker_offenders and worker_offenders[0]["group"].startswith("xfer"), (
        f"expected the halo-exchange transfers as top wait source, got "
        f"{[o['group'] for o in worker_offenders[:3]]}"
    )
    print("attribution names the halo-exchange transfers as top wait source ✓")

# --- the fused sweep as one Pallas kernel -------------------------------
# The kernel runs in the Pallas interpreter on the CPU platform and is
# compiled everywhere else.  The same sweep sharded over chips, with the
# halo rows exchanged by ppermute while the interior updates, is
# repro.comm.collectives.jacobi_step_sharded (`chip_smoke.py --chips 4`
# runs it on a four-chip mesh).
import jax.numpy as jnp
from repro.kernels.stencil import jacobi_sweep, jacobi_sweep_ref

g = jnp.asarray(np.random.default_rng(0).random((256, 256)), jnp.float32)
fused = jacobi_sweep(g, band=64)          # Pallas kernel
ref = jacobi_sweep_ref(g)                  # 5-view jnp chain (paper's form)
print(f"\nPallas fused-sweep kernel matches the 5-view reference: "
      f"{bool(jnp.allclose(fused, ref, atol=1e-6))}")
