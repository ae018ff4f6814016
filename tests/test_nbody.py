"""The N-body text (``benchmarks/paper_apps.py``) through the runtime
against its float64 reference, the matmul payload's precision, and the
execute layer's matmul and combine counts."""
import json
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import repro
from repro import api
from repro.api import ExecutionPolicy, RuntimeConfig
from repro.obs import collector as obs_collector
from repro.obs import trace

from benchmarks.paper_apps import (
    nbody,
    nbody_arrays,
    nbody_reference,
    nbody_state,
    nbody_steps,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "bench", "configs", "nbody-8192-f32.json")) as f:
    CONFIG = json.load(f)
LIMITS = CONFIG["limits"]
PARAMS = {k: CONFIG[k] for k in ("G", "dt", "eps")}
BODIES, BLOCK, STEPS, CHUNKS = 256, 32, 4, 2  # 8x8 blocks of the pairs
VECTORS = ("px", "py", "vx", "vy")


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    obs_collector.CURRENT = None
    yield
    obs_collector.CURRENT = None


def seeded_state(seed, n=BODIES):
    """``n`` bodies in N-body units, float32: masses normalised to total
    1, positions in the unit square, small random velocities."""
    rng = np.random.default_rng(seed)
    m = rng.random((n, 1))
    m /= m.sum()
    state = dict(m=m, px=rng.random((n, 1)), py=rng.random((n, 1)),
                 vx=rng.normal(0.0, 0.01, (n, 1)),
                 vy=rng.normal(0.0, 0.01, (n, 1)))
    return {k: v.astype(np.float32) for k, v in state.items()}


def _energy(m, vx, vy):
    return float(np.sum(m.astype(np.float64) * (vx * vx + vy * vy)))


def _gaps(got, got_energy, want, m):
    vmax = max(np.max(np.abs(want[k])) for k in ("vx", "vy"))
    vel = max(np.max(np.abs(got[k] - want[k])) for k in ("vx", "vy")) / vmax
    pos = max(np.max(np.abs(got[k] - want[k])) for k in ("px", "py"))
    e_want = _energy(m, want["vx"], want["vy"])
    return {"velocity_max_rel_err": float(vel),
            "position_max_abs_err": float(pos),
            "energy_max_rel_err": abs(got_energy - e_want) / e_want}


@pytest.mark.parametrize("fusion", [True, False], ids=["fused", "unfused"])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_nbody_matches_the_reference(backend, fusion):
    """Chunks of steps through the runtime, each read back and compared
    with the reference restarted from the state read before it, within
    the configuration's limits."""
    state = seeded_state(seed=2**31 + 3)
    config = RuntimeConfig(nprocs=4, block_size=BLOCK, fusion=fusion)
    policy = ExecutionPolicy(flush="async", backend=backend)
    start = state
    with repro.runtime(config, policy):
        s = nbody_arrays(state, np.float32)
        for _ in range(CHUNKS):
            nbody_steps(s, STEPS, **PARAMS)
            energy = np.sum(s["m"] * (s["vx"] * s["vx"] + s["vy"] * s["vy"]))
            got = {k: np.asarray(s[k]).astype(np.float64) for k in VECTORS}
            want = nbody_reference(start, STEPS, PARAMS)
            gaps = _gaps(got, float(energy), want, state["m"])
            for name, limit in LIMITS.items():
                assert gaps[name] <= limit, (name, gaps[name])
            start = dict(got, m=state["m"])


def test_nbody_defaults_match_the_reference():
    """The text's defaults (SI units, float64, the state of seed 1) on
    the NumPy backend are the reference's steps."""
    n, steps = 64, 2
    config = RuntimeConfig(nprocs=4, block_size=16)
    with repro.runtime(config, ExecutionPolicy(flush="async")):
        got = np.asarray(nbody(n=n, steps=steps))
    want = nbody_reference(nbody_state(n), steps,
                           dict(G=6.674e-11, dt=0.1, eps=1e-2))
    np.testing.assert_allclose(got, want["px"], rtol=1e-12)


def _dot_precisions(jaxpr):
    """The ``precision`` of every ``dot_general`` in ``jaxpr``, nested
    programs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_dot_precisions(sub))
    return out


@pytest.mark.parametrize("dtype,want", [
    (np.float32, (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)),
    (ml_dtypes.bfloat16, None),
], ids=["float32", "bfloat16"])
def test_matmul_program_precision(dtype, want):
    """A float32 product multiplies at ``HIGHEST``; a bfloat16 one keeps
    the default.  The programs are those the runtime built for an outer
    product of its own arrays."""
    host = np.linspace(0.0, 1.0, 32, dtype=np.float32).reshape(32, 1)
    config = RuntimeConfig(nprocs=2, block_size=16)
    with repro.runtime(config, ExecutionPolicy(flush="async",
                                               backend="jax")) as rt:
        a = repro.array(host.astype(dtype))
        ones = repro.ones((32, 1), dtype=dtype)
        got = np.asarray(api.matmul(a, ones, trans_b=True))
        cache = dict(rt._exec_backend_obj._programs)
    np.testing.assert_allclose(got.astype(np.float32),
                               np.repeat(host.astype(dtype), 32, axis=1)
                               .astype(np.float32))
    progs = {k: v for k, v in cache.items() if k[0] == "matmul"}
    assert progs and {k[-1] for k in progs} == {
        None if want is None else jax.lax.Precision.HIGHEST}
    x = jnp.ones((16, 1), dtype)
    for prog in progs.values():
        jaxpr = jax.make_jaxpr(prog)(x, x).jaxpr
        assert _dot_precisions(jaxpr) == [want]


def test_matmul_and_combine_counts_under_a_collector():
    """32x32 in 16x16 blocks: a product has 2x2 output blocks of 2
    partial products each (8 matmul payloads); a row sum combines 4
    partial sums, a whole sum 4 more.  Each combine's staging and launch
    run under an ``exec.combine`` span."""
    host = np.linspace(0.0, 1.0, 32 * 32, dtype=np.float32).reshape(32, 32)
    config = RuntimeConfig(nprocs=4, block_size=16)
    with trace() as tr:
        with repro.runtime(config, ExecutionPolicy(flush="async",
                                                   backend="jax")) as rt:
            a = repro.array(host)
            np.asarray(a + 0.0)  # builds the backend
            before = rt.backend_stats()
            got = [np.asarray(x) for x in (
                api.matmul(a, a), np.sum(a, axis=1, keepdims=True),
                np.sum(a))]
            after = rt.backend_stats()
    np.testing.assert_allclose(got[0], host @ host, rtol=1e-5)
    np.testing.assert_allclose(got[1], host.sum(axis=1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(got[2], host.sum(), rtol=1e-5)
    assert after["n_matmul"] - before["n_matmul"] == 8
    assert after["n_combine"] - before["n_combine"] == 8
    assert after["n_combine"] == 8 and after["n_matmul"] == 8
    spans = tr.span_totals()
    assert spans["exec.combine"][0] == 2 * after["n_combine"]
    assert spans["exec.combine"][1] <= spans["exec.stage"][1] + \
        spans["exec.launch"][1]
