"""Latency-hiding collectives under shard_map — runs in a SUBPROCESS with
8 fake XLA devices so the main test process keeps its single-device view.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.comm.collectives import (
        ring_all_gather, ring_reduce_scatter, ag_matmul, matmul_rs,
        halo_exchange, stencil_1d_sharded, jacobi_step_sharded,
    )

    mesh = jax.make_mesh((8,), ("x",))
    def smap(f, in_specs, out_specs):
        return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)

    k = jax.random.PRNGKey(0)
    # ring all-gather == lax.all_gather
    x = jax.random.normal(k, (16, 4))
    got = smap(lambda a: ring_all_gather(a, "x"), P("x"), P(None))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x), rtol=1e-6)
    print("ring_all_gather OK")

    # ring reduce-scatter == psum-then-slice oracle
    z = jax.random.normal(k, (64, 8))
    def rs2(a):  # local [8, 8]
        return ring_reduce_scatter(a.reshape(8, 8)[:, :], "x", axis=0)
    # oracle: psum then slice
    def oracle(a):
        full = jax.lax.psum(a, "x")
        i = jax.lax.axis_index("x")
        return jax.lax.dynamic_slice_in_dim(full, i * 1, 1, 0)
    got = smap(rs2, P("x", None), P("x", None))(z)
    want = smap(oracle, P("x", None), P("x", None))(z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    print("ring_reduce_scatter OK")

    # overlapped ag_matmul == all_gather(x) @ w
    xs = jax.random.normal(k, (32, 16))   # gather axis rows
    w = jax.random.normal(k, (16, 8))
    got = smap(lambda a, b: ag_matmul(a, b, "x", gather_axis=0),
               (P("x", None), P(None, None)), P(None, None))(xs, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(xs @ w), rtol=1e-4, atol=1e-4)
    got_nb = smap(lambda a, b: ag_matmul(a, b, "x", overlap="none", gather_axis=0),
                  (P("x", None), P(None, None)), P(None, None))(xs, w)
    np.testing.assert_allclose(np.asarray(got_nb), np.asarray(xs @ w), rtol=1e-4, atol=1e-4)
    print("ag_matmul OK")

    # overlapped matmul_rs == reduce_scatter(x @ w)
    xk = jax.random.normal(k, (32, 64))   # K sharded
    wk = jax.random.normal(k, (64, 8))
    got = smap(lambda a, b: matmul_rs(a, b, "x", scatter_axis=0),
               (P(None, "x"), P("x", None)), P("x", None))(xk, wk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(xk @ wk), rtol=1e-4, atol=1e-4)
    print("matmul_rs OK")

    # halo exchange + sharded stencil == dense stencil
    u = jax.random.normal(k, (64,))
    def pt(l, c, r):
        return 0.25 * l + 0.5 * c + 0.25 * r
    got = smap(lambda a: stencil_1d_sharded(a, "x", pt), P("x"), P("x"))(u)
    un = np.asarray(u)
    ext = np.concatenate([[0.0], un, [0.0]])
    want = 0.25 * ext[:-2] + 0.5 * ext[1:-1] + 0.25 * ext[2:]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    got_nb = smap(lambda a: stencil_1d_sharded(a, "x", pt, overlap="none"), P("x"), P("x"))(u)
    np.testing.assert_allclose(np.asarray(got_nb), want, rtol=1e-5, atol=1e-6)
    print("stencil_1d OK")

    # 2-D jacobi step, row-sharded == reference
    g = jax.random.normal(k, (32, 16))
    got = smap(lambda a: jacobi_step_sharded(a, "x"), P("x", None), P("x", None))(g)
    gn = np.asarray(g)
    ref = gn.copy()
    interior = 0.2 * (gn[1:-1, 1:-1] + gn[:-2, 1:-1] + gn[2:, 1:-1] + gn[1:-1, :-2] + gn[1:-1, 2:])
    pad_top = 0.2 * (gn[0, 1:-1] + 0 + gn[1, 1:-1] + gn[0, :-2] + gn[0, 2:])
    # reference via the same halo-zero convention: build padded array
    ext = np.zeros((34, 16)); ext[1:-1] = gn
    new = 0.2 * (ext[1:-1, 1:-1] + ext[:-2, 1:-1] + ext[2:, 1:-1] + ext[1:-1, :-2] + ext[1:-1, 2:])
    ref[:, 1:-1] = new
    ref[0] = gn[0]; ref[-1] = gn[-1]   # global Dirichlet rows
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-6)
    print("jacobi_step OK")
    print("ALL-COLLECTIVES-PASS")
    """
)


@pytest.mark.slow
def test_collectives_under_shard_map():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # force the host CPU backend: the fake-device XLA flag only applies to
    # it, and probing for a TPU wastes minutes when libtpu is present
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=600,
    )
    assert "ALL-COLLECTIVES-PASS" in res.stdout, res.stdout + "\n" + res.stderr
