"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels run in interpret mode (Python)
— wall-time there is meaningless.  What we CAN measure honestly:

* wall-time of the jnp reference paths (the XLA:CPU-compiled twins) —
  a correctness-speed proxy and a regression canary;
* the kernels' arithmetic/bytes roofline terms on the TPU target,
  derived analytically from the BlockSpec tiling (reported as `derived`).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.roofline.analysis import peaks_for

__all__ = ["rows"]

# the derived bounds are for the v5e target, not for the CPU this runs on
_HW = peaks_for("TPU v5 lite")


def _time(fn, *args, iters=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / iters


def rows():
    out = []
    key = jax.random.PRNGKey(0)

    # flash attention jnp twin
    from repro.models.attention import chunked_attention
    B, S, H, dgl = 1, 1024, 4, 64
    q = jax.random.normal(key, (B, S, H, dgl), jnp.float32)
    k = jax.random.normal(key, (B, S, H, dgl), jnp.float32)
    v = jax.random.normal(key, (B, S, H, dgl), jnp.float32)
    fn = jax.jit(lambda q, k, v: chunked_attention(q, k, v, causal=True, chunk=256))
    t = _time(fn, q, k, v)
    flops = 4 * B * H * S * S * dgl * 0.5  # causal half
    out.append(dict(name="attn_jnp_cpu", us_per_call=t * 1e6,
                    derived=f"tpu_compute_bound_us={flops / _HW.peak_flops * 1e6:.1f}"))

    # ssd scan jnp twin
    from repro.models.mamba2 import ssd_chunked
    b, s, h, p, n = 1, 2048, 8, 64, 64
    x = jax.random.normal(key, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(key, (b, s, h)))
    A = -jnp.ones((h,))
    Bm = jax.random.normal(key, (b, s, n))
    Cm = jax.random.normal(key, (b, s, n))
    fn = jax.jit(lambda *a: ssd_chunked(*a, chunk=128))
    t = _time(fn, x, dt, A, Bm, Cm)
    c = 128
    flops = (s // c) * h * (2 * c * c * n + 2 * c * c * p + 4 * c * p * n) * b
    out.append(dict(name="ssd_jnp_cpu", us_per_call=t * 1e6,
                    derived=f"tpu_compute_bound_us={flops / _HW.peak_flops * 1e6:.2f}"))

    # wkv jnp twin
    from repro.models.rwkv6 import wkv_chunked
    B2, T, H2, N = 1, 1024, 8, 64
    r = jax.random.normal(key, (B2, T, H2, N))
    kk = jax.random.normal(key, (B2, T, H2, N))
    vv = jax.random.normal(key, (B2, T, H2, N))
    w = jax.nn.sigmoid(jax.random.normal(key, (B2, T, H2, N))) * 0.5 + 0.45
    u = jax.random.normal(key, (H2, N))
    fn = jax.jit(lambda *a: wkv_chunked(*a, chunk=64))
    t = _time(fn, r, kk, vv, w, u)
    out.append(dict(name="wkv6_jnp_cpu", us_per_call=t * 1e6,
                    derived="intra-chunk O(c·c·N) dominated"))

    # fused jacobi sweep: jnp shifted-view chain vs fused kernel traffic
    from repro.kernels.stencil.ref import jacobi_sweep_ref
    n2 = 2048
    g = jax.random.normal(key, (n2, n2))
    fn = jax.jit(jacobi_sweep_ref)
    t = _time(fn, g)
    bytes_fused = 2 * n2 * n2 * 4
    bytes_views = 7 * n2 * n2 * 4  # 5 reads + 1 write + temp (paper's form)
    out.append(dict(
        name="jacobi_sweep_jnp_cpu", us_per_call=t * 1e6,
        derived=(f"tpu_mem_bound_us fused={bytes_fused / _HW.hbm_bw * 1e6:.0f} "
                 f"vs views={bytes_views / _HW.hbm_bw * 1e6:.0f} (3.5x)")))
    return out
