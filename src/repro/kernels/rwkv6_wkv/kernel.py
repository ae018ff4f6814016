"""RWKV6 wkv recurrence as a chunked Pallas TPU kernel.

Grid = (B, H, T/chunk), chunk axis sequential; the [N, N] state is VMEM
scratch.  The per-CHANNEL data-dependent decay (RWKV6's defining
feature) means the intra-chunk weights don't factor out of the r·k dot
— the kernel materializes the per-channel decay ratio tensor
``exp(cumprev_t − cum_j)`` for the chunk ([c, c, N], VMEM-resident) and
contracts it with r and k in one einsum.  On a GPU this is the part the
official CUDA kernel does with per-thread registers over the N lanes;
on TPU the [c,c,N] tile in VMEM plus VPU elementwise + MXU contraction
is the natural equivalent (c=64 ⇒ 1 MB f32 tile for N=64).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(
    r_ref, k_ref, v_ref, w_ref,  # [1, c, 1, N]
    u_ref,  # [1, N]
    s0_ref,  # [1, 1, N, N]
    y_ref,  # [1, c, 1, N]
    sout_ref,  # [1, 1, N, N]
    state_ref,  # scratch [N, N] f32  (S[i, j]: key-dim i, value-dim j)
    *,
    chunk: int,
):
    z = pl.program_id(2)
    nz = pl.num_programs(2)

    @pl.when(z == 0)
    def init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    f32 = jnp.float32
    r = r_ref[0, :, 0, :].astype(f32)  # [c, N]
    k = k_ref[0, :, 0, :].astype(f32)
    v = v_ref[0, :, 0, :].astype(f32)
    w = w_ref[0, :, 0, :].astype(f32)
    u = u_ref[0].astype(f32)  # [N]

    logw = jnp.log(jnp.maximum(w, 1e-12))  # [c, N], <= 0
    cum = jnp.cumsum(logw, axis=0)
    cumprev = cum - logw  # exclusive prefix (y_t sees S_{t-1})

    # intra-chunk, strict j < t, per-channel decay Π_{j<τ<t} w_τ[i]
    dec = jnp.exp(
        jnp.clip(cumprev[:, None, :] - cum[None, :, :], -60.0, 0.0)
    )  # [c(t), c(j), N]
    att = jnp.einsum("ti,tji,ji->tj", r, dec, k)  # [c, c]
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ji = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(ti > ji, att, 0.0)
    y = jax.lax.dot_general(
        att, v, (((1,), (0,)), ((), ())), preferred_element_type=f32
    )  # [c, N]

    # diagonal (j == t) with bonus u
    y = y + jnp.sum(r * u[None, :] * k, axis=1)[:, None] * v

    # inter-chunk: state entering step t has decayed by w_{1..t-1}
    st = state_ref[...]
    r_dec = r * jnp.exp(jnp.clip(cumprev, -60.0, 0.0))
    y = y + jax.lax.dot_general(
        r_dec, st, (((1,), (0,)), ((), ())), preferred_element_type=f32
    )
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    # state update: S' = diag(Π w) S + Σ_j (k_j ⊙ Π_{j<τ<=C} w_τ) v_jᵀ
    k_dec = k * jnp.exp(jnp.clip(cum[-1:, :] - cum, -60.0, 0.0))
    s_local = jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), preferred_element_type=f32
    )  # [N, N]
    state_ref[...] = st * jnp.exp(jnp.clip(cum[-1], -60.0, 0.0))[:, None] + s_local

    @pl.when(z == nz - 1)
    def fin():
        sout_ref[0, 0] = state_ref[...].astype(sout_ref.dtype)


def wkv6_kernel(r, k, v, w, u, s0, *, chunk: int = 64, interpret: bool = False):
    """r,k,v,w: [B,T,H,N] (T a chunk multiple — ops.py pads); u: [H,N];
    s0: [B,H,N,N].  Returns (y, final_state)."""
    B, T, H, N = r.shape
    grid = (B, H, T // chunk)
    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    seq_spec = pl.BlockSpec((1, chunk, 1, N), lambda b, h, z: (b, z, h, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, N), lambda b, h, z: (h, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, z: (b, h, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, N, N), lambda b, h, z: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H, N), r.dtype),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(r, k, v, w, u, s0)
