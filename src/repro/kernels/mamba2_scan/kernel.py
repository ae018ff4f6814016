"""Chunked SSD scan as a Pallas TPU kernel.

Grid = (B, H, S/chunk) with the chunk axis innermost and sequential
("arbitrary"): the [p, n] per-head state lives in VMEM scratch across
the sweep.  Each grid step does the three SSD pieces as dense MXU work
on one chunk:

    y_diag  = (L ⊙ C Bᵀ) · (dt ⊙ x)        intra-chunk   [c,c]@[c,p]
    y_off   = exp(cum) ⊙ (C · stateᵀ)       inter-chunk   [c,n]@[n,p]
    state'  = exp(cum_C) ⊙ state + (B ⊙ w)ᵀ·x             [n,c]@[c,p]

This is the TPU adaptation of the Mamba2 CUDA kernel: where the GPU
version streams chunks through shared memory with warp-level matmuls,
the TPU version makes each piece an MXU ``dot_general`` over a
VMEM-resident chunk, with the recurrence carried by the sequential grid
axis instead of a persistent thread block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,  # [1, c, 1, p]
    dt_ref,  # [1, c, 1]
    a_ref,  # [1]
    b_ref,  # [1, c, n]
    c_ref,  # [1, c, n]
    s0_ref,  # [1, 1, p, n]  initial state
    y_ref,  # [1, c, 1, p]
    sout_ref,  # [1, 1, p, n] final state
    state_ref,  # scratch [p, n] f32
    *,
    chunk: int,
):
    z = pl.program_id(2)
    nz = pl.num_programs(2)

    @pl.when(z == 0)
    def init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, :, 0, :].astype(jnp.float32)  # [c, p]
    dt = dt_ref[0, :, 0].astype(jnp.float32)  # [c]
    A = a_ref[0].astype(jnp.float32)  # scalar
    Bm = b_ref[0].astype(jnp.float32)  # [c, n]
    Cm = c_ref[0].astype(jnp.float32)  # [c, n]

    dA = dt * A  # [c], negative
    cum = jnp.cumsum(dA)  # [c]

    # intra-chunk decay L[t, l] = exp(cum_t - cum_l) for l <= t
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    ldiff = cum[:, None] - cum[None, :]
    L = jnp.where(ti >= li, jnp.exp(ldiff), 0.0)  # [c, c]

    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [c, c]
    xdt = x * dt[:, None]  # [c, p]
    y_diag = jax.lax.dot_general(
        L * scores, xdt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [c, p]

    # inter-chunk: y_off = exp(cum) ⊙ (C · stateᵀ)
    st = state_ref[...]  # [p, n]
    y_off = jax.lax.dot_general(
        Cm, st, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(cum)[:, None]  # [c, p]

    y_ref[0, :, 0, :] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: state' = exp(cum_C) ⊙ state + xᵀ·(B ⊙ w), w = exp(cum_C - cum)·dt
    w = jnp.exp(cum[-1] - cum) * dt  # [c]
    Bw = Bm * w[:, None]  # [c, n]
    s_local = jax.lax.dot_general(
        x, Bw, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [p, n]
    state_ref[...] = st * jnp.exp(cum[-1]) + s_local

    @pl.when(z == nz - 1)
    def fin():
        sout_ref[0, 0] = state_ref[...].astype(sout_ref.dtype)


def ssd_scan_kernel(
    x, dt, A, B, C, s0, *, chunk: int = 128, interpret: bool = False
):
    """x: [b,s,h,p]; dt: [b,s,h]; A: [h]; B,C: [b,s,n]; s0: [b,h,p,n].
    s must be a chunk multiple (ops.py pads).  Returns (y, final_state)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    nz = s // chunk
    grid = (b, h, nz)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, zi: (bi, zi, hi, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, zi: (bi, zi, hi)),
            pl.BlockSpec((1,), lambda bi, hi, zi: (hi,)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, zi: (bi, zi, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, zi: (bi, zi, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, zi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, zi: (bi, zi, hi, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, zi: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(x, dt, A, B, C, s0)
