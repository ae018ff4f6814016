"""Flash attention (causal + GQA + sliding window) as a Pallas TPU kernel.

Tiling: grid = (B, H, Sq/BQ, Sk/BK) with the KV axis innermost and
``dimension_semantics`` marking it "arbitrary" (sequential) — the online
softmax accumulators live in VMEM scratch across the KV sweep.  Block
shapes are MXU-aligned (multiples of 128 on the sequence dims; head_dim
padded to 128 by the wrapper).  Fully-masked causal/window tiles are
skipped via ``pl.when`` on the block indices — the flash-2 schedule
adapted to the TPU grid model: VMEM scratch + a sequential grid axis
replace the CUDA shared-memory/warp accumulator pattern.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(
    q_ref,  # [1, 1, BQ, d]
    k_ref,  # [1, 1, BK, d]
    v_ref,  # [1, 1, BK, d]
    o_ref,  # [1, 1, BQ, d]
    m_ref,  # scratch [BQ, 128]  (running max, lane-replicated)
    l_ref,  # scratch [BQ, 128]  (running denom)
    acc_ref,  # scratch [BQ, d]
    *,
    causal: bool,
    window: Optional[int],
    scale: float,
    block_q: int,
    block_k: int,
    sk_valid: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip tiles the causal/window mask kills entirely
    first_q = qi * block_q
    last_q = first_q + block_q - 1
    first_k = ki * block_k
    last_k = first_k + block_k - 1
    run = first_k < sk_valid
    if causal:
        run = run & (first_k <= last_q)
    if window is not None:
        run = run & (last_k >= first_q - window + 1)

    @pl.when(run)
    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BQ, BK]
        q_pos = first_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = first_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = k_pos < sk_valid
        if causal:
            ok = ok & (q_pos >= k_pos)
        if window is not None:
            ok = ok & (q_pos - k_pos < window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr[:, None] + jnp.broadcast_to(
            p.sum(axis=1)[:, None], l_ref.shape
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    @pl.when(ki == nk - 1)
    def finalize():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,  # [B, H, Sq, d]  (d padded to a 128-multiple by ops.py)
    k: jax.Array,  # [B, H, Sk, d]  (KV heads pre-broadcast to H)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    sk_valid: Optional[int] = None,
    interpret: bool = False,
):
    B, H, Sq, d = q.shape
    Sk = k.shape[2]
    scale = d ** -0.5 if scale is None else scale
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, "wrapper pads to block multiples"
    grid = (B, H, Sq // block_q, Sk // block_k)

    kernel = functools.partial(
        _attn_kernel,
        causal=causal,
        window=window,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        sk_valid=Sk if sk_valid is None else sk_valid,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(q, k, v)
