"""jit'd public wrapper for the flash-attention kernel.

Handles layout ([B,S,H,d] ↔ [B,H,S,d]), GQA head broadcast, head-dim
padding to the 128-lane MXU width, and sequence padding to block
multiples.  ``interpret=None`` runs the kernel body in the Pallas
interpreter on the CPU platform and compiles it everywhere else.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret

from .kernel import flash_attention_kernel


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # [B, Sq, H, d]
    k: jax.Array,  # [B, Sk, KV, d]
    v: jax.Array,  # [B, Sk, KV, d]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, Sq, H, d = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = d ** -0.5 if scale is None else scale

    # GQA: broadcast KV heads to H (the kernel is per-head)
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)

    # pad head dim to a 128 multiple (MXU lanes); zero-pad K ⇒ scores exact
    d_pad = (-d) % 128
    if d_pad:
        pad4 = ((0, 0), (0, 0), (0, 0), (0, d_pad))
        q = jnp.pad(q, pad4)
        k = jnp.pad(k, pad4)
        v = jnp.pad(v, pad4)

    # pad sequences to block multiples; padded K positions are masked via
    # sk_valid, padded Q rows are dropped on return
    bq = min(block_q, max(8, 1 << (Sq - 1).bit_length())) if Sq < block_q else block_q
    bk = min(block_k, max(8, 1 << (Sk - 1).bit_length())) if Sk < block_k else block_k
    sq_pad = (-Sq) % bq
    sk_pad = (-Sk) % bk
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, sq_pad), (0, 0), (0, 0)))
    if sk_pad:
        k = jnp.pad(k, ((0, 0), (0, sk_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad), (0, 0), (0, 0)))

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_kernel(
        qt, kt, vt,
        causal=causal, window=window, scale=scale,
        block_q=bq, block_k=bk, sk_valid=Sk,
        interpret=resolve_interpret(interpret),
    )
    out = out.transpose(0, 2, 1, 3)[:, :Sq, :, :d]
    return out
