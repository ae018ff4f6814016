"""jit'd public wrapper for the RWKV6 wkv kernel: padding + init state."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret

from .kernel import wkv6_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(
    r: jax.Array,  # [B, T, H, N]
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decay ∈ (0, 1)
    u: jax.Array,  # [H, N]
    init_state: Optional[jax.Array] = None,  # [B, H, N, N]
    *,
    chunk: int = 64,
    interpret: Optional[bool] = None,
):
    B, T, H, N = r.shape
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        z4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        r, k, v = jnp.pad(r, z4), jnp.pad(k, z4), jnp.pad(v, z4)
        w = jnp.pad(w, z4, constant_values=1.0)  # decay 1 = no-op padding
    s0 = (
        jnp.zeros((B, H, N, N), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )
    y, fin = wkv6_kernel(
        r, k, v, w, u, s0, chunk=chunk,
        interpret=resolve_interpret(interpret),
    )
    if pad:
        y = y[:, :T]
    return y, fin
