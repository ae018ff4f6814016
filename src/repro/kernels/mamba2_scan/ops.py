"""jit'd public wrapper for the SSD-scan kernel: padding + init state."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret

from .kernel import ssd_scan_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,  # [b, s, h, p]
    dt: jax.Array,  # [b, s, h]  (softplus'd, > 0)
    A: jax.Array,  # [h]        (negative)
    B: jax.Array,  # [b, s, n]
    C: jax.Array,  # [b, s, n]
    init_state: Optional[jax.Array] = None,  # [b, h, p, n]
    *,
    chunk: int = 128,
    interpret: Optional[bool] = None,
):
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        # dt=0 padding is exact: exp(0·A)=1 (no decay), dt·x=0 (no input)
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    s0 = (
        jnp.zeros((b, h, p, n), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )
    y, fin = ssd_scan_kernel(
        x, dt, A, B, C, s0, chunk=chunk,
        interpret=resolve_interpret(interpret),
    )
    if pad:
        y = y[:, :s]
    return y, fin
