"""jit'd public wrappers for the fused Jacobi-sweep kernels."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret

from .kernel import (
    HALO,
    LANES,
    band_for_width,
    jacobi_sweep_kernel,
    round_up,
    stencil5_block_kernel,
)


@functools.partial(jax.jit, static_argnames=("band", "interpret"))
def jacobi_sweep(x: jax.Array, *, band: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """One fused 5-point Jacobi sweep on [H, W] (Dirichlet boundary).

    ``band`` (rows per grid step, a multiple of 8) defaults to one
    chosen from the width; the grid is zero-padded to band and lane
    multiples and the padding is dropped on return."""
    H, W = x.shape
    Wp = round_up(W, LANES)
    band = band_for_width(Wp) if band is None else band
    if band % HALO:
        raise ValueError(f"band must be a multiple of {HALO}, got {band}")
    band = min(band, round_up(H, HALO))
    Hp = round_up(H, band)
    if (Hp, Wp) != (H, W):
        x = jnp.pad(x, ((0, Hp - H), (0, Wp - W)))
    out = jacobi_sweep_kernel(
        x, band=band, n_rows=H, n_cols=W, interpret=resolve_interpret(interpret)
    )
    return out[:H, :W]


@functools.partial(jax.jit, static_argnames=("weight", "interpret"))
def stencil5_block(x0, x1, x2, x3, x4, *, weight: float,
                   interpret: Optional[bool] = None):
    """Fused per-block 5-point combine ``weight * (x0+..+x4)`` (the
    repro.exec JaxBackend's fast path for fused stencil map payloads)."""
    return stencil5_block_kernel(
        x0, x1, x2, x3, x4, weight=weight, interpret=resolve_interpret(interpret)
    )
