"""Fused 5-point Jacobi sweep as a Pallas TPU kernel — the paper's
flagship application class (fig. 10 / §6 Jacobi Stencil), TPU-adapted.

The paper's NumPy expression evaluates five shifted array views through
five separate ufunc passes (5 reads + several temp writes of the whole
grid per sweep).  The paper's §7 "future work" proposes merging chained
ufuncs into one joint operation; this kernel IS that merge on TPU: one
HBM read + one HBM write per sweep (plus two 8-row halo blocks per
band), with the neighbour shifts done in VMEM.  Arithmetic intensity
rises from ~0.15 flop/B to ~0.5 flop/B — the same locality win the
DistNumPy fusion mode gets, moved from the interpreter to the memory
hierarchy.

Tiling: grid over row bands; each grid step reads its own ``(band, W)``
block plus the 8-row block just above and just below it (the smallest
row block the TPU's (8, 128) tiling allows), and writes one band.  The
band height is chosen from the width so the double-buffered working
set fits the scoped VMEM at any grid width.  Pallas double-buffers the
band fetches across sequential grid steps, which is exactly the paper's
double-buffering (§5.4) applied to the HBM→VMEM pipe instead of the
network.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HALO = 8  # rows per halo block: the TPU's sublane tile
LANES = 128
# elements per (band, W) tile: 256 Ki f32 = 1 MiB, so the pipelined
# buffers plus the kernel's temporaries stay inside the 16 MiB scoped VMEM
_BAND_ELEMS = 256 * 1024
_MAX_BAND = 512


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def band_for_width(width: int) -> int:
    """Band height (a multiple of 8) whose ``(band, width)`` f32 tile
    holds about :data:`_BAND_ELEMS` elements."""
    band = (_BAND_ELEMS // max(width, 1)) // HALO * HALO
    return max(HALO, min(_MAX_BAND, band))


def _jacobi_kernel(up_ref, cur_ref, dn_ref, o_ref, *, band: int,
                   n_rows: int, n_cols: int):
    i = pl.program_id(0)
    cur = cur_ref[...].astype(jnp.float32)  # [band, W]
    W = cur.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
    # vertical neighbours: rotate the band one row, then patch the row
    # that wrapped around with the neighbouring band's halo row
    up_row = up_ref[HALO - 1 : HALO, :].astype(jnp.float32)  # row i*band - 1
    dn_row = dn_ref[0:1, :].astype(jnp.float32)  # row (i+1)*band
    up = jnp.where(rows == 0, up_row, pltpu.roll(cur, 1, 0))
    down = jnp.where(rows == band - 1, dn_row, pltpu.roll(cur, band - 1, 0))
    # horizontal neighbours wrap only into the boundary columns, which
    # keep their value below
    left = pltpu.roll(cur, 1, 1)
    right = pltpu.roll(cur, W - 1, 1)
    new = 0.2 * ((((cur + up) + down) + left) + right)

    # Dirichlet boundary: first/last real row and column; the padding
    # the wrapper adds beyond them is dropped on return
    grow = i * band + rows
    edge = (grow == 0) | (grow >= n_rows - 1) | (cols == 0) | (cols >= n_cols - 1)
    o_ref[...] = jnp.where(edge, cur, new).astype(o_ref.dtype)


def jacobi_sweep_kernel(x: jax.Array, *, band: int, n_rows: int,
                        n_cols: int, interpret: bool = False):
    """One fused sweep over ``x[:n_rows, :n_cols]`` of a padded grid.

    ``x``: [H, W] with H a multiple of ``band``, ``band`` a multiple of 8
    and W a multiple of 128 (``ops.jacobi_sweep`` pads)."""
    H, W = x.shape
    if band % HALO or H % band or W % LANES:
        raise ValueError(
            f"jacobi_sweep_kernel needs band % {HALO} == 0, H % band == 0 "
            f"and W % {LANES} == 0; got H={H}, W={W}, band={band}"
        )
    nb = H // band
    hb = band // HALO  # halo blocks per band
    n_halo = H // HALO
    kernel = functools.partial(
        _jacobi_kernel, band=band, n_rows=n_rows, n_cols=n_cols
    )
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            # the 8 rows just above the band (clamped at the top edge,
            # where the row it feeds is boundary and masked)
            pl.BlockSpec((HALO, W), lambda i: (jnp.maximum(i * hb - 1, 0), 0)),
            pl.BlockSpec((band, W), lambda i: (i, 0)),
            # the 8 rows just below the band (clamped at the bottom edge)
            pl.BlockSpec(
                (HALO, W), lambda i: (jnp.minimum((i + 1) * hb, n_halo - 1), 0)
            ),
        ],
        out_specs=pl.BlockSpec((band, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
    )(x, x, x)


# ---------------------------------------------------------------------------
# Block-level fused stencil combine (used by repro.exec's JaxBackend)
# ---------------------------------------------------------------------------

# a (tile_rows, tile_cols) tile of 128 Ki elements: six double-buffered
# f32 operands take 6 MiB of scoped VMEM
_S5_TILE_ELEMS = 128 * 1024
_S5_MAX_COLS = 2048  # a multiple of 128


def _stencil5_tiles(rows: int, cols: int) -> tuple[int, int]:
    """Tile shape for a ``(rows, cols)`` block.  Each tile dim is either
    the whole dim or a multiple of (8, 128); edge tiles may be partial."""
    tc = cols if cols <= _S5_MAX_COLS else _S5_MAX_COLS
    if rows <= HALO:
        return rows, tc
    tr = max(HALO, (_S5_TILE_ELEMS // tc) // HALO * HALO)
    return min(tr, rows // HALO * HALO), tc


def _stencil5_kernel(x0_ref, x1_ref, x2_ref, x3_ref, x4_ref, o_ref, *, weight):
    acc = x0_ref[...].astype(jnp.float32) + x1_ref[...].astype(jnp.float32)
    acc = acc + x2_ref[...].astype(jnp.float32)
    acc = acc + x3_ref[...].astype(jnp.float32)
    acc = acc + x4_ref[...].astype(jnp.float32)
    o_ref[...] = (weight * acc).astype(o_ref.dtype)


def stencil5_block_kernel(x0, x1, x2, x3, x4, *, weight: float,
                          interpret: bool = False):
    """Fused ``weight * (x0+x1+x2+x3+x4)`` over five same-shape 2-D blocks.

    This is the per-sub-view-block form of the Jacobi sweep: the runtime's
    fragment iteration already materialized the five shifted views as
    separate operands (with halos delivered into scratch buffers by the
    transfer channel), so the remaining compute is a pure 5-way
    elementwise combine — one VMEM pass instead of four ufunc round
    trips.  Addition order matches the interpreter's left-nested chain.
    The grid walks (8, 128)-aligned tiles, so any block size compiles;
    ragged fragment shapes get whole-dim or partial edge tiles.
    """
    rows, cols = x0.shape
    tr, tc = _stencil5_tiles(rows, cols)
    spec = pl.BlockSpec((tr, tc), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_stencil5_kernel, weight=weight),
        grid=(pl.cdiv(rows, tr), pl.cdiv(cols, tc)),
        in_specs=[spec] * 5,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x0.shape, x0.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
    )(x0, x1, x2, x3, x4)
