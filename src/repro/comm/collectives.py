"""Latency-hiding collective primitives (paper §5.4/§5.7 → XLA ordering).

All functions are written for use **inside** ``jax.shard_map`` (they call
``lax.axis_index`` / ``lax.ppermute`` on a named mesh axis).  The ring
variants decompose one big collective into per-shard-block steps: at every
step the next block's transfer is *initiated before* the current block's
compute is emitted, which is exactly the paper's invariant 2 ("computation
only starts when no communication is ready to initiate") expressed as HLO
op order.  XLA's async collective pairs (``*-start``/``*-done``) then
overlap the permute with the matmul.

Shape convention: ``x`` is the *local shard*; matmuls contract the last
dim of ``x`` with the first dim of ``w``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "ring_all_gather",
    "ring_reduce_scatter",
    "ag_matmul",
    "matmul_rs",
    "halo_exchange",
    "stencil_1d_sharded",
    "jacobi_step_sharded",
]


def _fwd_perm(n: int):
    """ring: rank i sends to i+1 (accumulators travel forward)."""
    return [(i, (i + 1) % n) for i in range(n)]


def _bwd_perm(n: int):
    """ring: rank i sends to i-1 (so we *receive* rank i+1's block)."""
    return [(i, (i - 1) % n) for i in range(n)]


# ---------------------------------------------------------------------------
# Ring all-gather / reduce-scatter (building blocks)
# ---------------------------------------------------------------------------

def ring_all_gather(x: jax.Array, axis_name: str, *, axis: int = 0) -> jax.Array:
    """All-gather via a ring of ``ppermute``s — n-1 steps, each step's
    transfer overlappable with whatever consumes the already-held blocks.

    Returns the gathered array with shard blocks concatenated along
    ``axis`` in rank order.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    shape = list(x.shape)
    size_local = shape[axis]
    shape[axis] = size_local * n
    out = jnp.zeros(shape, x.dtype)

    def write(out, blk, src):
        return lax.dynamic_update_slice_in_dim(out, blk, src * size_local, axis=axis)

    blk = x
    for k in range(n):
        src = (idx + k) % n  # the rank this block originated from
        if k < n - 1:
            nxt = lax.ppermute(blk, axis_name, _bwd_perm(n))  # comm first
        out = write(out, blk, src)
        if k < n - 1:
            blk = nxt
    return out


def ring_reduce_scatter(
    partials: Callable[[jax.Array], jax.Array] | jax.Array,
    axis_name: str,
    *,
    axis: int = 0,
) -> jax.Array:
    """Reduce-scatter via a forward ring.

    ``partials`` is either the full local partial-sum array (scattered
    along ``axis``) or a callable ``chunk_index -> partial block`` that
    *computes* the partial lazily — the lazy form lets the caller overlap
    each step's ppermute with the *next* partial's computation (the paper's
    sub-view-block interleave).
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)

    if callable(partials):
        get = partials
    else:
        full = partials
        size_local = full.shape[axis] // n

        def get(c):
            return lax.dynamic_slice_in_dim(full, c * size_local, size_local, axis)

    # accumulator for chunk c starts at rank c+1 and travels forward,
    # visiting every rank once and ending at rank c after n-1 hops.
    c0 = (idx - 1) % n
    acc = get(c0)
    for t in range(1, n):
        nxt_partial = get((idx - 1 - t) % n)  # independent of the permute
        acc = lax.ppermute(acc, axis_name, _fwd_perm(n))  # comm first
        acc = acc + nxt_partial
    return acc


# ---------------------------------------------------------------------------
# Overlapped collective matmuls (the TP workhorses)
# ---------------------------------------------------------------------------

def ag_matmul(
    x: jax.Array,
    w: jax.Array,
    axis_name: str,
    *,
    overlap: str = "ring",
    gather_axis: int = -2,
) -> jax.Array:
    """``all_gather(x) @ w`` with the gather hidden behind the matmul.

    ``x``: local shard ``[..., S/n, K]`` (sharded along ``gather_axis``);
    ``w``: ``[K, N_local]`` (already the local TP shard).
    Returns ``[..., S, N_local]``.

    overlap="ring": n partial matmuls, each overlapped with the ppermute
    bringing the next x-block (paper §5.4 schedule).
    overlap="none": one blocking all-gather then one matmul (paper's
    blocking baseline).
    """
    n = lax.axis_size(axis_name)
    if overlap == "none" or n == 1:
        xg = lax.all_gather(x, axis_name, axis=gather_axis % x.ndim, tiled=True)
        return xg @ w

    idx = lax.axis_index(axis_name)
    ga = gather_axis % x.ndim
    s_local = x.shape[ga]
    out_shape = list(x.shape)
    out_shape[ga] = s_local * n
    out_shape[-1] = w.shape[-1]
    out = jnp.zeros(out_shape, jnp.result_type(x.dtype, w.dtype))

    blk = x
    for k in range(n):
        src = (idx + k) % n
        if k < n - 1:
            nxt = lax.ppermute(blk, axis_name, _bwd_perm(n))  # comm first
        y = blk @ w  # overlaps the in-flight permute
        out = lax.dynamic_update_slice_in_dim(out, y.astype(out.dtype), src * s_local, axis=ga)
        if k < n - 1:
            blk = nxt
    return out


def matmul_rs(
    x: jax.Array,
    w: jax.Array,
    axis_name: str,
    *,
    overlap: str = "ring",
    scatter_axis: int = -2,
) -> jax.Array:
    """``reduce_scatter(x @ w)`` with the scatter hidden behind the matmul.

    ``x``: ``[..., S, K_local]`` (K TP-sharded); ``w``: ``[K_local, N]``.
    Returns ``[..., S/n, N]`` — the fully-reduced shard of rows.

    overlap="ring": the partial matmul for each row-chunk is computed
    just-in-time while the accumulator ring-permutes (each hop overlapped).
    overlap="none": full matmul then one blocking psum_scatter.
    """
    n = lax.axis_size(axis_name)
    if overlap == "none" or n == 1:
        y = x @ w
        return lax.psum_scatter(y, axis_name, scatter_dimension=scatter_axis % y.ndim, tiled=True)

    sa = scatter_axis % x.ndim
    s = x.shape[sa]
    s_local = s // n

    def partial_chunk(c):
        xc = lax.dynamic_slice_in_dim(x, c * s_local, s_local, sa)
        return xc @ w

    return ring_reduce_scatter(partial_chunk, axis_name, axis=sa)


# ---------------------------------------------------------------------------
# Halo exchange + stencils (the paper's flagship application class)
# ---------------------------------------------------------------------------

def halo_exchange(
    u: jax.Array,
    axis_name: str,
    *,
    halo: int = 1,
    axis: int = 0,
    periodic: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Exchange ``halo``-wide boundary slabs with ring neighbours.

    Returns ``(left_halo, right_halo)`` — the slabs received from the
    previous/next rank along ``axis_name``.  Non-periodic boundaries get
    zero slabs (masked after the permute so the wire pattern is uniform).
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    L = u.shape[axis]

    send_right = lax.slice_in_dim(u, L - halo, L, axis=axis)
    send_left = lax.slice_in_dim(u, 0, halo, axis=axis)
    # both permutes initiated back-to-back — XLA overlaps them with any
    # subsequent independent compute (the interior update).
    left_halo = lax.ppermute(send_right, axis_name, _fwd_perm(n))
    right_halo = lax.ppermute(send_left, axis_name, _bwd_perm(n))
    if not periodic:
        zero = jnp.zeros_like(left_halo)
        left_halo = jnp.where(idx == 0, zero, left_halo)
        right_halo = jnp.where(idx == n - 1, zero, right_halo)
    return left_halo, right_halo


def stencil_1d_sharded(
    u: jax.Array,
    axis_name: str,
    point_fn: Callable[[jax.Array, jax.Array, jax.Array], jax.Array],
    *,
    overlap: str = "ring",
    periodic: bool = False,
) -> jax.Array:
    """One 3-point-stencil sweep over a 1-D sharded array.

    ``point_fn(left, center, right)`` computes the new center value from the
    shifted neighbours (all same-shape arrays).

    overlap="ring" (paper §5.4): initiate halo permutes, compute the
    *interior* (needs no remote data) while they fly, then patch the two
    boundary cells.  overlap="none": wait for halos, then one full update —
    the halo transfer sits on the critical path.
    """
    L = u.shape[0]
    lh, rh = halo_exchange(u, axis_name, halo=1, axis=0, periodic=periodic)

    if overlap == "none":
        ext = jnp.concatenate([lh, u, rh], axis=0)
        return point_fn(ext[:-2], ext[1:-1], ext[2:])

    # interior update — depends only on local data; emitted after the
    # permute-starts so XLA hides the halo latency behind it.
    interior = point_fn(u[:-2], u[1:-1], u[2:])  # rows 1..L-2
    first = point_fn(lh[0], u[0], u[1])
    last = point_fn(u[L - 2], u[L - 1], rh[0])
    return jnp.concatenate([first[None], interior, last[None]], axis=0)


def jacobi_step_sharded(
    full: jax.Array,
    axis_name: str,
    *,
    overlap: str = "ring",
) -> jax.Array:
    """One 5-point Jacobi sweep on a 2-D grid sharded along rows (axis 0).

    Boundary rows/cols of the *global* grid are Dirichlet (kept fixed);
    interior is updated with the classic 0.2·(c+u+d+l+r) rule from the
    paper's Jacobi-Stencil benchmark (fig. 10).
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    L = full.shape[0]

    lh, rh = halo_exchange(full, axis_name, halo=1, axis=0, periodic=False)

    def update(up, c, down):
        return 0.2 * (c[:, 1:-1] + up[:, 1:-1] + down[:, 1:-1] + c[:, :-2] + c[:, 2:])

    if overlap == "none":
        ext = jnp.concatenate([lh, full, rh], axis=0)
        new_int = update(ext[:-2], ext[1:-1], ext[2:])
    else:
        # interior rows first (local-only), boundary rows after the halos.
        interior = update(full[:-2], full[1:-1], full[2:])  # rows 1..L-2
        top = update(lh, full[:1], full[1:2])
        bot = update(full[L - 2 : L - 1], full[L - 1 :], rh)
        new_int = jnp.concatenate([top, interior, bot], axis=0)

    out = full.at[:, 1:-1].set(new_int)
    # re-pin global Dirichlet boundary rows (first row of rank 0, last of n-1)
    out = jnp.where(
        (idx == 0) & (jnp.arange(L)[:, None] == 0), full, out
    )
    out = jnp.where(
        (idx == n - 1) & (jnp.arange(L)[:, None] == L - 1), full, out
    )
    return out
