"""Pallas TPU kernel: PRK 3-point stencil (paper Fig. 3 workload).

The 1-D input is laid out as ``(rows, 128)`` — one vector lane per
column, so every block is a whole number of native ``(sublanes, 128)``
tiles.  Neighbours come from ``pltpu.roll`` (a lane/sublane rotate on
the VPU) rather than unaligned slices: rolling along lanes gives the
in-row neighbour, and the element that crosses a row boundary is the
rolled column rolled once more along sublanes.  The halo across a block
boundary is one element of the adjacent block; each grid step reads it
from a single aligned tile (``(sublanes, 128)``) bound through its own
BlockSpec, not the whole neighbour block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def sublanes(dtype) -> int:
    """Rows of one native vreg tile for ``dtype`` (8 for 32-bit, 16 for
    16-bit): block and halo heights are multiples of it."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _pick(tile, r, c):
    """Element ``tile[r, c]`` as a ``(1, 1)`` array."""
    tile = tile.astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    hit = (row == r % tile.shape[0]) & (col == c)
    picked = jnp.sum(jnp.where(hit, tile, 0.0), axis=1, keepdims=True)
    return jnp.sum(picked, axis=0, keepdims=True)


def _stencil_kernel(prev_ref, cur_ref, nxt_ref, o_ref):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    cur = cur_ref[...].astype(jnp.float32)
    br = cur.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
    # Halos: the last element before the block and the first after it
    # (zero at the array's ends), picked out of their aligned tiles by a
    # masked (1, 1) reduction — no unaligned load.
    halo_l = jnp.where(i == 0, 0.0, _pick(prev_ref[...], -1, LANES - 1))
    halo_r = jnp.where(i == n - 1, 0.0, _pick(nxt_ref[...], 0, 0))
    # x[j-1]: lane roll by +1; lane 0 takes the previous row's last lane.
    lane_l = pltpu.roll(cur, 1, 1)
    left = jnp.where(col == 0,
                     jnp.where(row == 0, halo_l, pltpu.roll(lane_l, 1, 0)),
                     lane_l)
    # x[j+1]: lane roll by -1; the last lane takes the next row's lane 0.
    lane_r = pltpu.roll(cur, LANES - 1, 1)
    right = jnp.where(col == LANES - 1,
                      jnp.where(row == br - 1, halo_r, pltpu.roll(lane_r, br - 1, 0)),
                      lane_r)
    o_ref[...] = (0.5 * left + cur + 0.5 * right).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def stencil(x, *, block: int, interpret: bool):
    """x: (N,) with N % block == 0 and block a multiple of one
    ``(sublanes(x.dtype), 128)`` tile."""
    n = x.shape[0]
    sub = sublanes(x.dtype)
    if block % (sub * LANES) or n % block:
        raise ValueError(
            f"stencil needs block % {sub * LANES} == 0 and N % block == 0 "
            f"for {x.dtype}; got N={n}, block={block}")
    br = block // LANES
    grid = (n // block,)
    tiles = br // sub  # halo tiles per block
    last_tile = n // (sub * LANES) - 1
    x2 = x.reshape(n // LANES, LANES)
    out = pl.pallas_call(
        _stencil_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sub, LANES),
                         lambda i: (jnp.maximum(i * tiles - 1, 0), 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((sub, LANES),
                         lambda i: (jnp.minimum((i + 1) * tiles, last_tile), 0)),
        ],
        out_specs=pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, x2, x2)
    return out.reshape(n)
