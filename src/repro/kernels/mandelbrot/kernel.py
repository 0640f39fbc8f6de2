"""Pallas TPU kernel: Mandelbrot escape iterations (paper Fig. 5 workload).

Grid tiles the image; each step derives its pixel coordinates from
``pl.program_id`` + iota (no input operands at all), runs the fixed-trip
escape loop on the VPU with z held in VMEM scratch, and writes the
iteration-count tile.
Complex arithmetic is explicit (zr, zi) — TPU Pallas has no complex dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mandel_kernel(o_ref, zr_ref, zi_ref, *, bh, bw, width, height, max_iter,
                   x0, x1, y0, y1):
    i = pl.program_id(0)
    j = pl.program_id(1)
    # Integer iota, then cast: Mosaic's iota takes integer types only.
    rows = (i * bh + jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 0)).astype(jnp.float32)
    cols = (j * bw + jax.lax.broadcasted_iota(jnp.int32, (bh, bw), 1)).astype(jnp.float32)
    cr = x0 + cols * ((x1 - x0) / max(width - 1, 1))
    ci = y0 + rows * ((y1 - y0) / max(height - 1, 1))

    # z and the count live in VMEM across iterations, not in loop-carried
    # values: a carry that starts as a splat constant and comes back as a
    # full tile is a relayout Mosaic refuses.
    zr_ref[...] = jnp.zeros((bh, bw), jnp.float32)
    zi_ref[...] = jnp.zeros((bh, bw), jnp.float32)
    o_ref[...] = jnp.zeros((bh, bw), jnp.int32)

    @pl.loop(0, max_iter)
    def _(_):
        zr, zi = zr_ref[...], zi_ref[...]
        live = zr * zr + zi * zi <= 4.0
        zr_ref[...] = jnp.where(live, zr * zr - zi * zi + cr, zr)
        zi_ref[...] = jnp.where(live, 2.0 * zr * zi + ci, zi)
        o_ref[...] += live.astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("height", "width", "max_iter", "block", "interpret")
)
def mandelbrot(
    *,
    height: int,
    width: int,
    max_iter: int = 64,
    block: "tuple[int, int]" = (128, 128),
    interpret: bool,
):
    bh, bw = block
    assert height % bh == 0 and width % bw == 0, (height, width, block)
    kern = functools.partial(
        _mandel_kernel,
        bh=bh, bw=bw, width=width, height=height, max_iter=max_iter,
        x0=-2.0, x1=1.0, y0=-1.5, y1=1.5,
    )
    return pl.pallas_call(
        kern,
        grid=(height // bh, width // bw),
        in_specs=[],
        out_specs=pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((height, width), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bh, bw), jnp.float32)] * 2,
        interpret=interpret,
    )()
