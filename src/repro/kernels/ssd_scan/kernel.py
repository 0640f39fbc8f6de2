"""Pallas TPU kernel: Mamba-2 SSD chunked scan (forward).

TPU adaptation (DESIGN.md §2): the CUDA SSD kernel stages chunks through
shared memory with warp-level matmuls; here each grid step owns one
(sequence-chunk x head-group) VMEM tile, the intra-chunk quadratic term
runs on the MXU as (L, L) dot products, and the inter-chunk state (N, P)
is carried in VMEM scratch across the sequential innermost grid dim —
exactly the role the CUDA version gives to its persistent accumulator.

Layout: G = batch*heads rows; per row: x (S, P), dt (S,), B/C (S, N),
A scalar brought in as a (1,1) block from a (G, 1) operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *, L):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)  # (L, P)
    dt = dt_ref[0].astype(jnp.float32)  # (L,)
    a = a_ref[0, 0].astype(jnp.float32)  # scalar
    B = b_ref[0].astype(jnp.float32)  # (L, N)
    C = c_ref[0].astype(jnp.float32)  # (L, N)

    da = dt * a  # (L,)
    cum = jnp.cumsum(da)  # (L,)

    # intra-chunk: att[i, j] = (C_i . B_j) exp(cum_i - cum_j) dt_j, j <= i
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    decay = jnp.exp(cum[:, None] - cum[None, :])
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    att = jnp.where(jj <= ii, scores * decay * dt[None, :], 0.0)
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # inter-chunk: incoming state contribution
    state = state_scr[...]  # (N, P)
    y += jax.lax.dot_general(
        C * jnp.exp(cum)[:, None], state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # state update: S <- exp(cum_L) S + sum_j exp(cum_L - cum_j) dt_j B_j x_j^T
    w = jnp.exp(cum[-1] - cum) * dt  # (L,)
    new_state = jnp.exp(cum[-1]) * state + jax.lax.dot_general(
        B * w[:, None], x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    state_scr[...] = new_state
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, interpret: bool, chunk: int = 64):
    """x: (G, S, P); dt: (G, S); A: (G,); B/C: (G, S, N) -> (G, S, P)."""
    G, S, P = x.shape
    N = B.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    grid = (G, S // L)
    a2 = A.reshape(G, 1)
    kern = functools.partial(_ssd_kernel, L=L)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, L), lambda g, c: (g, c)),
            pl.BlockSpec((1, 1), lambda g, c: (g, 0)),
            pl.BlockSpec((1, L, N), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, L, N), lambda g, c: (g, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, L, P), lambda g, c: (g, c, 0)),
        out_shape=jax.ShapeDtypeStruct((G, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt, a2, B, C)
