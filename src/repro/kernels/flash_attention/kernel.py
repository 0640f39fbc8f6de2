"""Pallas TPU flash attention (forward): blocked online softmax.

TPU adaptation of the FlashAttention idea (DESIGN.md §2): the CUDA version
tiles over SM shared memory; here blocks are BlockSpec-mapped VMEM tiles
sized for the MXU (128-aligned), and the kv-block loop is the *innermost
sequential grid dimension* with the running (m, l, acc) statistics carried
in VMEM scratch — the canonical Pallas-TPU flash pattern.

GQA without KV duplication: the kv BlockSpec index map sends query head
``h`` to kv head ``h // R`` — grouping lives in the index map, not in
memory.

Causal masking: kv blocks strictly above the diagonal are skipped via
``pl.when`` (no wasted MXU work); the diagonal block is masked elementwise.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, bq, bk, causal, scale):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: only kv blocks intersecting the lower triangle do work
    run = (ki * bk <= qi * bq + (bq - 1)) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]  # (bq, D)
        k = k_ref[0, 0]  # (bk, D)
        v = v_ref[0, 0]  # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_bhsd(
    q, k, v, *, interpret: bool, causal: bool = True, bq: int = 128, bk: int = 128,
):
    """q: (B, H, Sq, D); k/v: (B, K, Skv, D), H % K == 0 -> (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    K, Skv = k.shape[1], k.shape[2]
    R = H // K
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, Skv, bq, bk)
    grid = (B, H, Sq // bq, Skv // bk)
    kern = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, scale=1.0 / math.sqrt(D)
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // R, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // R, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
