"""Pallas TPU paged attention (decode): page-table-indirect flash.

vLLM-style serving keeps every sequence's KV cache as fixed-size *pages*
scattered through a global per-device pool, so batches of different-length
sequences share ONE executable with zero token-padding waste (DESIGN.md
§15).  The kernel is the flash pattern of
``kernels/flash_attention/kernel.py`` — online softmax with running
``(m, l, acc)`` statistics in VMEM scratch over the innermost sequential
grid dimension — with one twist: the kv BlockSpec does not walk contiguous
sequence blocks, it walks the sequence's **page table**.

The page table and lengths ride ``PrefetchScalarGridSpec`` scalar-prefetch
arguments: they are available *before* the kernel body runs, so the kv
index map can compute the physical page for grid step ``(b, h, j)`` as
``table[b, j]`` and the DMA engine fetches exactly that page from the pool
in HBM — the gather lives in the index map, not in memory (the same trick
the flash kernel uses for GQA head grouping, ``h // R``).

Masking: pages at or beyond ``ceil(length / P)`` are skipped outright via
``pl.when`` (their table slots must still hold a valid page index — the
pool's slot 0 by convention — so the prefetched DMA stays in bounds); the
sequence's last partial page is masked elementwise against ``length``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page_size):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    # Whole pages past the sequence's tail do no work at all.
    run = j * page_size < length

    @pl.when(run)
    def _step():
        q = q_ref[...].reshape(1, -1)          # (1, D)
        k = k_ref[...].reshape(page_size, -1)  # (P, D)
        v = v_ref[...].reshape(page_size, -1)  # (P, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (1.0 / math.sqrt(q.shape[-1]))     # (1, P)
        pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        o_ref[...] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        ).reshape(o_ref.shape).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_bhd(q, k_pages, v_pages, page_table, lengths, *,
                        interpret: bool):
    """q: (B, H, D); k/v_pages: (N, P, K, D), H % K == 0;
    page_table: (B, M) int32; lengths: (B,) int32 -> (B, H, D)."""
    B, H, D = q.shape
    N, P, K, Dk = k_pages.shape
    M = page_table.shape[1]
    R = H // K
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, lengths
        grid=(B, H, M),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda b, h, j, tbl, ln: (b, h, 0)),
            pl.BlockSpec((1, P, 1, D), lambda b, h, j, tbl, ln: (tbl[b, j], 0, h // R, 0)),
            pl.BlockSpec((1, P, 1, D), lambda b, h, j, tbl, ln: (tbl[b, j], 0, h // R, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, h, j, tbl, ln: (b, h, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
    )
    kern = functools.partial(_paged_kernel, page_size=P)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), q, k_pages, v_pages)
