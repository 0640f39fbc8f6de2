"""Pallas TPU kernel for the partition benchmark map (paper Fig. 4/6).

Pure VPU workload — one block in VMEM per grid step.  The interesting
part of the paper's benchmark is not this kernel but the *pipelining*:
partitions stream through copy->compute->copy with futures overlapping
the stages (see benchmarks/fig4_partition.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _map_kernel(x_ref, o_ref):
    x = x_ref[...]
    s, c = jnp.sin(x), jnp.cos(x)
    o_ref[...] = jnp.sqrt(s * s + c * c)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def partition_map(x, *, block: int = 8192, interpret: bool):
    n = x.shape[0]
    assert n % block == 0, (n, block)
    return pl.pallas_call(
        _map_kernel,
        grid=(n // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)
