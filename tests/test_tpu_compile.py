"""Compile the main path for a described TPU v5e, without a chip.

The TPU compiler is installed even where no chip is attached: these tests
lower and compile the Pallas kernels and one full-width olmo-1b decode
step for a ``v5e:2x2`` topology, so that a kernel the chip's compiler
refuses (unaligned blocks, float iota, unsupported relayouts) or weights
baked into the program as constants fail here rather than on the chip.

Only one process may load the TPU runtime library, so the topology is
described inside a module fixture (never at import), and the whole check
stays in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype,block", [(jnp.float32, 1 << 16), (jnp.bfloat16, 2048)])
def test_stencil_compiles_for_v5e(one_chip, dtype, block):
    from repro.kernels.stencil.kernel import stencil

    c = jax.jit(lambda x: stencil(x, block=block, interpret=False)).lower(
        _spec((1 << 24,), one_chip, dtype)).compile()
    assert _has_kernel(c)


def test_partition_map_compiles_for_v5e(one_chip):
    from repro.kernels.partition_map.kernel import partition_map

    c = jax.jit(lambda x: partition_map(x, block=8192, interpret=False)).lower(
        _spec((1 << 24,), one_chip)).compile()
    assert _has_kernel(c)


def test_mandelbrot_compiles_for_v5e(one_chip):
    from repro.kernels.mandelbrot.kernel import mandelbrot

    # No operands: the output sharding is what places it on the chip.
    c = jax.jit(lambda: mandelbrot(height=1024, width=1024, interpret=False),
                out_shardings=one_chip).lower().compile()
    assert _has_kernel(c)


def test_flash_attention_compiles_at_olmo_widths(one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention_bhsd

    q = _spec((1, 16, 2048, 128), one_chip)  # olmo-1b: 16 heads x 128, 2k context
    c = jax.jit(lambda q, k, v: flash_attention_bhsd(q, k, v, interpret=False)).lower(
        q, q, q).compile()
    assert _has_kernel(c)


def test_olmo_1b_decode_step_takes_weights_as_arguments(one_chip):
    from repro.configs import get_config
    from repro.models.model import get_model
    from repro.serving.paged import zoo_steps

    cfg = get_config("olmo-1b")
    shapes = jax.eval_shape(lambda k: get_model(cfg).init(cfg, k), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _spec(s.shape, one_chip, s.dtype), shapes)
    weight_bytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    spec_fn, _, dec = zoo_steps(cfg)
    spec = spec_fn(cfg)
    pages = (1 << 30) // spec.page_bytes
    slab = _spec((spec.layers, pages, spec.page_size, spec.kv_heads, spec.head_dim), one_chip)
    B, M = 8, 64
    i32 = lambda *s: _spec(s, one_chip, jnp.int32)  # noqa: E731
    c = dec.lower(params, slab, slab, None, i32(B), i32(B), i32(B, M), i32(B)).compile()
    mem = c.memory_analysis()
    # Weights arrive as arguments (a closure would bake them in as
    # constants and leave the arguments at the slabs and the batch).
    assert mem.argument_size_in_bytes >= weight_bytes + 2 * slab.size * 4
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes + (
        mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16e9  # one v5e chip
