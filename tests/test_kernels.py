"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp
oracle, swept over shapes/dtypes — plus hypothesis property sweeps
(deterministic fallback sweeps when hypothesis isn't installed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # minimal container: seeded fallback sweeps
    from _hypothesis_compat import given, settings, strategies as st

from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.mandelbrot.kernel import mandelbrot
from repro.kernels.mandelbrot.ref import mandelbrot_ref
from repro.kernels.partition_map.kernel import partition_map
from repro.kernels.partition_map.ref import partition_map_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.kernels.stencil.kernel import stencil
from repro.kernels.stencil.ref import stencil_ref

# ---------------------------------------------------------------------------
# registry surface
# ---------------------------------------------------------------------------


def test_all_kernels_aggregates_every_package():
    from repro.kernels import all_kernels

    ks = all_kernels()
    # one representative op per package, all callable
    for name in ("stencil", "partition_map", "mandelbrot", "flash_attention", "ssd"):
        assert name in ks and callable(ks[name]), name
    # aggregation is deterministic (fixed package order)
    assert list(ks) == list(all_kernels())


# ---------------------------------------------------------------------------
# stencil
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,block", [(2048, 2048), (4096, 2048), (16384, 4096), (65536, 8192)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stencil_matches_ref(n, block, dtype):
    x = jax.random.normal(jax.random.key(n), (n,), dtype)
    got = stencil(x, block=block, interpret=True)
    want = stencil_ref(x)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2
    )


@settings(max_examples=10, deadline=None)
@given(
    nb=st.integers(2, 8),
    block=st.sampled_from([1024, 2048, 4096]),
    seed=st.integers(0, 2**16),
)
def test_stencil_property(nb, block, seed):
    x = jax.random.normal(jax.random.key(seed), (nb * block,), jnp.float32)
    got = stencil(x, block=block, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(stencil_ref(x)), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# partition map
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,block", [(128, 32), (8192, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_partition_map_matches_ref(n, block, dtype):
    x = (jax.random.normal(jax.random.key(7), (n,)) * 10).astype(dtype)
    got = partition_map(x, block=block, interpret=True)
    want = partition_map_ref(x)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2
    )


def test_partition_map_is_one():
    x = jax.random.normal(jax.random.key(0), (1024,), jnp.float32) * 100
    np.testing.assert_allclose(np.asarray(partition_map(x, block=256, interpret=True)), 1.0, rtol=1e-5)


# ---------------------------------------------------------------------------
# mandelbrot
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,w,blk", [(64, 64, (32, 32)), (128, 256, (64, 128))])
def test_mandelbrot_matches_ref(h, w, blk):
    got = mandelbrot(height=h, width=w, max_iter=32, block=blk, interpret=True)
    want = mandelbrot_ref(h, w, 32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mandelbrot_interior_hits_max_iter():
    it = mandelbrot(height=64, width=64, max_iter=24, block=(32, 32), interpret=True)
    # the origin neighbourhood is inside the set -> max_iter
    mid = np.asarray(it)[32, 21]  # c approx (-1, 0): inside
    assert mid == 24


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Sq,Skv,H,K,D,bq,bk", [
    (1, 128, 128, 4, 4, 64, 64, 64),     # MHA
    (2, 256, 256, 8, 2, 32, 128, 64),    # GQA R=4
    (1, 128, 256, 4, 1, 64, 64, 128),    # MQA, cross Skv>Sq
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, Sq, Skv, H, K, D, bq, bk, causal):
    if causal and Sq != Skv:
        pytest.skip("causal requires square here")
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, K, D), jnp.float32)
    got = flash_attention_bhsd(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
        causal=causal, bq=bq, bk=bk, interpret=True,
    ).swapaxes(1, 2)
    want = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@settings(max_examples=8, deadline=None)
@given(
    B=st.integers(1, 2),
    nq=st.integers(1, 4),
    K=st.sampled_from([1, 2, 4]),
    R=st.sampled_from([1, 2, 4]),
    D=st.sampled_from([16, 64]),
    seed=st.integers(0, 2**16),
)
def test_flash_attention_property(B, nq, K, R, D, seed):
    bq = bk = 32
    Sq = Skv = nq * bq
    H = K * R
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, K, D), jnp.float32)
    got = flash_attention(q, k, v, causal=True, bq=bq, bk=bk, impl="pallas")
    want = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-4, atol=3e-4)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 2, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 32), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, bq=64, bk=64, impl="pallas")
    want = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=5e-2, atol=5e-2
    )


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("G,S,P,N,chunk", [
    (2, 64, 16, 8, 16),
    (4, 128, 32, 16, 64),
    (1, 256, 64, 128, 64),   # mamba2-130m-like head
])
def test_ssd_scan_matches_sequential_ref(G, S, P, N, chunk):
    ks = jax.random.split(jax.random.key(11), 5)
    x = jax.random.normal(ks[0], (G, S, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (G, S))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (G,)) * 0.3)
    B = jax.random.normal(ks[3], (G, S, N), jnp.float32) * 0.5
    C = jax.random.normal(ks[4], (G, S, N), jnp.float32) * 0.5
    got = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    want = ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


@settings(max_examples=8, deadline=None)
@given(
    G=st.integers(1, 3),
    nc=st.integers(1, 4),
    chunk=st.sampled_from([8, 16, 32]),
    P=st.sampled_from([8, 16]),
    N=st.sampled_from([4, 16]),
    seed=st.integers(0, 2**16),
)
def test_ssd_scan_property(G, nc, chunk, P, N, seed):
    S = nc * chunk
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (G, S, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (G, S))) * 0.2
    A = -jnp.exp(jax.random.normal(ks[2], (G,)) * 0.2)
    B = jax.random.normal(ks[3], (G, S, N)) * 0.3
    C = jax.random.normal(ks[4], (G, S, N)) * 0.3
    got = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    want = ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-3, atol=5e-3)


def test_ssd_model_layer_uses_same_math():
    """The model's ssd_chunked and the kernel agree (same chunk boundaries)."""
    from repro.models.ssm import ssd_chunked

    Bz, S, H, P, N = 2, 64, 3, 16, 8
    ks = jax.random.split(jax.random.key(13), 5)
    x = jax.random.normal(ks[0], (Bz, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bz, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (Bz, S, 1, N)) * 0.5
    C = jax.random.normal(ks[4], (Bz, S, 1, N)) * 0.5
    y_model, _ = ssd_chunked(x, dt, A, B, C, chunk=16)

    from repro.kernels.ssd_scan.ops import ssd

    Bh = jnp.repeat(B, H, axis=2)
    Ch = jnp.repeat(C, H, axis=2)
    y_kernel = ssd(x, dt, A, Bh, Ch, impl="pallas", chunk=16)
    np.testing.assert_allclose(
        np.asarray(y_model), np.asarray(y_kernel, np.float32), rtol=2e-3, atol=2e-3
    )
