"""Paged KV cache + prefill/decode disaggregation (DESIGN.md §15): the
paged-attention kernel against the flash oracle across ragged and
page-straddling lengths, page-pool alloc/free/defrag invariants (no page
leaked, no page double-owned), honest AGAS accounting through
``Registry.update_nbytes``, LRU sequence spill, coalesced migration, the
``Scheduler.charge`` direct-route fix, per-kind ``LanePolicy`` lanes, and
the ``PagedServeEngine`` end to end."""
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # minimal container: deterministic fallback sweep
    from tests._hypothesis_compat import given, settings, strategies as st

from repro.core import Scheduler, get_all_devices
from repro.core import agas
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.kernel import paged_attention_bhd
from repro.kernels.paged_attention.ops import paged_attention, paged_attention_layers
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.serving import LanePolicy, RequestEngine
from repro.serving.paged import OutOfPages, PagedKVCache, PagedServeEngine, PageSpec


@pytest.fixture(scope="module")
def device():
    return get_all_devices(1, 0).get()[0]


# ---------------------------------------------------------------------------
# kernel: paged attention vs the gather oracle vs the flash reference
# ---------------------------------------------------------------------------


def _random_paged(rng, B, H, K, D, P, M, lengths):
    """Pool + tables covering ``lengths``; unreferenced pages (and page 0)
    hold huge-but-finite garbage so a masking bug shows up as a numeric
    blowup, not a rounding error."""
    N = 1 + sum(-(-l // P) for l in lengths) + 2
    k_pages = np.full((N, P, K, D), 1e6, np.float32)
    v_pages = np.full((N, P, K, D), -1e6, np.float32)
    tbl = np.zeros((B, M), np.int32)
    nxt = 1
    for b, l in enumerate(lengths):
        for j in range(-(-l // P)):
            tbl[b, j] = nxt
            valid = min(P, l - j * P)
            k_pages[nxt, :valid] = rng.normal(size=(valid, K, D))
            v_pages[nxt, :valid] = rng.normal(size=(valid, K, D))
            nxt += 1
    q = rng.normal(size=(B, H, D)).astype(np.float32)
    return q, k_pages, v_pages, tbl, np.asarray(lengths, np.int32)


def _contiguous(k_pages, v_pages, tbl, P, b, l):
    toks = [(tbl[b, t // P], t % P) for t in range(l)]
    k = np.stack([k_pages[p, o] for p, o in toks])[None]
    v = np.stack([v_pages[p, o] for p, o in toks])[None]
    return k, v


def test_paged_ref_matches_flash_on_ragged_lengths():
    rng = np.random.default_rng(0)
    B, H, K, D, P, M = 4, 4, 2, 8, 4, 6
    # partial page, exact boundary, straddling, full table
    lengths = [3, 4, 7, 24]
    q, kp, vp, tbl, lens = _random_paged(rng, B, H, K, D, P, M, lengths)
    ref = np.asarray(paged_attention_ref(q, kp, vp, tbl, lens))
    assert np.isfinite(ref).all()
    for b, l in enumerate(lengths):
        kc, vc = _contiguous(kp, vp, tbl, P, b, l)
        want = np.asarray(flash_attention_ref(q[b : b + 1, None], kc, vc, causal=False))
        np.testing.assert_allclose(ref[b], want[0, 0], rtol=1e-5, atol=1e-5)


def test_paged_kernel_matches_ref_in_interpret_mode():
    rng = np.random.default_rng(1)
    B, H, K, D, P, M = 3, 4, 2, 8, 4, 5
    lengths = [1, 6, 20]  # sub-page, page-straddling, full table
    q, kp, vp, tbl, lens = _random_paged(rng, B, H, K, D, P, M, lengths)
    ref = np.asarray(paged_attention_ref(q, kp, vp, tbl, lens))
    got = np.asarray(paged_attention_bhd(q, kp, vp, tbl, lens, interpret=True))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_paged_op_dispatches_and_matches():
    rng = np.random.default_rng(2)
    q, kp, vp, tbl, lens = _random_paged(rng, 2, 2, 1, 4, 4, 3, [5, 9])
    auto = np.asarray(paged_attention(q, kp, vp, tbl, lens))
    forced = np.asarray(paged_attention(q, kp, vp, tbl, lens, impl="kernel"))
    np.testing.assert_allclose(auto, forced, rtol=1e-5, atol=1e-5)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), page=st.sampled_from([2, 4, 8]))
def test_paged_kernel_property_ragged(seed, page):
    rng = np.random.default_rng(seed)
    B = int(rng.integers(1, 4))
    K = int(rng.integers(1, 3))
    H = K * int(rng.integers(1, 3))
    D = 4
    M = int(rng.integers(1, 4))
    lengths = [int(rng.integers(1, M * page + 1)) for _ in range(B)]
    q, kp, vp, tbl, lens = _random_paged(rng, B, H, K, D, page, M, lengths)
    ref = np.asarray(paged_attention_ref(q, kp, vp, tbl, lens))
    got = np.asarray(paged_attention_bhd(q, kp, vp, tbl, lens, interpret=True))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def _random_layered(rng, Lc, B, H, K, D, P, M, lengths):
    """Folded multi-layer slab: one table, per-layer page contents
    (``_random_paged`` allocates pages deterministically from ``lengths``,
    so each layer's table comes out identical — the zoo invariant)."""
    qs, ks, vs = [], [], []
    tbl = lens = None
    for _ in range(Lc):
        q, kp, vp, tbl, lens = _random_paged(rng, B, H, K, D, P, M, lengths)
        qs.append(q), ks.append(kp), vs.append(vp)
    return np.stack(qs), np.stack(ks), np.stack(vs), tbl, lens


def test_paged_layers_matches_per_layer_ref():
    rng = np.random.default_rng(3)
    Lc, B, H, K, D, P, M = 3, 3, 4, 2, 8, 4, 5
    q, kp, vp, tbl, lens = _random_layered(rng, Lc, B, H, K, D, P, M, [3, 8, 17])
    got = np.asarray(paged_attention_layers(q, kp, vp, tbl, lens))
    assert got.shape == (Lc, B, H, D)
    for l in range(Lc):  # the fold is exactly L per-layer calls, bitwise
        want = np.asarray(paged_attention_ref(q[l], kp[l], vp[l], tbl, lens))
        np.testing.assert_array_equal(got[l], want)


def test_paged_layers_rejects_mismatched_layer_dims():
    rng = np.random.default_rng(4)
    q, kp, vp, tbl, lens = _random_layered(rng, 2, 2, 2, 1, 4, 4, 3, [5, 9])
    with pytest.raises(ValueError, match="layer dims"):
        paged_attention_layers(q[:1], kp, vp, tbl, lens)


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen2-moe-a2.7b", "hymba-1.5b", "whisper-tiny"])
def test_paged_kernel_zoo_geometries(arch):
    """The Pallas kernel handles every zoo ``paged_spec`` geometry —
    multi-layer folds and GQA head ratios — matching the gather ref."""
    from repro.configs import get_config, smoke
    from repro.models.model import paged_surface

    cfg = smoke(get_config(arch))
    spec = paged_surface(cfg)[0](cfg)
    H, K, D = cfg.num_heads, spec.kv_heads, spec.head_dim
    assert H % K == 0, f"{arch}: GQA ratio must be integral"
    rng = np.random.default_rng(6)
    P, M = 4, 4
    q, kp, vp, tbl, lens = _random_layered(
        rng, spec.layers, 2, H, K, D, P, M, [3, 10])
    ref = np.asarray(paged_attention_layers(q, kp, vp, tbl, lens, impl="ref"))
    got = np.asarray(paged_attention_layers(q, kp, vp, tbl, lens, impl="kernel"))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# page pool / sequence lifecycle invariants
# ---------------------------------------------------------------------------


def _spec(P=2):
    return PageSpec(layers=1, page_size=P, kv_heads=1, head_dim=2)


def _fill(spec, seq_id, tokens):
    """Deterministic page-in payload: token t of sequence s holds
    ``s * 1000 + t`` — readable back for content checks."""
    base = np.arange(tokens, dtype=np.float32) + seq_id * 1000.0
    k = np.broadcast_to(
        base[None, :, None, None],
        (spec.layers, tokens, spec.kv_heads, spec.head_dim),
    ).copy()
    return k, -k


def _check_invariants(kv):
    """No page leaked, no page double-owned, page 0 never owned."""
    for key, pool in kv.pools.items():
        owned = []
        for s in kv._seqs.values():
            if s.pool is pool:
                owned.extend(s.pages)
        assert 0 not in owned, f"{key}: reserved page 0 owned"
        assert len(owned) == len(set(owned)), f"{key}: page double-owned"
        free = set(pool._free)
        assert not (free & set(owned)), f"{key}: page both free and owned"
        assert len(free) + len(owned) == pool.num_pages - 1, f"{key}: page leaked"


def _seq_tokens(kv, seq):
    """Token values currently paged in for ``seq`` (first ``length``)."""
    k, _v = seq.pool.read_pages(seq.pages)
    flat = np.moveaxis(k, 0, 1).reshape(kv.spec.layers, -1, 1, kv.spec.head_dim)
    return flat[0, : seq.length, 0, 0]


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_alloc_free_defrag_invariants(seed):
    # No fixture params under @given: the hypothesis-compat wrapper hides
    # the signature from pytest's fixture resolution.
    device = get_all_devices(1, 0).get()[0]
    rng = np.random.default_rng(seed)
    spec = _spec(P=2)
    kv = PagedKVCache(spec, devices=[device], pool_pages=24)
    live = {}
    next_id = 0
    for _ in range(40):
        op = rng.choice(["new", "free", "defrag", "spill", "resident"])
        if op == "new":
            tokens = int(rng.integers(1, 7))
            if kv.pools[device.key].num_free < spec.pages_for(tokens):
                continue
            seq = kv.new_seq(device)
            k, v = _fill(spec, next_id, tokens)
            kv.append(seq, k, v)
            live[next_id] = (seq, tokens)
            next_id += 1
        elif op == "free" and live:
            sid = int(rng.choice(list(live)))
            seq, _ = live.pop(sid)
            kv.free_seq(seq)
        elif op == "defrag":
            kv.defrag(device)
        elif op == "spill" and live:
            sid = int(rng.choice(list(live)))
            live[sid][0].spill().get()
        elif op == "resident" and live:
            sid = int(rng.choice(list(live)))
            try:
                live[sid][0].ensure_resident()
            except OutOfPages:
                pass
        _check_invariants(kv)
    # Contents survived every alloc/free/defrag/spill interleaving.
    for sid, (seq, tokens) in live.items():
        seq.ensure_resident()
        got = _seq_tokens(kv, seq)
        np.testing.assert_array_equal(got, np.arange(tokens) + sid * 1000.0)
    for seq, _ in live.values():
        kv.free_seq(seq)
    assert kv.pools[device.key].used_pages == 0


def test_page_size_is_explicit():
    with pytest.raises(ValueError, match="page_size"):
        PageSpec(1, 0, 2, 4)
    assert PageSpec(1, 4, 2, 4).page_size == 4


def test_pool_overflow_and_double_free(device):
    spec = _spec()
    kv = PagedKVCache(spec, devices=[device], pool_pages=4)  # 3 allocatable
    pool = kv.pools[device.key]
    pages = pool.alloc(3)
    with pytest.raises(OutOfPages):
        pool.alloc(1)
    pool.free(pages)
    with pytest.raises(ValueError, match="double free"):
        pool.free([pages[0]])
    with pytest.raises(ValueError, match="not an allocatable"):
        pool.free([0])


def test_defrag_compacts_and_preserves_contents(device):
    spec = _spec(P=2)
    kv = PagedKVCache(spec, devices=[device], pool_pages=16)
    seqs = []
    for sid in range(4):
        seq = kv.new_seq(device)
        kv.append(seq, *_fill(spec, sid, 4))
        seqs.append(seq)
    kv.free_seq(seqs[0])
    kv.free_seq(seqs[2])  # holes at the front and middle
    moved = kv.defrag(device)
    assert moved > 0
    live = sorted(p for s in (seqs[1], seqs[3]) for p in s.pages)
    assert live == list(range(1, len(live) + 1))  # compacted to the low slots
    for sid, seq in ((1, seqs[1]), (3, seqs[3])):
        np.testing.assert_array_equal(_seq_tokens(kv, seq), np.arange(4) + sid * 1000.0)
    assert kv.defrag(device) == 0  # idempotent once compact
    # Free explicitly: SeqPages <-> kv._seqs is a reference cycle, so a
    # leaked live sequence's AGAS registration survives until the cyclic
    # GC runs — nondeterministically mid-way through a LATER test's
    # resident-bytes accounting.
    kv.free_seq(seqs[1])
    kv.free_seq(seqs[3])


# ---------------------------------------------------------------------------
# honest accounting + scheduler integration
# ---------------------------------------------------------------------------


def test_update_nbytes_moves_resident_accounting(device):
    gid = agas.registry.register(
        object(), agas.Placement(device.key), kind="buffer", nbytes=100)
    base = agas.registry.resident_bytes(device.key)
    agas.registry.update_nbytes(gid, 350)
    assert agas.registry.resident_bytes(device.key) == base + 250
    agas.registry.update_nbytes(gid, 0)
    assert agas.registry.resident_bytes(device.key) == base - 100
    agas.registry.unregister(gid)
    with pytest.raises(KeyError):
        agas.registry.update_nbytes(gid, 1)


def test_seq_pages_account_spill_and_refetch(device):
    spec = _spec(P=2)
    kv = PagedKVCache(spec, devices=[device], pool_pages=16)
    before = agas.registry.resident_bytes(device.key)
    seq = kv.new_seq(device)
    kv.append(seq, *_fill(spec, 7, 5))  # 3 pages
    assert seq.nbytes == 3 * spec.page_bytes
    assert agas.registry.resident_bytes(device.key) == before + seq.nbytes
    free_before = kv.pools[device.key].num_free

    assert seq.spill().get() is True
    # Pages returned to the pool, bytes moved to the host pool.
    assert kv.pools[device.key].num_free == free_before + 3
    assert agas.registry.resident_bytes(device.key) == before
    assert agas.registry.placement(seq.gid).device_key == agas.HOST_KEY

    seq.ensure_resident()
    assert agas.registry.placement(seq.gid).device_key == device.key
    np.testing.assert_array_equal(_seq_tokens(kv, seq), np.arange(5) + 7000.0)
    kv.free_seq(seq)
    assert agas.registry.resident_bytes(device.key) == before


def test_spill_lru_evicts_cold_sequence_first(device):
    spec = _spec(P=2)
    kv = PagedKVCache(spec, devices=[device], pool_pages=16)
    cold = kv.new_seq(device)
    hot = kv.new_seq(device)
    kv.append(cold, *_fill(spec, 0, 4))
    kv.append(hot, *_fill(spec, 1, 4))
    cold._last_use = 0.0  # oldest spillable resident on this device
    sched = Scheduler([device], policy="least_loaded")
    for f in sched.spill_lru(device, need_bytes=1):
        f.get()
    assert cold.spilled and not hot.spilled
    kv.free_seq(cold)
    kv.free_seq(hot)


def test_scheduler_charge_biases_least_loaded(device):
    sched = Scheduler([device], policy="least_loaded")
    assert not sched._recent_extras()
    sched.charge(device, 16)
    extras = sched._recent_extras()
    assert extras.get(device.key, 0.0) > 10.0  # decays from 16
    sched.charge(device, 0)  # no-op
    assert sched._recent_extras()[device.key] <= extras[device.key] + 1e-6


# ---------------------------------------------------------------------------
# engine lanes (RequestEngine LanePolicy) + dtype round-trip
# ---------------------------------------------------------------------------


def test_lane_token_budget_caps_prefill_batches(device):
    seen = []

    def prefill(batch):  # rows (b, 16): tokens_per_row = 16
        seen.append(batch.shape[0])
        return batch * 1.0

    eng = RequestEngine(
        {"prefill": prefill},
        max_batch=8,
        max_delay_s=0.05,
        scheduler=Scheduler([device]),
        graph=False,
        lanes={"prefill": LanePolicy(token_budget=32)},  # 32 // 16 = 2 rows
        name="t-lanes",
    )
    try:
        futs = [eng.submit(np.ones((1, 16), np.float32), kind="prefill") for _ in range(6)]
        for f in futs:
            f.get(timeout=60)
    finally:
        eng.close()
    assert max(seen) <= 2  # token budget bound, not max_batch=8
    with pytest.raises(KeyError, match="unknown kind"):
        RequestEngine({"x": prefill}, lanes={"nope": LanePolicy()})


def test_lane_deadline_overrides_engine_default(device):
    eng = RequestEngine(
        {"decode": lambda b: b + 1.0},
        max_batch=8,
        max_delay_s=0.25,  # engine-wide: slow
        scheduler=Scheduler([device]),
        graph=False,
        lanes={"decode": LanePolicy(max_delay_s=0.002)},  # lane: tight
        name="t-deadline",
    )
    try:
        t0 = time.monotonic()
        eng.submit(np.ones((1, 4), np.float32), kind="decode").get(timeout=60)
        assert time.monotonic() - t0 < 0.2  # dispatched at the lane deadline
    finally:
        eng.close()


@settings(max_examples=4, deadline=None)
@given(dt=st.sampled_from(["bfloat16", "float16", "float32"]), rows=st.integers(1, 3))
def test_engine_round_trips_sub_fp32_dtypes(dt, rows):
    device = get_all_devices(1, 0).get()[0]
    dtype = jnp.dtype(dt)

    def step(batch):
        return {"cache": batch["cache"] * 2, "next": batch["tokens"]}

    eng = RequestEngine(
        {"decode": step}, max_batch=4, scheduler=Scheduler([device]),
        graph=False, name="t-dtype",
    )
    try:
        cache = jnp.full((rows, 3, 2), 1.5, dtype)
        out = eng.submit(
            {"cache": cache, "tokens": np.ones((rows, 1), np.int32), "pos": np.int32(0)},
            kind="decode",
        ).get(timeout=60)
    finally:
        eng.close()
    assert out["cache"].dtype == np.dtype(dtype)
    assert out["cache"].shape == (rows, 3, 2)
    np.testing.assert_array_equal(
        np.asarray(out["cache"], np.float32), np.full((rows, 3, 2), 3.0, np.float32))


def test_padding_waste_reported(device):
    eng = RequestEngine(
        lambda b: b * 1.0, max_batch=8, max_delay_s=0.001,
        scheduler=Scheduler([device]), graph=False, name="t-waste",
    )
    try:
        for _ in range(3):  # 3 rows pad to the 4-bucket
            eng.submit(np.ones((3, 2), np.float32)).get(timeout=60)
        m = eng.metrics()
    finally:
        eng.close()
    assert m["padded_rows"] >= 1
    assert m["padding_waste"] == pytest.approx(m["padded_rows"] / m["rows"])


# ---------------------------------------------------------------------------
# PagedServeEngine end to end (single device; fleet spread in subprocess)
# ---------------------------------------------------------------------------


def _toy_paged_model(V=64, K=1, D=4, P=4):
    """Deterministic LM on the engine's contract, its params an embedding
    table: the logits are one-hot at (last + 1) % V, so greedy sampling
    serves exactly that token, but the KV pools and the paged-attention
    gather are genuinely exercised (a masking or table bug turns the
    attention output non-finite, which moves the hot logit one token on,
    failing the assert).  Returns ``(prefill_fn, decode_fn, emb)``."""
    emb = jnp.asarray(np.random.default_rng(0).normal(size=(V, K, D)).astype(np.float32))

    @jax.jit
    def prefill_fn(params, tokens, extras):
        e = params[tokens]  # (B, T, K, D)
        return e[:, None], e[:, None], None, jax.nn.one_hot((tokens[:, -1] + 1) % V, V)

    @jax.jit
    def decode_fn(params, kp, vp, state, tokens, positions, tables, lengths):
        e = params[tokens]
        b = tokens.shape[0]
        page = tables[jnp.arange(b), positions // P]
        slot = positions % P
        kp = kp.at[0, page, slot].set(e)
        vp = vp.at[0, page, slot].set(e)
        o = paged_attention_ref(e.reshape(b, K, D), kp[0], vp[0], tables, lengths + 1)
        guard = jnp.where(jnp.isfinite(o.sum(axis=(1, 2))), 0, 1)
        return kp, vp, None, jax.nn.one_hot((tokens + 1 + guard) % V, V)

    return prefill_fn, decode_fn, emb


def _replicas(params, devices):
    """One params replica per pool device, keyed by device key."""
    return {d.key: jax.device_put(params, d.jax_device) for d in devices}


def test_paged_engine_serves_mixed_lengths_with_zero_padding(device):
    V, P = 64, 4
    prefill_fn, decode_fn, emb = _toy_paged_model(V=V, P=P)
    kv = PagedKVCache(PageSpec(1, P, 1, 4), devices=[device], pool_pages=64)
    eng = PagedServeEngine(
        kv, prefill_fn, decode_fn, weights=_replicas(emb, [device]), max_seq_len=32,
        scheduler=Scheduler([device]), name="t-paged",
    )
    rng = np.random.default_rng(3)
    try:
        futs = []
        for _ in range(9):
            plen = int(rng.integers(1, 10))  # mixed lengths share decode steps
            prompt = rng.integers(0, V - 16, size=plen).astype(np.int32)
            futs.append((prompt, eng.submit(prompt, max_new_tokens=5)))
        for prompt, f in futs:
            out = f.get(timeout=120)
            want = [(int(prompt[-1]) + 1 + j) % V for j in range(5)]
            assert list(out) == want
        m = eng.metrics()
    finally:
        eng.close()
    assert m["requests_completed"] == 9
    # Sequence dimension is never padded; rows pad only when a shrinking
    # tail reuses a warm (already-compiled) batch shape, capped at 2x.
    assert m["padding_waste"] <= 0.5
    assert m["decode_steps"] < 9 * 4 + 5  # mixed lengths actually shared steps
    assert kv.pools[device.key].used_pages == 0  # all pages back


def test_paged_engine_needs_one_weights_replica_per_pool(device):
    prefill_fn, decode_fn, emb = _toy_paged_model()
    kv = PagedKVCache(PageSpec(1, 4, 1, 4), devices=[device], pool_pages=16)
    with pytest.raises(TypeError, match="weights"):
        PagedServeEngine(kv, prefill_fn, decode_fn, max_seq_len=16)
    with pytest.raises(ValueError, match="one params replica per pool device"):
        PagedServeEngine(kv, prefill_fn, decode_fn, weights={"tpu:99": emb},
                         max_seq_len=16)


def test_paged_engine_admission_guards(device):
    prefill_fn, decode_fn, emb = _toy_paged_model()
    kv = PagedKVCache(PageSpec(1, 4, 1, 4), devices=[device], pool_pages=16)
    eng = PagedServeEngine(kv, prefill_fn, decode_fn, weights=_replicas(emb, [device]),
                           max_seq_len=16, scheduler=Scheduler([device]), name="t-guard")
    try:
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(np.zeros((0,), np.int32), 4)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.submit(np.ones((12,), np.int32), 8)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# concurrency: decode/spill/defrag races, partial prefill failure, policy zeros
# ---------------------------------------------------------------------------


def test_spill_serializes_against_held_seq_lock(device):
    """A decode step holds the sequence's lock through the kernel call;
    a racing spill must wait for it, never free the pages mid-step."""
    spec = _spec(P=2)
    kv = PagedKVCache(spec, devices=[device], pool_pages=16)
    seq = kv.new_seq(device)
    kv.append(seq, *_fill(spec, 1, 4))
    seq._lock.acquire()  # simulate an in-flight decode step pinning the seq
    try:
        f = seq.spill()
        time.sleep(0.05)
        assert not f.done()  # blocked on the seq lock
        assert seq.pages and not seq.spilled  # pages untouched mid-step
    finally:
        seq._lock.release()
    assert f.get(timeout=30) is True  # spill proceeds once the step ends
    assert seq.spilled
    kv.free_seq(seq)


def test_paged_engine_exact_tokens_under_spill_pressure(device):
    """Hammer the decode lanes with a concurrent spiller (the regime where
    an unpinned sequence's pages could be freed and re-owned mid-step):
    every generated token must still be exact."""
    V, P = 64, 4
    prefill_fn, decode_fn, emb = _toy_paged_model(V=V, P=P)
    kv = PagedKVCache(PageSpec(1, P, 1, 4), devices=[device], pool_pages=64)
    eng = PagedServeEngine(kv, prefill_fn, decode_fn, weights=_replicas(emb, [device]),
                           max_seq_len=32, scheduler=Scheduler([device]), name="t-spillrace")
    stop = threading.Event()

    def spiller():
        while not stop.is_set():
            with kv._seq_lock:
                seqs = list(kv._seqs.values())
            for s in seqs:
                try:
                    s.spill().get(timeout=30)
                except Exception:  # noqa: BLE001 - freed mid-flight is fine
                    pass
            time.sleep(0.001)

    th = threading.Thread(target=spiller, daemon=True)
    th.start()
    rng = np.random.default_rng(7)
    try:
        futs = []
        for _ in range(8):
            plen = int(rng.integers(1, 9))
            prompt = rng.integers(0, V - 16, size=plen).astype(np.int32)
            futs.append((prompt, eng.submit(prompt, max_new_tokens=6)))
        for prompt, f in futs:
            out = f.get(timeout=120)
            want = [(int(prompt[-1]) + 1 + j) % V for j in range(6)]
            assert list(out) == want
    finally:
        stop.set()
        th.join(timeout=30)
        eng.close()
    assert kv.pools[device.key].used_pages == 0


def test_defrag_no_deadlock_with_concurrent_spillers(device):
    """defrag takes seq locks before the pool lock (same order as spill);
    the old pool-then-seq order was an ABBA deadlock against _spill_now."""
    spec = _spec(P=2)
    kv = PagedKVCache(spec, devices=[device], pool_pages=32)
    seqs = []
    for sid in range(6):
        seq = kv.new_seq(device)
        kv.append(seq, *_fill(spec, sid, 4))
        seqs.append(seq)
    stop = threading.Event()

    def churner(offset):
        i = offset
        while not stop.is_set():
            s = seqs[i % len(seqs)]
            try:
                s._spill_now()          # seq._lock -> pool.lock
                s.ensure_resident()     # seq._lock -> pool.lock
            except OutOfPages:
                pass
            i += 1

    threads = [threading.Thread(target=churner, args=(i,), daemon=True)
               for i in range(2)]
    for t in threads:
        t.start()

    def defragger():
        for _ in range(50):
            kv.defrag(device)

    d = threading.Thread(target=defragger, daemon=True)
    d.start()
    d.join(timeout=60)
    deadlocked = d.is_alive()
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert not deadlocked, "defrag deadlocked against concurrent spill"
    for sid, seq in enumerate(seqs):
        seq.ensure_resident()
        np.testing.assert_array_equal(_seq_tokens(kv, seq), np.arange(4) + sid * 1000.0)
    _check_invariants(kv)
    for seq in seqs:
        kv.free_seq(seq)


def test_prefill_partial_failure_fails_only_unadmitted(device):
    """A mid-group prefill failure must fail only the requests prefill
    still owns: already-admitted members finish normally, the lane thread
    survives (no double settlement), drain() returns, no page leaks."""
    V, P = 64, 4
    prefill_fn, decode_fn, emb = _toy_paged_model(V=V, P=P)
    kv = PagedKVCache(PageSpec(1, P, 1, 4), devices=[device], pool_pages=64)
    eng = PagedServeEngine(
        kv, prefill_fn, decode_fn, weights=_replicas(emb, [device]), max_seq_len=32,
        scheduler=Scheduler([device]),
        prefill=LanePolicy(max_batch=8, max_delay_s=0.25, token_budget=4096),
        name="t-partial")
    orig = eng._pool_with_room
    calls = {"n": 0}

    def flaky(dev, need_pages):
        calls["n"] += 1
        if calls["n"] == 3:
            raise OutOfPages("injected mid-group failure")
        return orig(dev, need_pages)

    eng._pool_with_room = flaky
    try:
        prompts = [np.arange(4, dtype=np.int32) + i for i in range(4)]
        futs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        for i in (0, 1):  # admitted before the failure: complete exactly
            out = futs[i].get(timeout=120)
            want = [(int(prompts[i][-1]) + 1 + j) % V for j in range(4)]
            assert list(out) == want
        for i in (2, 3):  # owned by prefill at the failure: fail cleanly
            with pytest.raises(OutOfPages, match="injected"):
                futs[i].get(timeout=120)
        eng.drain()  # in-flight accounting survives the failure path
        eng._pool_with_room = orig
        # The prefill thread and the decode lane are still alive.
        out = eng.submit(np.arange(4, dtype=np.int32), 3).get(timeout=120)
        assert list(out) == [(3 + 1 + j) % V for j in range(3)]
        m = eng.metrics()
    finally:
        eng.close()
    assert m["requests_failed"] == 2
    assert m["requests_completed"] == 3
    assert kv.pools[device.key].used_pages == 0


def test_lane_policy_explicit_zero_not_treated_as_unset(device):
    """LanePolicy(token_budget=0) / max_delay_s=0.0 are real bounds, not
    'inherit the default' (matching RequestEngine._lane_bounds)."""
    V, P = 64, 4
    prefill_fn, decode_fn, emb = _toy_paged_model(V=V, P=P)
    kv = PagedKVCache(PageSpec(1, P, 1, 4), devices=[device], pool_pages=64)
    eng = PagedServeEngine(
        kv, prefill_fn, decode_fn, weights=_replicas(emb, [device]), max_seq_len=32,
        scheduler=Scheduler([device]),
        prefill=LanePolicy(max_batch=8, max_delay_s=0.05, token_budget=0),
        decode=LanePolicy(max_batch=64, max_delay_s=0.0),
        name="t-zero")
    try:
        prompts = [np.arange(4, dtype=np.int32) for _ in range(3)]
        futs = [eng.submit(p, 3) for p in prompts]
        for p, f in zip(prompts, futs):
            out = f.get(timeout=120)
            assert list(out) == [(int(p[-1]) + 1 + j) % V for j in range(3)]
        m = eng.metrics()
    finally:
        eng.close()
    # token_budget=0 floors at one row per prefill batch; `x or default`
    # would have read it as unset and batched all three rows together.
    assert m["prefill_batches"] == 3


# ---------------------------------------------------------------------------
# fleet: migration + spread (forced multi-device subprocess, as test_scheduler)
# ---------------------------------------------------------------------------

_FLEET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import Scheduler, get_all_devices, agas
    from repro.serving.paged import PagedKVCache, PagedServeEngine, PageSpec
    from tests.test_paged import _toy_paged_model, _replicas, _fill, _spec, _seq_tokens

    devs = list(get_all_devices().get())
    assert len(devs) == 4

    # -- coalesced migration preserves contents and re-homes the AGAS record
    spec = _spec(P=2)
    kv = PagedKVCache(spec, devices=devs, pool_pages=16)
    seq = kv.new_seq(devs[0])
    kv.append(seq, *_fill(spec, 5, 5))
    src_free = kv.pools[devs[0].key].num_free
    kv.migrate(seq, devs[2])
    assert seq.pool.device.key == devs[2].key
    assert agas.registry.placement(seq.gid).device_key == devs[2].key
    assert kv.pools[devs[0].key].num_free == src_free + 3   # source pages freed
    np.testing.assert_array_equal(_seq_tokens(kv, seq), np.arange(5) + 5000.0)
    kv.migrate(seq, devs[2])  # no-op: already home
    kv.free_seq(seq)

    # -- engine spreads sequences over the fleet, zero padding waste
    V, P = 64, 4
    prefill_fn, decode_fn, emb = _toy_paged_model(V=V, P=P)
    kv = PagedKVCache(PageSpec(1, P, 1, 4), devices=devs, pool_pages=64)
    sched = Scheduler(devs, policy="least_loaded")
    eng = PagedServeEngine(kv, prefill_fn, decode_fn, weights=_replicas(emb, devs),
                           max_seq_len=32, scheduler=sched, name="fleet")
    rng = np.random.default_rng(0)
    futs = []
    for i in range(16):
        plen = int(rng.integers(1, 9))
        prompt = rng.integers(0, V - 16, size=plen).astype(np.int32)
        futs.append((prompt, eng.submit(prompt, max_new_tokens=6)))
    for prompt, f in futs:
        out = f.get(timeout=120)
        want = [(int(prompt[-1]) + 1 + j) % V for j in range(6)]
        assert list(out) == want, (list(out), want)
    m = eng.metrics()
    eng.close()
    assert m["padding_waste"] <= 0.5  # row pad only for warm-shape reuse
    spread = [k for k, v in m["placements"].items() if v > 0]
    assert len(spread) >= 2, m["placements"]
    print("FLEET_OK", len(spread))
""")


def test_paged_fleet_migration_and_spread():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.path.join(os.path.dirname(__file__), ".."),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _FLEET], capture_output=True,
                       text=True, timeout=420, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FLEET_OK" in r.stdout
