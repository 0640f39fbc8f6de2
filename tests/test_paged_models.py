"""Model zoo x paged engine parity (DESIGN.md §17).

Every architecture family decodes through ``PagedServeEngine.from_config``
with greedy tokens BIT-IDENTICAL to the padded ``decode_step`` oracle, over
ragged prompt lengths that straddle page boundaries.  Both paths share the
same prefill math (``paged_prefill``); the oracle's dense cache is seeded
from the prefill rows, so the assertion isolates exactly the part that
changed — the ragged paged decode step vs the padded one.

Also here: sampling determinism (same (seed, request_id, position) ->
same tokens at fleet size 1 vs 8), honest AGAS accounting for resident
recurrent state, and the cross-locality prefill -> page-ship -> decode
path over a loopback parcelport.
"""
import functools
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke
from repro.core import agas, get_all_devices
from repro.models.model import get_model, paged_surface
from repro.serving import PagedKVCache, PagedServeEngine, PageSpec, SamplingParams

PAGE = 16          # REPRO_PAGE_SIZE default; PageSpec(page_size=0) resolves to it
MAX_PAGES = 3
MAX_SEQ = MAX_PAGES * PAGE   # oracle cache width == engine table width * P
# partial page / straddles a boundary mid-decode / straddles at prefill
PROMPT_LENS = (5, 14, 17)
MAX_NEW = 6

ZOO = ["olmo-1b", "qwen2-moe-a2.7b", "mamba2-130m", "hymba-1.5b", "whisper-tiny"]


@pytest.fixture(scope="module")
def device():
    return get_all_devices(1, 0).get()[0]


def _setup(name, seed=0):
    cfg = smoke(get_config(name))
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(seed))
    return cfg, params


def _prompts(cfg, rng):
    return [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in PROMPT_LENS]


def _extras(cfg, rng):
    if cfg.family != "encdec":
        return None
    e = cfg.encdec
    return {"frames": rng.normal(0, 0.02, (e.encoder_seq, cfg.d_model)).astype(np.float32)}


# ---------------------------------------------------------------------------
# padded oracle: dense cache seeded from the SAME prefill, decode_step loop
# ---------------------------------------------------------------------------


def _seed_cache(cfg, m, cache, k, v, state, T):
    """Write one prefill row (k/v: (L', T', K, hd) numpy) into the padded
    decode cache, per family layout."""
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        ck = np.asarray(cache["k"]).copy()
        cv = np.asarray(cache["v"]).copy()
        ck[:, 0, : k.shape[1]] = k
        cv[:, 0, : v.shape[1]] = v
        return {"k": jnp.asarray(ck), "v": jnp.asarray(cv)}
    if fam == "ssm":
        # state: {'state': (L, H, N, P), 'conv': (L, W-1, C)} one row
        return {
            "state": jnp.asarray(state["state"])[:, None],
            "conv": jnp.asarray(state["conv"])[:, None],
        }
    if fam == "encdec":
        ck = np.asarray(cache["self_k"]).copy()
        cv = np.asarray(cache["self_v"]).copy()
        ck[:, 0, : k.shape[1]] = k
        cv[:, 0, : v.shape[1]] = v
        return {
            "self_k": jnp.asarray(ck),
            "self_v": jnp.asarray(cv),
            "cross_k": jnp.asarray(state["cross_k"])[:, None],
            "cross_v": jnp.asarray(state["cross_v"])[:, None],
        }
    if fam == "hybrid":
        from repro.models.hybrid import _is_global, kv_producers

        producers = kv_producers(cfg)
        swa = [l for l in producers if not _is_global(cfg, l)]
        glob = [l for l in producers if _is_global(cfg, l)]
        Tp = k.shape[1]  # meta + T: prefill registers meta tokens as pages
        out = {kk: np.asarray(vv).copy() for kk, vv in cache.items()}
        ring = out["swa_k"].shape[2] if swa else 0
        for i, l in enumerate(swa):
            li = producers.index(l)
            for t in range(Tp):  # ring layout: slot t % ring holds token t
                out["swa_k"][i, 0, t % ring] = k[li, t]
                out["swa_v"][i, 0, t % ring] = v[li, t]
        for j, l in enumerate(glob):
            li = producers.index(l)
            out["glob_k"][j, 0, :Tp] = k[li]
            out["glob_v"][j, 0, :Tp] = v[li]
        out["ssm_state"] = np.asarray(state["ssm_state"])[:, None]
        out["ssm_conv"] = np.asarray(state["ssm_conv"])[:, None]
        return {kk: jnp.asarray(vv) for kk, vv in out.items()}
    raise AssertionError(cfg.family)


def _oracle_tokens(cfg, params, prompt, extras, max_new):
    """Greedy tokens from the padded decode path: prefill once via the
    SHARED ``paged_prefill`` (both paths start from identical logits and
    cache rows), then ``decode_step`` over a dense ``MAX_SEQ``-wide cache
    — the width the paged path's masked attend reduces over."""
    m = get_model(cfg)
    tok = jnp.asarray(prompt)[None]
    ex = None
    if extras is not None:
        ex = {kk: jnp.asarray(vv)[None] for kk, vv in extras.items()}
    k, v, state, logits = jax.jit(functools.partial(m.paged_prefill, cfg, params))(tok, ex)
    out = [int(np.argmax(np.asarray(logits)[0]))]
    state = None if state is None else jax.tree_util.tree_map(
        lambda a: np.asarray(a)[0], state)
    cache = m.init_cache(cfg, 1, MAX_SEQ, dtype=jnp.float32)
    cache = _seed_cache(cfg, m, cache, np.asarray(k)[0], np.asarray(v)[0], state, len(prompt))

    dec = jax.jit(functools.partial(m.decode_step, cfg, params))
    T = len(prompt)
    for g in range(max_new - 1):
        # hybrid counts CONTENT tokens (meta offset added inside)
        logits, cache = dec(cache, jnp.asarray([[out[-1]]], jnp.int32),
                            jnp.int32(T + g))
        out.append(int(np.argmax(np.asarray(logits)[0, 0])))
    return out


# ---------------------------------------------------------------------------
# greedy parity: paged engine == padded oracle, bitwise, every family
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ZOO)
def test_zoo_greedy_parity_bitwise(arch, device):
    cfg, params = _setup(arch)
    rng = np.random.default_rng(3)
    prompts = _prompts(cfg, rng)
    extras = _extras(cfg, rng)

    want = [_oracle_tokens(cfg, params, p, extras, MAX_NEW) for p in prompts]

    eng = PagedServeEngine.from_config(
        cfg, params=params, devices=[device], max_seq_len=MAX_SEQ,
        name=f"t-zoo-{arch}")
    try:
        assert eng.max_pages == MAX_PAGES  # oracle width == table width * P
        futs = [eng.submit(p, MAX_NEW, extras=extras) for p in prompts]
        got = [list(np.asarray(f.get(timeout=600))) for f in futs]
    finally:
        eng.close()
    for p, w, g in zip(prompts, want, got):
        assert g == w, f"{arch} T={len(p)}: paged {g} != oracle {w}"


def test_decode_step_writes_each_rows_token_once_in_place():
    """One transformer decode step over a batch padded the way the decode
    lane pads it (pad rows repeat the last row) changes the slabs only at
    ``(l, table[b, pos // P], pos % P)`` for each real row and layer, and
    writes there exactly the K/V the padded oracle makes for that row."""
    from repro.models import transformer
    from repro.serving.paged import zoo_steps

    cfg, params = _setup("olmo-1b")
    rng = np.random.default_rng(11)
    L_, K, D, N = cfg.num_layers, cfg.num_kv_heads, cfg.hd, 8
    shape = (L_, N, PAGE, K, D)
    k0 = rng.normal(size=shape).astype(np.float32)
    v0 = rng.normal(size=shape).astype(np.float32)
    # mid-page / first slot of a new page / last slot of a page; page 0
    # pads the tables, as the pool's sentinel does
    tables = np.asarray([[1, 0, 0], [2, 3, 0], [4, 5, 0]], np.int32)
    positions = np.asarray([5, 16, 31], np.int32)
    tokens = rng.integers(1, cfg.vocab_size, size=3).astype(np.int32)
    pad = lambda a: np.concatenate([a, a[-1:]])  # noqa: E731 - 3 real rows -> 4

    _, _, dec = zoo_steps(cfg)
    k1, v1, _, _ = dec(params, jnp.asarray(k0), jnp.asarray(v0), None, pad(tokens),
                       pad(positions), pad(tables), pad(positions))
    k1, v1 = np.asarray(k1), np.asarray(v1)

    oracle = jax.jit(functools.partial(transformer.decode_step, cfg, params))
    written = np.zeros(shape[:3], bool)
    for b in range(3):
        page, slot = tables[b, positions[b] // PAGE], positions[b] % PAGE
        written[:, page, slot] = True
        # the oracle at the same batch size: the row's pages, read as one
        # dense cache, in each of its 4 rows
        dense = {n: jnp.asarray(np.broadcast_to(
            s[:, tables[b]].reshape(L_, 1, MAX_SEQ, K, D), (L_, 4, MAX_SEQ, K, D)))
            for n, s in (("k", k0), ("v", v0))}
        _, cache = oracle(dense, jnp.full((4, 1), tokens[b], jnp.int32),
                          jnp.int32(positions[b]))
        np.testing.assert_array_equal(k1[:, page, slot], np.asarray(cache["k"])[:, 0, positions[b]])
        np.testing.assert_array_equal(v1[:, page, slot], np.asarray(cache["v"])[:, 0, positions[b]])
    np.testing.assert_array_equal(k1[~written], k0[~written])
    np.testing.assert_array_equal(v1[~written], v0[~written])


def test_zoo_two_model_fleet_interleaved(device):
    """Two engines over different families serve concurrently on one
    device pool without cross-talk (the tutorial §10 shape)."""
    cfg_a, par_a = _setup("olmo-1b")
    cfg_b, par_b = _setup("mamba2-130m")
    rng = np.random.default_rng(7)
    pa, pb = _prompts(cfg_a, rng)[0], _prompts(cfg_b, rng)[1]
    want_a = _oracle_tokens(cfg_a, par_a, pa, None, MAX_NEW)
    want_b = _oracle_tokens(cfg_b, par_b, pb, None, MAX_NEW)

    ea = PagedServeEngine.from_config(cfg_a, params=par_a, devices=[device],
                                      max_seq_len=MAX_SEQ, name="t-fleet-a")
    eb = PagedServeEngine.from_config(cfg_b, params=par_b, devices=[device],
                                      max_seq_len=MAX_SEQ, name="t-fleet-b")
    try:
        fa = ea.submit(pa, MAX_NEW)
        fb = eb.submit(pb, MAX_NEW)
        assert list(np.asarray(fa.get(timeout=600))) == want_a
        assert list(np.asarray(fb.get(timeout=600))) == want_b
    finally:
        ea.close()
        eb.close()


# ---------------------------------------------------------------------------
# sampling: per-request PRNG keyed by (request_id, position)
# ---------------------------------------------------------------------------


def test_sample_token_reproducible_and_param_sensitive():
    from repro.serving import sample_token

    logits = np.random.default_rng(0).normal(size=257)
    sp = SamplingParams(temperature=0.7, top_k=16, top_p=0.9, seed=11)
    a = sample_token(logits, sp, request_id=5, position=3)
    assert a == sample_token(logits, sp, request_id=5, position=3)
    draws = {sample_token(logits, sp, 5, pos) for pos in range(64)}
    assert len(draws) > 1  # position advances the stream
    # greedy ignores the PRNG entirely
    g = sample_token(logits, SamplingParams(), 5, 3)
    assert g == int(np.argmax(logits))
    # top_k=1 is greedy regardless of temperature
    assert sample_token(logits, SamplingParams(temperature=2.0, top_k=1, seed=1), 0, 0) == g


_SAMPLING_CHILD = textwrap.dedent(
    """
    import os, sys
    n = int(sys.argv[1])
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d "
                               "--xla_cpu_multi_thread_eigen=false "
                               + os.environ.get("XLA_FLAGS", "")) % n
    import numpy as np
    import jax
    from repro.configs import get_config, smoke
    from repro.models.model import get_model
    from repro.serving import PagedServeEngine, SamplingParams

    cfg = smoke(get_config("olmo-1b"))
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    eng = PagedServeEngine.from_config(cfg, params=params, max_seq_len=48,
                                       name="t-fleet-sample")
    try:
        rng = np.random.default_rng(9)
        sp = SamplingParams(temperature=0.8, top_k=24, top_p=0.95, seed=13)
        prompts = [rng.integers(1, cfg.vocab_size, size=5 + i).astype(np.int32)
                   for i in range(8)]
        futs = [eng.submit(p, 6, sampling=sp, request_id=1000 + i)
                for i, p in enumerate(prompts)]
        for f in futs:
            print("TOKENS", list(np.asarray(f.get(timeout=600))))
    finally:
        eng.close()
    print("OK", len(jax.devices()))
    """
)


@pytest.mark.slow
def test_sampling_bitwise_across_fleet_sizes():
    """Same seed + request_ids -> the SAME sampled tokens whether the
    fleet is 1 device or 8: the PRNG keys on (seed, request_id,
    position), never on batch composition or placement."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src:" + env.get("PYTHONPATH", "")
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outs = {}
    for n in (1, 8):
        proc = subprocess.run(
            [sys.executable, "-c", _SAMPLING_CHILD, str(n)],
            capture_output=True, text=True, env=env, cwd=cwd, timeout=900)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert f"OK {n}" in proc.stdout, proc.stdout
        outs[n] = [l for l in proc.stdout.splitlines() if l.startswith("TOKENS")]
        assert len(outs[n]) == 8
    assert outs[1] == outs[8], (outs[1], outs[8])


# ---------------------------------------------------------------------------
# resident state: honest bytes through AGAS (spill/placement sees it)
# ---------------------------------------------------------------------------


def test_resident_state_counts_toward_agas_bytes(device):
    spec = PageSpec(layers=1, page_size=4, kv_heads=1, head_dim=2)
    kv = PagedKVCache(spec, devices=[device], pool_pages=8)
    # The AGAS registry is process-global, so other live registrations on
    # this device key are possible — assert deltas, not absolutes.
    start = agas.registry.resident_bytes(device.key)
    seq = kv.new_seq(device)
    k = np.ones((1, 4, 1, 2), np.float32)
    kv.append(seq, k, -k)
    key = next(iter(kv.pools))
    base = kv.stats()[key]["resident_bytes"]
    st = {"a": np.ones((16, 16), np.float32), "b": np.arange(8, dtype=np.int32)}
    seq.set_state(st)
    extra = 16 * 16 * 4 + 8 * 4
    assert seq.nbytes == spec.page_bytes + extra
    assert kv.stats()[key]["resident_bytes"] == base + extra
    # replacing the state re-declares, not accumulates
    seq.set_state({"a": np.ones((4,), np.float32)})
    assert kv.stats()[key]["resident_bytes"] == base + 16
    kv.free_seq(seq)
    assert agas.registry.resident_bytes(device.key) == start


def test_export_import_roundtrip_preserves_state(device):
    spec = PageSpec(layers=2, page_size=4, kv_heads=1, head_dim=2)
    kv = PagedKVCache(spec, devices=[device], pool_pages=16)
    seq = kv.new_seq(device)
    rng = np.random.default_rng(4)
    k = rng.normal(size=(2, 7, 1, 2)).astype(np.float32)
    kv.append(seq, k, -k)
    seq.set_state({"s": rng.normal(size=(3, 5)).astype(np.float32)})
    payload = kv.export_seq(seq)
    assert payload["length"] == 7

    twin = kv.import_seq(device, payload)
    assert twin.length == 7
    np.testing.assert_array_equal(
        np.asarray(twin.state["s"]), np.asarray(seq.state["s"]))
    k2, v2 = kv.export_seq(twin)["k"], kv.export_seq(twin)["v"]
    np.testing.assert_array_equal(k2, payload["k"])
    np.testing.assert_array_equal(v2, payload["v"])
    assert twin.nbytes == seq.nbytes  # identical accounting on the far side
    kv.free_seq(seq)
    kv.free_seq(twin)


# ---------------------------------------------------------------------------
# resident state slabs: each row lives in its sequence's slot on the device
# ---------------------------------------------------------------------------


def _random_row(rng, signature):
    treedef, leaves = signature
    return jax.tree_util.tree_unflatten(
        treedef, [rng.normal(size=shape).astype(dt) for shape, dt in leaves])


def _assert_same_row(got, want):
    jax.tree_util.tree_map(
        lambda g, w: np.testing.assert_array_equal(np.asarray(g), w), got, want)


def _state_round_trip(case, devices):
    """Write a state row, put its sequence through ``case``, and read the
    row back bit-identical (runs in a child with two devices for
    ``migrate``)."""
    spec = PageSpec(layers=1, page_size=4, kv_heads=1, head_dim=2)
    kv = PagedKVCache(spec, devices=devices, pool_pages=16)
    rng = np.random.default_rng(5)
    seq = kv.new_seq(devices[0])
    k = rng.normal(size=(1, 6, 1, 2)).astype(np.float32)
    kv.append(seq, k, -k)
    row = {"s": rng.normal(size=(3, 5)).astype(np.float32),
           "c": rng.normal(size=(2,)).astype(np.float32)}
    seq.set_state(row)
    slab = next(iter(seq.pool.state_slabs.values()))
    back = seq
    if case == "spill_refetch":
        assert seq.spill().get()
        assert slab.num_free == slab.capacity  # a spilled sequence pins no slot
        seq.ensure_resident()
    elif case == "migrate":
        kv.migrate(seq, devices[1])
        assert slab.num_free == slab.capacity
        assert seq.pool.device.key == devices[1].key
        assert [s.device.key for s in seq.pool.state_slabs.values()] == [devices[1].key]
    elif case == "export_import":
        back = kv.import_seq(devices[0], kv.export_seq(seq))
    elif case == "free_reuse":
        kv.free_seq(seq)
        assert kv.new_seq(devices[0]).state is None
        back = kv.new_seq(devices[0])
        row = {"s": rng.normal(size=(3, 5)).astype(np.float32),
               "c": rng.normal(size=(2,)).astype(np.float32)}
        back.set_state(row)
        assert slab.num_free == slab.capacity - 1  # the freed slot, taken again
    _assert_same_row(back.state, row)
    assert back.nbytes == len(back.pages) * spec.page_bytes + (15 + 2) * 4


_MIGRATE_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                               + os.environ.get("XLA_FLAGS", ""))
    from repro.core import get_all_devices
    from tests.test_paged_models import _state_round_trip

    _state_round_trip("migrate", list(get_all_devices().get()))
    print("MIGRATE_OK")
""")


@pytest.mark.parametrize("case", ["spill_refetch", "migrate", "export_import", "free_reuse"])
def test_resident_state_reads_back_bit_identical(case, device):
    if case != "migrate":
        _state_round_trip(case, [device])
        return
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _MIGRATE_CHILD], capture_output=True,
                       text=True, timeout=300, env=env, cwd=root)
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    assert "MIGRATE_OK" in r.stdout


def test_pad_rows_leave_every_other_slot_unchanged(device):
    """Decode steps of one real row padded to two (``decode_shapes=(2,)``)
    write that row's slot alone: rows parked in the other slots read back
    bit-identical, and the tokens are the model's own."""
    cfg, params = _setup("mamba2-130m")
    rng = np.random.default_rng(8)
    prompt = _prompts(cfg, rng)[0]
    want = _oracle_tokens(cfg, params, prompt, None, MAX_NEW)
    eng = PagedServeEngine.from_config(
        cfg, params=params, devices=[device], max_seq_len=MAX_SEQ,
        decode_shapes=(2,), name="t-state-pad")
    try:
        eng.submit(prompt, 2).get(timeout=600)  # makes the slab
        (slab,) = eng.kv.pool_of(device).state_slabs.values()
        parked = []
        for _ in range(3):
            seq = eng.kv.new_seq(device)
            row = _random_row(rng, slab.signature)
            seq.set_state(row)
            parked.append((seq, row))
        got = list(np.asarray(eng.submit(prompt, MAX_NEW).get(timeout=600)))
        c = eng.counters()
    finally:
        eng.close()
    assert got == want
    for seq, row in parked:
        _assert_same_row(seq.state, row)
    assert c["state_rows_on_device"] == 1 + (MAX_NEW - 1)


def test_state_slab_doubles_without_losing_a_row(device):
    spec = PageSpec(layers=1, page_size=4, kv_heads=1, head_dim=2)
    kv = PagedKVCache(spec, devices=[device], pool_pages=16)
    rng = np.random.default_rng(9)
    rows = []
    for _ in range(5):  # a slab made by its first row holds one slot
        seq = kv.new_seq(device)
        row = {"s": rng.normal(size=(4, 3)).astype(np.float32)}
        seq.set_state(row)
        rows.append((seq, row))
    (slab,) = kv.pool_of(device).state_slabs.values()
    assert slab.capacity == 8 and slab.num_free == 3
    assert kv.counters.snapshot()["state_slab_grows"] == 3  # 1 -> 2 -> 4 -> 8
    for seq, row in rows:
        _assert_same_row(seq.state, row)
    for seq, _ in rows:
        kv.free_seq(seq)
    assert slab.num_free == slab.capacity


# ---------------------------------------------------------------------------
# cross-locality: prefill here, ship pages, decode THERE, same tokens
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["olmo-1b", "mamba2-130m"])
def test_cross_locality_page_ship_decode_parity(arch, device):
    """Prefill on this locality, ship the page set + state over the
    parcelport ``invoke`` lane, resume decode on a loopback locality:
    tokens must equal the single-locality engine's (the worker re-derives
    bit-identical params from the config name + seed)."""
    from repro.core import LoopbackParcelport
    from repro.serving.paged import paged_worker_reset

    cfg, params = _setup(arch)
    rng = np.random.default_rng(6)
    prompt = _prompts(cfg, rng)[2]  # page-straddling prefill

    # single-locality reference: the full engine path
    eng = PagedServeEngine.from_config(
        cfg, params=params, devices=[device], max_seq_len=MAX_SEQ,
        name=f"t-ship-ref-{arch}")
    try:
        want = list(np.asarray(eng.submit(prompt, MAX_NEW).get(timeout=600)))
        max_pages = eng.max_pages
    finally:
        eng.close()

    # prefill side: pages + state + first token, exported as one payload
    spec_fn, prefill_fn, _ = paged_surface(cfg)
    kv = PagedKVCache(spec_fn(cfg), devices=[device], pool_pages=32)
    k, v, state, logits = jax.jit(functools.partial(prefill_fn, cfg, params))(
        jnp.asarray(prompt)[None], None)
    seq = kv.new_seq(device)
    kv.append(seq, np.asarray(k)[0], np.asarray(v)[0])
    if state is not None:
        seq.set_state(jax.tree_util.tree_map(lambda a: np.asarray(a)[0], state))
    first = int(np.argmax(np.asarray(logits)[0]))
    shipped = kv.export_seq(seq)
    kv.free_seq(seq)

    port = LoopbackParcelport(n_localities=2)
    try:
        lid = port.localities()[1].process_index
        paged_worker_reset({})
        got = port.call(lid, "invoke", {
            "fn": "repro.serving.paged:paged_worker_decode",
            "payload": {
                "name": f"t-ship-{arch}", "config": arch, "smoke": True,
                "seed": 0, "pool_pages": 32, "seq": shipped,
                "first_token": first, "max_new": MAX_NEW,
                "max_pages": max_pages, "sampling": None, "request_id": 0,
            },
        }).get(timeout=600)
        assert list(np.asarray(got)) == want, (arch, list(np.asarray(got)), want)
    finally:
        paged_worker_reset({})
        port.shutdown()
