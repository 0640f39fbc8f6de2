"""Fig. 9 (extension): continuous-batching + paged serving (DESIGN.md §12, §15).

The ``RequestEngine`` exists to keep accelerators utilized under many
small concurrent requests: per-request dispatch overhead (queue hop,
device_put, executable lookup) is paid once per MICRO-BATCH instead of
once per request.  This benchmark drives identical request streams two
ways:

* ``serial``  — per-request serving: each request runs alone through
  ``Program.run`` and is waited on before the next starts (the no-engine
  baseline every request-level server starts from).
* ``batched`` — all requests submitted concurrently to a
  ``RequestEngine`` (max_batch=8): the engine assembles micro-batches,
  pads to buckets, replays the captured step on an engine stream and
  slices per-request results.

Rows report seconds per request (us_per_call column), with requests/s,
latency p50/p99 and the engine's padded-row waste in the derived field;
a forced-8-device row shows the same stream spread over a fleet by
``least_loaded``.

The ``paged`` rows drive the §15 stack end to end: two toy GQA LMs (a
multi-model fleet) served by ``PagedServeEngine`` — prompts prefilled in
token-budgeted groups, KV paged into per-device pools, one decode lane
per device stepping its residents continuously over page tables.  Rows
report sequences/s, token-latency p99 against the serving SLO
(``REPRO_SERVING_SLO_MS``, default 250), time-to-first-token p99, and
padding waste; generated tokens are asserted identical between the
1-device and 8-device fleets.

**Occupancy model** (the fig6 pattern): a CPU-only runner has one set of
cores behind all forced host devices, so 8 "devices" can never genuinely
beat 1 on raw compute.  As in fig6, each decode step therefore *occupies
its device's real ops-queue lane* for ``rows x _TOK_S`` (a ``time.sleep``
submitted through the lane FIFO — it releases the GIL, so co-located
engines serialize on their shared device while distinct devices overlap
exactly like real hardware), and the real jitted paged-attention math
runs for correctness on top.  Everything the runtime is responsible for —
admission, prefill grouping, page alloc/free, table builds, placement,
warm-shape reuse, donation — is exercised for real; only the per-row
device clock is synthetic.

The ``zoo`` rows replace the toys with REAL zoo architectures (smoke'd):
a dense transformer and an SSM LM served as one fleet through
``PagedServeEngine.from_config`` (DESIGN.md §17) — multi-layer folded
pages, resident recurrent state, host-side sampling — under the same
occupancy model, with 1-device vs 8-device token streams asserted
bit-identical.

jax fixes the device count at first init, so this benchmark re-execs
itself in a subprocess with ``--xla_force_host_platform_device_count=8``
and parses the CSV it prints (the fig6 pattern).  Results land in
``BENCH_serving.json`` via ``benchmarks/run.py``; CI asserts the batched
row beats the serial row and holds >= 0.95x of the 1-device engine's
requests/s when spread over the fleet, that the paged and zoo 8-device
fleets meet or beat their single-device rows on sequences/s, and that
their token p99 is inside the SLO.
"""
from __future__ import annotations

import os
import subprocess
import sys

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           "--xla_cpu_multi_thread_eigen=false "
                           + os.environ.get("XLA_FLAGS", ""))
import time

import numpy as np
import jax
import jax.numpy as jnp
from repro.core import Scheduler, get_all_devices, wait_all
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.partition_map.ref import partition_map_ref
from repro.serving import LanePolicy, PagedKVCache, PagedServeEngine, PageSpec, RequestEngine

quick = bool(int(os.environ.get("BENCH_QUICK", "0")))
N = 256
LOOPS = 2 if quick else 4
R = 32 if quick else 64
REPS = 2 if quick else 3

def step(x):
    def body(i, v):
        return partition_map_ref(v) * 0.5 + v * 0.5
    return jax.lax.fori_loop(0, LOOPS, body, x)

devices = get_all_devices(1, 0).get()
assert len(devices) == 8, devices
dev = devices[0]
rng = np.random.default_rng(0)
payloads = [rng.normal(size=(1, N)).astype(np.float32) for _ in range(R)]

def pct(lats, q):
    ls = sorted(lats)
    return ls[int(q * (len(ls) - 1))]

# --- serial: one request at a time through Program.run ----------------------
prog = dev.create_program({"step": step}, "fig9").get()
prog.run([payloads[0]], "step").get()  # warm the executable

def serial_pass():
    lats = []
    t0 = time.perf_counter()
    for p in payloads:
        t = time.perf_counter()
        prog.run([p], "step").get()
        lats.append(time.perf_counter() - t)
    return time.perf_counter() - t0, lats

serial_pass()
best_wall, best_lats = min((serial_pass() for _ in range(REPS)), key=lambda r: r[0])
ref = [np.asarray(prog.run([p], "step").get()) for p in payloads]
print(f"CSVROW,fig9/serving_serial_1dev,{best_wall / R * 1e6:.1f},"
      f"rps={R / best_wall:.1f};p50_ms={pct(best_lats, 0.5) * 1e3:.2f};"
      f"p99_ms={pct(best_lats, 0.99) * 1e3:.2f};requests={R}")

# --- batched: concurrent submission through the RequestEngine ----------------
def make_batched(sched, name):
    eng = RequestEngine(step, max_batch=8, max_delay_s=0.002, max_queue=4 * R,
                        scheduler=sched, name=name)
    # Warm every bucket route the placement will actually use: jit
    # caches key on (rows x device); sticky placement pins a stream, so
    # either the fleet is covered within a few passes (spread policies)
    # or it never will be (a held home) — 16 passes bounds both.
    for _ in range(16):
        wait_all([eng.submit(p) for p in payloads])
        if len(sched.stats()) >= len(sched.devices()):
            break
    return eng

def batched_rep(eng):
    t0 = time.perf_counter()
    stamped = []
    for p in payloads:
        ts = time.perf_counter()
        f = eng.submit(p)
        # client-observed latency: submit -> slice resolution
        stamped.append(f.then(
            lambda v, ts=ts: (time.perf_counter() - ts, v), executor="inline"
        ))
    wait_all(stamped)
    return time.perf_counter() - t0, stamped

sched1 = Scheduler([dev], policy="least_loaded")
sched8 = Scheduler(devices, policy="least_loaded")
eng1 = make_batched(sched1, "fig9-1dev")
eng8 = make_batched(sched8, "fig9-8dev")
BREPS = 5 if quick else 6
best1 = best8 = None
try:
    # Interleaved reps: the CI gate checks the 8dev/1dev RATIO, so both
    # fleets must sample the same noise environment — two disjoint
    # measurement windows would put the ratio at the mercy of machine-
    # load drift between them.
    for _ in range(BREPS):
        w, s = batched_rep(eng1)
        if best1 is None or w < best1[0]:
            best1 = (w, s)
        w, s = batched_rep(eng8)
        if best8 is None or w < best8[0]:
            best8 = (w, s)
    for label, (wall, stamped), eng, sched in (
            ("1dev", best1, eng1, sched1), ("8dev", best8, eng8, sched8)):
        lats = []
        for want, f in zip(ref, stamped):
            lat, got = f.get()
            lats.append(lat)
            assert got.dtype == want.dtype and np.array_equal(got, want), "diverged"
        m = eng.metrics()
        spread = f"spread={len(sched.stats())};" if label == "8dev" else ""
        print(f"CSVROW,fig9/serving_batched_{label},{wall / R * 1e6:.1f},"
              f"rps={R / wall:.1f};p50_ms={pct(lats, 0.5) * 1e3:.2f};"
              f"p99_ms={pct(lats, 0.99) * 1e3:.2f};"
              f"mean_batch={m['mean_batch_rows']:.1f};"
              f"waste={m['padding_waste']:.3f};{spread}requests={R}")
finally:
    eng1.close()
    eng8.close()

# --- paged: prefill/decode disaggregation over paged KV (DESIGN.md S15) ------
PAGE = 16
MAXLEN = 128
_TOK_S = 10e-3 if quick else 4e-3  # modeled device-s per decode row (docstring)
_PRE_TOK_S = 5e-5    # modeled device-seconds per prefill prompt token
S = 32 if quick else 64  # /2 models: pow-2 seqs per engine = exact warm shape
NEW = 12 if quick else 32
SLO_MS = float(os.environ.get("REPRO_SERVING_SLO_MS", "250"))

_by_jax = {d.jax_device: d for d in devices}

def _occupy(jdev, seconds):
    # Hold the device's REAL lane FIFO for the modeled device time:
    # engines sharing a device serialize here, distinct devices overlap
    # (sleep releases the GIL) — exactly the fig6 occupancy model.
    _by_jax[jdev].ops_queue.submit(lambda: time.sleep(seconds)).get()

def _dev_of(a):
    d = getattr(a, "device", None)
    if callable(d):
        d = d()
    if d is None:
        d = next(iter(a.devices()))
    return d

def make_paged_lm(seed, V, Dm, H, K):
    D = Dm // H
    r = np.random.default_rng(seed)
    s = 1.0 / np.sqrt(Dm)
    shapes = {"emb": (V, Dm), "wq": (Dm, H * D), "wk": (Dm, K * D),
              "wv": (Dm, K * D), "wo": (H * D, Dm), "wu": (Dm, V)}
    params = {n: r.normal(size=sh).astype(np.float32) * s for n, sh in shapes.items()}

    @jax.jit
    def prefill_core(p, tokens, extras):
        x = p["emb"][tokens]                          # (B, T, Dm)
        B, T, _ = x.shape
        k = (x @ p["wk"]).reshape(B, T, K, D)
        v = (x @ p["wv"]).reshape(B, T, K, D)
        q = (x[:, -1] @ p["wq"]).reshape(B, K, H // K, D)  # GQA: grouped heads
        sc = jnp.einsum("bkrd,btkd->bkrt", q, k) / np.sqrt(D)
        o = jnp.einsum("bkrt,btkd->bkrd", jax.nn.softmax(sc, axis=-1), v)
        logits = (o.reshape(B, H * D) @ p["wo"]) @ p["wu"]
        return k[:, None], v[:, None], None, logits

    def decode_core(p, kp, vp, state, tokens, positions, tables, lengths):
        x = p["emb"][tokens]                          # (B, Dm)
        b = tokens.shape[0]
        q = (x @ p["wq"]).reshape(b, H, D)
        k = (x @ p["wk"]).reshape(b, K, D)
        v = (x @ p["wv"]).reshape(b, K, D)
        page = tables[jnp.arange(b), positions // PAGE]
        kp = kp.at[0, page, positions % PAGE].set(k)  # scatter the new token
        vp = vp.at[0, page, positions % PAGE].set(v)
        o = paged_attention_ref(q, kp[0], vp[0], tables, lengths + 1)
        logits = (o.reshape(b, H * D) @ p["wo"]) @ p["wu"]
        return kp, vp, None, logits
    decode_core = jax.jit(decode_core, donate_argnums=(1, 2))

    def prefill_fn(p, tokens, extras):
        _occupy(_dev_of(tokens), tokens.shape[0] * tokens.shape[1] * _PRE_TOK_S)
        return prefill_core(p, tokens, extras)

    def decode_fn(p, kp, vp, state, tokens, positions, tables, lengths):
        _occupy(_dev_of(kp), tokens.shape[0] * _TOK_S)
        return decode_core(p, kp, vp, state, tokens, positions, tables, lengths)

    return params, prefill_fn, decode_fn, decode_core, K, D

# Multi-model fleet: two GQA LMs of different sizes share the scheduler.
# Built ONCE so both fleet labels hit the same jit caches.
MODELS = ((0, 512, 128, 4, 2), (1, 256, 64, 4, 2))
LMS = [make_paged_lm(*m) for m in MODELS]
POOL_PAGES = 192
plens = [4, 8, 16]
work = sorted(
    [(i % 2, plens[int(v)], NEW) for i, v in enumerate(rng.integers(0, 3, size=S))],
    key=lambda t: (t[0], t[1]))  # sorted: deterministic prefill groups

def paged_pass(devs, label):
    # Each request prefills on the device it is placed on, so every
    # (rows, device) prefill shape compiles once; round-robin placement
    # repeats from pass to pass (as in zoo_pass), so the warm pass
    # compiles every shape the measured passes use.
    sched = Scheduler(devs, policy="round_robin")
    # Palette of decode row counts this fleet can see: steady state is
    # seqs-per-engine split over len(devs) lanes; 4x headroom covers skew.
    avg = max(1, -(-(S // 2) // len(devs)))
    shapes = tuple(b for b in (1, 2, 4, 8, 16, 32, 64)
                   if b <= min(S // 2, 4 * avg))
    engines = []
    for (seed, *_), (params, pf, df, core, kh, hd) in zip(MODELS, LMS):
        kv = PagedKVCache(PageSpec(1, PAGE, kh, hd), devices=devs,
                          pool_pages=POOL_PAGES)
        weights = {d.key: jax.device_put(params, d.jax_device) for d in devs}
        engines.append(PagedServeEngine(
            kv, pf, df, weights=weights, max_seq_len=MAXLEN, scheduler=sched,
            prefill=LanePolicy(max_batch=16, max_delay_s=0.05, token_budget=1024),
            decode=LanePolicy(max_batch=64, max_delay_s=0.05),
            decode_shapes=shapes,
            name=f"fig9-paged-{label}-m{seed}"))

    # Prewarm every palette shape on every device OUTSIDE the measured
    # window: jit caches key on (rows x device), so a first use inside a
    # measured rep would charge a ~100ms compile to some token's p99.
    M = MAXLEN // PAGE
    for eng, (_, pf, df, core, kh, hd) in zip(engines, LMS):
        for d in devs:
            sh = (1, POOL_PAGES, PAGE, kh, hd)
            kz = jax.device_put(np.zeros(sh, np.float32), d.jax_device)
            vz = jax.device_put(np.zeros(sh, np.float32), d.jax_device)
            for b in shapes:
                kz, vz, _, _ = core(eng.weights[d.key], kz, vz, None,
                                    np.zeros(b, np.int32),
                                    np.zeros(b, np.int32),
                                    np.zeros((b, M), np.int32),
                                    np.zeros(b, np.int32))
            jax.block_until_ready((kz, vz))

    def one_pass():
        t0 = time.perf_counter()
        futs = [engines[mi].submit(np.arange(plen, dtype=np.int32) % 100, nnew)
                for mi, plen, nnew in work]
        outs = [np.asarray(f.get()) for f in futs]
        return outs, time.perf_counter() - t0

    one_pass()  # warm: compiles the prefill groups and warm decode shapes
    best = None
    for _ in range(REPS):
        for e in engines:
            e.reset_metrics()
        outs, wall = one_pass()
        ms = [e.metrics() for e in engines]
        if best is None or wall < best[1]:
            best = (outs, wall, ms)
    for e in engines:
        e.close()
    outs, wall, ms = best
    rows = sum(m["rows"] for m in ms)
    padded = sum(m["padded_rows"] for m in ms)
    print(f"CSVROW,fig9/serving_paged_{label},{wall / S * 1e6:.1f},"
          f"seqs_per_s={S / wall:.2f};"
          f"p99_tok_ms={max(m['token_latency_p99_s'] for m in ms) * 1e3:.1f};"
          f"ttft_p99_ms={max(m['ttft_p99_s'] for m in ms) * 1e3:.1f};"
          f"waste={(padded / rows) if rows else 0.0:.3f};"
          f"slo_ms={SLO_MS:.0f};migrations={sum(m['migrations'] for m in ms)};"
          f"sequences={S};new_tokens={NEW}")
    return outs

out1 = paged_pass(devices[:1], "1dev")
out8 = paged_pass(devices, "8dev")
# Same prompts, same models, two fleets: greedy tokens must agree bit-for-bit.
assert all(np.array_equal(a, b) for a, b in zip(out1, out8)), "paged fleets diverged"

# --- zoo: real architectures through the paged engine (DESIGN.md S17) --------
# Two model-zoo families (dense transformer + SSM) served as ONE fleet by
# ``PagedServeEngine.from_config`` — the smoke'd real models, not toys:
# multi-layer folded pages, resident recurrent state, host-side sampling.
# The occupancy model wraps the zoo decode step exactly as above.
from repro.configs import get_config, smoke
from repro.models.model import get_model
from repro.serving import SamplingParams

S_ZOO = 16 if quick else 24
NEW_ZOO = 6 if quick else 10
ZOO = ("olmo-1b", "mamba2-130m")
ZOO_CFGS = [smoke(get_config(n)) for n in ZOO]
ZOO_PARAMS = [get_model(c).init(c, jax.random.PRNGKey(i))
              for i, c in enumerate(ZOO_CFGS)]
zoo_work = sorted(
    [(i % 2, (5, 9, 17)[int(v)], NEW_ZOO)
     for i, v in enumerate(rng.integers(0, 3, size=S_ZOO))],
    key=lambda t: (t[0], t[1]))  # sorted: deterministic prefill groups

ZOO_POOL = 96
ZOO_SHAPES = (1, 2, 4, 8)

def zoo_pass(devs, label):
    engines, inners = [], []
    for i, (cfg, params) in enumerate(zip(ZOO_CFGS, ZOO_PARAMS)):
        # Each request prefills on the device it is placed on, so every
        # (rows, device) prefill shape compiles once; a round-robin
        # scheduler per engine makes placement repeat from pass to pass,
        # so the warm pass compiles every shape the measured passes use.
        eng = PagedServeEngine.from_config(
            cfg, params=params, devices=devs, max_seq_len=48,
            pool_pages=ZOO_POOL, scheduler=Scheduler(devs, policy="round_robin"),
            prefill=LanePolicy(max_batch=8, max_delay_s=0.02, token_budget=512),
            decode=LanePolicy(max_batch=8, max_delay_s=0.02),
            decode_shapes=ZOO_SHAPES,
            name=f"fig9-zoo-{label}-m{i}")
        inner = eng.decode_fn
        def wrapped(params, ks, vs, state, tokens, positions, tables, lengths, _in=inner):
            _occupy(_dev_of(ks), tokens.shape[0] * _TOK_S)
            return _in(params, ks, vs, state, tokens, positions, tables, lengths)
        eng.decode_fn = wrapped
        engines.append(eng)
        inners.append(inner)

    # Prewarm every palette row count on every device OUTSIDE the measured
    # window, exactly as paged_pass does: the decode jit keys on
    # (rows x device), and a real-model compile inside a measured rep
    # would charge ~1s to some token's p99.  A throwaway 1-row prefill
    # yields the family's resident-state row template (None for pure
    # transformers); zero slabs of the pool's geometry stand in for the
    # real pools (page 0 is the scatter sink — it is the reserved
    # sentinel, never read back).
    for eng, inner in zip(engines, inners):
        spec = eng.kv.spec
        st = eng.prefill_fn(eng.weights[devs[0].key], np.ones((1, 4), np.int32), None)[2]
        row = (None if st is None
               else jax.tree_util.tree_map(lambda a: np.asarray(a)[0], st))
        sh = (spec.layers, ZOO_POOL, spec.page_size, spec.kv_heads,
              spec.head_dim)
        for d in devs:
            kz = jax.device_put(np.zeros(sh, np.float32), d.jax_device)
            vz = jax.device_put(np.zeros(sh, np.float32), d.jax_device)
            for b in ZOO_SHAPES:
                stb = (None if row is None else jax.tree_util.tree_map(
                    lambda a, _b=b: np.stack([a] * _b), row))
                kz, vz, _, _ = inner(eng.weights[d.key], kz, vz, stb, np.zeros(b, np.int32),
                                     np.zeros(b, np.int32),
                                     np.zeros((b, eng.max_pages), np.int32),
                                     np.zeros(b, np.int32))
            jax.block_until_ready((kz, vz))

    def one_pass():
        t0 = time.perf_counter()
        futs = [engines[mi].submit(
                    np.arange(plen, dtype=np.int32) % (ZOO_CFGS[mi].vocab_size - 1) + 1,
                    nnew)
                for mi, plen, nnew in zoo_work]
        outs = [np.asarray(f.get()) for f in futs]
        return outs, time.perf_counter() - t0

    one_pass()  # warm: prefill groups + decode palette compile here
    best = None
    for _ in range(REPS):
        for e in engines:
            e.reset_metrics()
        outs, wall = one_pass()
        ms = [e.metrics() for e in engines]
        if best is None or wall < best[1]:
            best = (outs, wall, ms)
    for e in engines:
        e.close()
    outs, wall, ms = best
    rows = sum(m["rows"] for m in ms)
    padded = sum(m["padded_rows"] for m in ms)
    print(f"CSVROW,fig9/serving_zoo_{label},{wall / S_ZOO * 1e6:.1f},"
          f"seqs_per_s={S_ZOO / wall:.2f};"
          f"p99_tok_ms={max(m['token_latency_p99_s'] for m in ms) * 1e3:.1f};"
          f"ttft_p99_ms={max(m['ttft_p99_s'] for m in ms) * 1e3:.1f};"
          f"waste={(padded / rows) if rows else 0.0:.3f};"
          f"slo_ms={SLO_MS:.0f};models={len(ZOO)};"
          f"sequences={S_ZOO};new_tokens={NEW_ZOO}")
    return outs

z1 = zoo_pass(devices[:1], "1dev")
z8 = zoo_pass(devices, "8dev")
# Real-model fleets must agree bit-for-bit too (greedy, per-row math).
assert all(np.array_equal(a, b) for a, b in zip(z1, z8)), "zoo fleets diverged"
"""


def run(quick: bool = False):
    env = dict(os.environ)
    env["BENCH_QUICK"] = "1" if quick else "0"
    env["PYTHONPATH"] = "src:" + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=1800,
    )
    rows = []
    for line in proc.stdout.splitlines():
        if line.startswith("CSVROW,"):
            _, name, us, derived = line.split(",", 3)
            rows.append({"name": name, "s": float(us) / 1e6, "derived": derived})
    if len(rows) < 7 or proc.returncode != 0:
        rows.append(
            {"name": "fig9/FAILED", "s": -1.0, "derived": proc.stderr.strip()[-200:].replace(",", ";")}
        )
    return rows
