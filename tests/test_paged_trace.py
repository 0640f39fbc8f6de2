"""Spans and counters inside ``PagedServeEngine`` (``repro.core.trace``).

The counters are checked exactly against sizes reckoned from the arrays'
shapes (pow-2 page padding and pad rows included) and against what the
futures returned; the spans and counter events against a CPU profiler
trace read back with ``jax.profiler.ProfileData``; ``metrics()``
percentiles against the bounded record of finished requests.
"""
import glob

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.configs import get_config, smoke
from repro.core import Scheduler, get_all_devices
from repro.core.trace import COUNTER, Counters
from repro.models.model import get_model
from repro.serving import LanePolicy, PagedKVCache, PagedServeEngine
from repro.serving import paged
from repro.serving.paged import zoo_steps

MAX_SEQ = 64  # 4 pages of 16 tokens


@pytest.fixture(scope="module")
def device():
    return get_all_devices(1, 0).get()[0]


def _engine(name, device, **kw):
    cfg = smoke(get_config(name))
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    kw.setdefault("decode_shapes", (2,))
    eng = PagedServeEngine.from_config(
        cfg, params=params, devices=[device], max_seq_len=MAX_SEQ, pool_pages=32,
        scheduler=Scheduler([device]), name=f"t-trace-{name}", **kw)
    return cfg, params, eng


def _page_write_bytes(spec, pages):
    """A page write moves the pow-2 padded index vector and k and v pages."""
    n = 1 << (pages - 1).bit_length()
    return n * 4 + n * spec.page_bytes


@pytest.mark.parametrize("arch", ["olmo-1b", "mamba2-130m"])
def test_host_device_bytes_by_stage_match_the_shapes(arch, device):
    """One request of a 40-token prompt and 4 output tokens: its prefill,
    its 3-page prompt write (padded to 4), then 3 decode steps of 2 rows,
    one of them a pad row (``decode_shapes=(2,)``)."""
    T, new = 40, 4
    cfg, params, eng = _engine(arch, device)
    try:
        out = eng.submit(np.arange(1, T + 1, dtype=np.int32), new).get(timeout=600)
        c = eng.counters()
    finally:
        eng.close()
    assert len(out) == new
    spec = eng.kv.spec
    _, pre, _ = zoo_steps(cfg)
    k, _, state, logits = jax.eval_shape(pre, params, np.zeros((1, T), np.int32), None)
    kv_bytes = 2 * int(np.prod(k.shape)) * k.dtype.itemsize
    row_logits = int(np.prod(logits.shape)) * logits.dtype.itemsize
    row_state = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                    for a in jax.tree_util.tree_leaves(state))
    steps, rows = new - 1, 2
    operands = rows * 4 + 2 * rows * 4 + rows * eng.max_pages * 4  # tokens, lengths x2, tables
    want_h2d = {"prefill_tokens": T * 4,
                "prompt_kv": _page_write_bytes(spec, spec.pages_for(T)),
                "decode_operands": steps * operands}
    want_d2h = {"logits": row_logits * (1 + steps * rows), "prompt_kv": kv_bytes}
    if row_state:
        want_h2d["state"] = steps * rows * row_state
        want_d2h["state"] = row_state + steps * rows * row_state
    assert c["h2d_bytes"] == want_h2d
    assert c["d2h_bytes"] == want_d2h
    assert c["decode_steps"] == steps
    assert c["prefill_tokens"] == T


def test_spill_and_refetch_count_pages_and_bytes(device):
    cfg = smoke(get_config("olmo-1b"))
    spec = zoo_steps(cfg)[0](cfg)
    kv = PagedKVCache(spec, devices=[device], pool_pages=16)
    T = 40  # 3 pages; every move of them pads to 4
    shape = (spec.layers, T, spec.kv_heads, spec.head_dim)
    seq = kv.new_seq(device)
    kv.append(seq, np.ones(shape, np.float32), np.ones(shape, np.float32))
    assert seq.spill().get()
    seq.ensure_resident()
    c = kv.counters.snapshot()
    moved = _page_write_bytes(spec, 3)
    assert c["spilled_pages"] == c["refetched_pages"] == 3
    assert c["h2d_bytes"] == {"prompt_kv": moved, "spill": 4 * 4, "refetch": moved}
    assert c["d2h_bytes"] == {"spill": 4 * spec.page_bytes}
    kv.free_seq(seq)


def _three_under_a_cap_of_two(device):
    """Three 8-token prompts prefill as one group and join the decode lane
    together (both lanes wait out their deadline for the batch to fill);
    the lane steps at most 2 of them at a time."""
    _, _, eng = _engine(
        "olmo-1b", device, decode_shapes=None,
        prefill=LanePolicy(max_batch=3, max_delay_s=1.0, token_budget=1 << 20),
        decode=LanePolicy(max_batch=2, max_delay_s=1.0))
    try:
        futs = [eng.submit(np.arange(1, 9, dtype=np.int32) + i, 4) for i in range(3)]
        outs = [np.asarray(f.get(timeout=600)) for f in futs]
        return outs, eng.counters()
    finally:
        eng.close()


def test_decode_counts_the_rows_that_sit_a_step_out(device):
    _, c = _three_under_a_cap_of_two(device)
    # Rows A, B, C each decode 3 tokens, two a step, survivors rotating to
    # the tail: AB, CA, BC, AB (A and B finish), then C alone.  Four steps
    # with 3 active leave one out each; the last has 1 active.
    assert c["decode_steps"] == 5
    assert c["decode_active_row_steps"] == 3 * 4 + 1
    assert c["decode_left_out_row_steps"] == 4


def test_admitted_and_output_tokens_match_the_futures(device):
    outs, c = _three_under_a_cap_of_two(device)
    assert c["admitted"] == len(outs) == 3
    assert c["output_tokens"] == sum(o.size for o in outs) == 12
    assert c["prefill_tokens"] == 3 * 8
    assert 0 < c["admission_wait_s"] < 3 * 60


def _host_events(log_dir):
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    data = ProfileData.from_file(path)
    plane = next(p for p in data.planes if p.name == "/host:CPU")
    return [(e.name, dict(e.stats)) for line in plane.lines for e in line.events
            if e.name.startswith("paged.") or e.name == COUNTER]


def test_every_span_and_counter_lands_in_a_profiler_trace(device, tmp_path):
    spans = {"paged.prefill.step", "paged.prefill.kv_to_host", "paged.kv.append",
             "paged.kv.spill", "paged.kv.refetch", "paged.decode.table",
             "paged.decode.state_in", "paged.decode.step", "paged.decode.state_out",
             "paged.decode.sample"}
    engines = [_engine(arch, device)[2] for arch in ("olmo-1b", "mamba2-130m")]
    jax.profiler.start_trace(str(tmp_path))
    try:
        for eng in engines:
            eng.submit(np.arange(1, 21, dtype=np.int32), 3).get(timeout=600)
        seq = engines[0].kv.new_seq(device)
        shape = (engines[0].kv.spec.layers, 16, engines[0].kv.spec.kv_heads,
                 engines[0].kv.spec.head_dim)
        engines[0].kv.append(seq, np.ones(shape, np.float32), np.ones(shape, np.float32))
        seq.spill().get()
        seq.ensure_resident()
        engines[0].kv.free_seq(seq)
    finally:
        jax.profiler.stop_trace()
        for eng in engines:
            eng.close()
    events = _host_events(tmp_path)
    assert spans <= {name for name, _ in events}
    # Prefill spans carry the request id, decode spans the lane's step.
    assert all("rid" in st for n, st in events if n.startswith("paged.prefill."))
    assert all("step" in st for n, st in events if n.startswith("paged.decode."))
    # Counter events add up to the totals counted while the trace ran.
    traced: dict = {}
    for name, st in events:
        if name == COUNTER:
            key = (st["counter"], st.get("key"))
            traced[key] = traced.get(key, 0) + st["n"]
    want: dict = {}
    for eng in engines:
        for name, v in eng.counters().items():
            for key, n in (v.items() if isinstance(v, dict) else [(None, v)]):
                want[(name, key)] = want.get((name, key), 0) + n
    assert traced == pytest.approx(want)


def test_counters_keep_plain_and_keyed_totals():
    c = Counters()
    c.add("steps")
    c.add("steps", 2)
    c.add("bytes", 10, key="a")
    c.add("bytes", 5, key="b")
    c.add("bytes", 1, key="a")
    snap = c.snapshot()
    assert snap == {"steps": 3, "bytes": {"a": 11, "b": 5}}
    snap["bytes"]["a"] = 0  # a snapshot is a copy
    assert c.snapshot()["bytes"]["a"] == 11


def test_metrics_percentiles_come_from_the_bounded_record(device, monkeypatch):
    monkeypatch.setattr(paged, "_RECENT_REQUESTS", 3)
    _, _, eng = _engine("olmo-1b", device)
    try:
        outs = [np.asarray(eng.submit(np.arange(1, 9, dtype=np.int32), 2 + i).get(timeout=600))
                for i in range(5)]
        recent = list(eng._recent)
        m = eng.metrics()
        eng.reset_metrics()
        cleared = eng.metrics()
    finally:
        eng.close()
    assert len(recent) == 3  # the record holds the newest 3 of 5
    assert [t.tokens for t in recent] == [o.size for o in outs[-3:]]
    for t in recent:
        assert t.arrived <= t.launched <= t.first_token <= t.last_token
        assert t.gaps.size == t.tokens - 1
        assert t.gaps.sum() == pytest.approx(t.last_token - t.first_token)
    gaps = np.sort(np.concatenate([t.gaps for t in recent]))
    assert m["token_latency_p50_s"] == gaps[int(0.5 * (gaps.size - 1))]
    assert m["token_latency_p99_s"] == gaps[int(0.99 * (gaps.size - 1))]
    ttft = sorted(t.first_token - t.arrived for t in recent)
    assert m["ttft_p99_s"] == ttft[int(0.99 * (len(ttft) - 1))]
    assert "seq_latency_p99_s" not in m
    assert m["decode_steps"] == sum(o.size - 1 for o in outs)
    assert cleared["ttft_p99_s"] == cleared["token_latency_p99_s"] == 0.0
    assert cleared["decode_steps"] == cleared["prefill_tokens"] == 0
