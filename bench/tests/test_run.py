"""A whole run of each cell at a tiny size on the CPU, past the harness's
look for a chip: the timed path as it is comes out ``correct``, and with
the timed path broken underneath it comes out not correct, once for each
fault a serving cell can have, and once with the weights stored in
bfloat16 where the configuration states float32.  The fp8 control, put in
the program's place and judged by the configuration's limits, comes out
not correct at the model's widths cut to a size the CPU holds.

The closed loop on a synthetic engine: never more requests in flight than
clients, the next sent only when one resolves, the window's requests
counted by send time, and the clients sending on while they drain.

Also: without a TPU, or without the system under test beside ``bench/``,
``run.py`` exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import loadgen, run, serve

ROOT = Path(__file__).resolve().parents[2]
# The benchmark's one-chip cells, and a closed loop (``conv-batch``, a cell
# that BENCHMARK.json leaves out while the program fails requests when its
# page pool is overcommitted, PERF.md section 7) at a size that does not
# overcommit the pool.
CELLS = [("olmo-1b", "conv"), ("mamba2-130m", "chat-burst"), ("olmo-1b", "conv-batch")]
PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny(config, traffic_mix):
    """A configuration and a traffic mix from their files, cut to a size the
    CPU runs in seconds, as a cell of their own."""
    spec = run.load_json(ROOT / "BENCHMARK.json")
    cfg = run.load_json(run.BENCH / "configs" / f"{config}.json")
    traffic = run.load_json(run.BENCH / "traffic" / f"{traffic_mix}.json")
    wl = {"name": f"{config}.{traffic_mix}", "config": config, "traffic": traffic_mix, "chips": 1}
    m = cfg["model"]
    if cfg["family"] == "dense":
        m.update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=256)
    else:
        m.update(num_layers=2, d_model=64, vocab_size=256)
        m["ssm"] = dict(m["ssm"], d_state=16, head_dim=16, chunk=16)
    cfg["engine"].update(max_seq_len=128, pool_bytes=8 << 20, decode_max_batch=4, decode_shapes=[1, 2, 4])
    if loadgen.is_closed(traffic):  # more clients than the decode cap, as on the chip
        traffic["arrival"].update(clients=6, requests=1024)
    else:
        traffic.update(rate_per_s=6.0)
    traffic.update(lead_in_s=1.0, drain_limit_s=30)
    traffic["prompt_tokens"].update(median=24, min=8, max=64, palette=[16, 32, 64])
    traffic["output_tokens"].update(median=8, min=2, max=16)
    return spec, wl, cfg, traffic


def control_size(config, traffic_mix):
    """As ``tiny``, with widths at which the fp8 control's rounding shows
    as it does at the published ones: the dense model at a d_model of
    1024, the state-space model at its own 768, both over a 4096 vocab."""
    spec, wl, cfg, traffic = tiny(config, traffic_mix)
    m = cfg["model"]
    if cfg["family"] == "dense":
        m.update(d_model=1024, num_heads=16, num_kv_heads=16, d_ff=4096, vocab_size=4096)
    else:
        m.update(d_model=768, num_layers=4, vocab_size=4096)
        m["ssm"] = dict(m["ssm"], d_state=64, head_dim=64)
    return spec, wl, cfg, traffic


def _run(cell, fault=None, control=False, seed=2**31 + 7, size=tiny, chips=1):
    spec, wl, cfg, traffic = size(*cell)
    wl["chips"] = chips
    return run.run_cell(spec, wl, cfg, traffic, seed=seed, seconds=2.0, trace=False,
                        devices=jax.devices()[:chips], peak=PEAK, fault=fault, control=control)


def _state_unchanged(eng):
    """The decode step returns the pages and the resident state it was given."""
    step = eng.decode_fn

    def broken(params, k_pages, v_pages, state, *rest):
        keep = jax.tree_util.tree_map(jnp.copy, (k_pages, v_pages, state))
        _, _, _, logits = step(params, k_pages, v_pages, state, *rest)
        return (*keep, logits)

    eng.decode_fn = broken


def _token_altered(eng):
    """Each decode step's logits favour another token than its best."""
    step = eng.decode_fn

    def broken(*args):
        k, v, st, logits = step(*args)
        other = (jnp.argmax(logits, axis=1) + 1) % logits.shape[1]
        return k, v, st, logits.at[jnp.arange(logits.shape[0]), other].add(100.0)

    eng.decode_fn = broken


def _answer_altered(eng):
    """The prefill step's logits are off where they are produced."""
    step = eng.prefill_fn

    def broken(*args):
        k, v, st, logits = step(*args)
        return k, v, st, logits + 0.5 * jnp.sin(jnp.arange(logits.shape[1], dtype=logits.dtype))

    eng.prefill_fn = broken


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["stats"]["compiles_in_window"] == 0
    assert res["stats"]["sample_tokens"] > 0
    e2e = res["end_to_end"]
    assert e2e["output_tokens_per_s"] > 0 and e2e["latency_p90_s"] > 0 and e2e["setup_s"] > 0
    closed = cell[1] == "conv-batch"
    assert (res["stats"]["clients"] == 6) == closed
    assert (res["stats"]["offered_tokens_in_window"] is None) == closed
    assert (res["stats"]["rate_per_s"] is None) == closed
    # The result line carries the cell's own end-to-end metrics, and the
    # compared numbers last.
    spec = run.load_json(ROOT / "BENCHMARK.json")
    wl = next((w for w in spec["workloads"] if (w["config"], w["traffic"]) == cell),
              {"name": ".".join(cell), "chips": 1})
    line = run.result_line(spec, wl, res, jax.devices(), False)
    assert set(line["metrics"]) == {m["name"] for m in run.metrics_for(spec, wl["name"], "end_to_end")}
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert all(0 < v["value"] < float("inf") for v in line["metrics"].values())
    assert list(line)[-1] == "check"


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered, _answer_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault):
    res = _run(cell, fault=fault)
    assert not res["correct"], res["check"]


def _prompts_fail(eng):
    """Every traffic prompt's prefill raises (warm-up prompts are all ones)."""
    step = eng.prefill_fn

    def broken(params, tokens, extras):
        if int(np.asarray(tokens).max()) > 1:
            raise RuntimeError("planted prefill failure")
        return step(params, tokens, extras)

    eng.prefill_fn = broken


@pytest.mark.parametrize("cell", [CELLS[0], CELLS[2]])
def test_failed_requests_are_counted_with_their_cause(cell):
    res = _run(cell, fault=_prompts_fail)
    assert not res["correct"]
    assert res["failed"] == res["attempted"] > 0
    assert res["stats"]["failed_because"] == {"RuntimeError: planted prefill failure": res["failed"]}
    assert res["end_to_end"]["latency_p90_s"] == float("inf")


def _token_altered_on(device: int):
    """As ``_token_altered``, on one device's decode lane alone."""
    def fault(eng):
        from bench import serve

        step = eng.decode_fn
        broken_id = jax.devices()[device].id

        def broken(params, k_pages, *rest):
            here = serve.device_of(k_pages) == broken_id  # before the step donates the pages
            k, v, st, logits = step(params, k_pages, *rest)
            if not here:
                return k, v, st, logits
            other = (jnp.argmax(logits, axis=1) + 1) % logits.shape[1]
            return k, v, st, logits.at[jnp.arange(logits.shape[0]), other].add(100.0)

        eng.decode_fn = broken
    return fault


def _smallest_sample(config, traffic_mix):
    """As ``tiny``, with a sample no larger than one request per device."""
    spec, wl, cfg, traffic = tiny(config, traffic_mix)
    cfg["check"]["sample_min_tokens"] = 1
    return spec, wl, cfg, traffic


@pytest.mark.parametrize("cell", CELLS[:1])
def test_run_over_two_devices_is_correct(cell):
    res = _run(cell, chips=2)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["stats"]["compiles_in_window"] == 0
    assert set(res["stats"]["steps_by_device"]["decode"]) == {d.id for d in jax.devices()[:2]}
    assert len(res["stats"]["pages_peak"]) == 2


@pytest.mark.parametrize("device", [0, 1])
def test_token_altered_on_one_device_is_not_correct(device):
    """The sample holds a request of each device, so a fault in one
    replica or lane shows whichever device it is on."""
    res = _run(CELLS[0], chips=2, size=_smallest_sample, fault=_token_altered_on(device))
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("cell", CELLS)
def test_warm_up_warms_every_device(cell):
    """After ``warm_up`` over two devices, serving every palette length on
    each of them, several rows a step, compiles nothing."""
    import numpy as np

    from bench import serve

    _, wl, cfg, traffic = tiny(*cell)
    devs = jax.devices()[:2]
    eng = serve.build(cfg, devs, serve.make_weights(cfg, jax.random.PRNGKey(5), devs[0]), name=wl["name"])
    probe = serve.Probe()
    probe.wrap(eng)
    palette = traffic["prompt_tokens"]["palette"]
    serve.warm_up(eng, probe, palette)
    assert {(p[0], p[3]) for p in probe.prefills} == {(T, d.id) for T in palette for d in devs}
    assert {d[3] for d in probe.decodes} == {d.id for d in devs}
    clock = run.CompileClock()
    futs = [eng.submit(np.arange(1, T + 1, dtype=np.int32), 6, request_id=k)
            for k, T in enumerate(palette * 2)]
    for f in futs:
        f.get(timeout=300)
    eng.close()
    assert {d[3] for d in probe.decodes} == {d.id for d in devs}
    assert clock.count == 0


def test_warm_up_stops_when_placement_skips_a_device():
    """A placement that never reaches a device stops the warm-up, rather
    than leaving that device's compiles to the window."""
    from repro.core.scheduler import Scheduler

    from bench import serve

    _, wl, cfg, traffic = tiny(*CELLS[0])
    devs = jax.devices()[:2]
    eng = serve.build(cfg, devs, serve.make_weights(cfg, jax.random.PRNGKey(5), devs[0]), name=wl["name"])
    probe = serve.Probe()
    probe.wrap(eng)
    first = next(pool.device for pool in eng.kv.pools.values() if pool.device.jax_device == devs[0])
    eng._scheduler = Scheduler([first], policy="round_robin")
    try:
        with pytest.raises(RuntimeError, match="placement"):
            serve.warm_up(eng, probe, traffic["prompt_tokens"]["palette"])
    finally:
        eng.close()


def _weights_in_bf16(eng):
    """The served weights are stored in bfloat16 and widened to float32
    inside each step."""
    eng.weights = {k: jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), w)
                   for k, w in eng.weights.items()}
    widen = jax.jit(lambda w: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w))
    prefill, decode = eng.prefill_fn, eng.decode_fn
    eng.prefill_fn = lambda params, *rest: prefill(widen(params), *rest)
    eng.decode_fn = lambda params, *rest: decode(widen(params), *rest)


@pytest.mark.parametrize("cell", CELLS)
def test_weights_in_another_dtype_are_not_correct(cell):
    res = _run(cell, fault=_weights_in_bf16)
    assert res["check"]["stored_dtype_off"]["value"] >= 1
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    res = _run(cell, control=True, size=control_size)
    assert res["correct"], res["check"]
    assert not res["control"]["correct"], res["control"]


@pytest.mark.parametrize("cell", CELLS)
def test_probe_counts_every_output_token(cell):
    """The steps the probe records make exactly the tokens served, pad rows
    of a decode step left out (``output_tokens_per_s`` counts these)."""
    import numpy as np

    from bench import serve

    _, wl, cfg, _ = tiny(*cell)
    devs = jax.devices()[:1]
    eng = serve.build(cfg, devs, serve.make_weights(cfg, jax.random.PRNGKey(3), devs[0]), name=wl["name"])
    probe = serve.Probe()
    probe.wrap(eng)
    lengths = [(16, 40), (32, 30), (16, 35)]  # three rows decoding at once pad to 4
    futs = [eng.submit(np.arange(1, T + 1, dtype=np.int32), n, request_id=i)
            for i, (T, n) in enumerate(lengths)]
    served = sum(np.asarray(f.get(timeout=300)).size for f in futs)
    eng.close()
    assert served == sum(n for _, n in lengths)
    assert sum(n for _, n in probe.produced) == served


def test_per_layer_metrics_have_readers_and_move_reported_metrics():
    spec = run.load_json(ROOT / "BENCHMARK.json")
    for m in spec["per_layer"]:
        assert callable(run._reader(m["name"]))
        for cell in m.get("workloads", [w["name"] for w in spec["workloads"]]):
            assert m["moves"] in {e["name"] for e in run.metrics_for(spec, cell, "end_to_end")}, m


def _bench(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _bench(["--workload", "olmo-1b.conv", "--seed", "1", "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    p = _bench(["--workload", "olmo-1b.conv", "--seed", "1", "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


class _Held:
    """A synthetic engine whose requests resolve only when the test says."""

    def __init__(self):
        self.promises = []
        self.most_open = 0

    def submit(self, prompt, max_new, request_id):
        from repro.core.futures import Promise

        p = Promise()
        self.promises.append((p, max_new))
        self.most_open = max(self.most_open, sum(1 for q, _ in self.promises if not q.get_future().done()))
        return p.get_future()


def _requests(n):
    return [loadgen.Request(i, 0.0, np.ones(4, np.int32), 3) for i in range(n)]


def test_closed_loop_sends_only_when_a_request_resolves():
    eng = _Held()
    loop = serve.ClosedLoop(eng, _requests(50), clients=3)
    loop.run_until(time.perf_counter() + 0.15)
    assert loop.next == 3 and loop.in_flight() == 3
    p, n = eng.promises[1]
    p.set_value(np.zeros(n, np.int32))
    loop.run_until(time.perf_counter() + 0.15)
    assert loop.next == 4 and loop.in_flight() == 3 and loop.sent[1].done is not None
    eng.promises[0][0].set_exception(RuntimeError("failed"))  # found at the next tick
    loop.run_until(time.perf_counter() + 0.25)
    assert loop.next == 5 and loop.in_flight() == 3
    assert eng.most_open == 3
    assert [s.req.rid for s in loop.sent] == [0, 1, 2, 3, 4]
    assert all(s.due == s.sent for s in loop.sent)


class _Timed:
    """A synthetic engine that serves each request in ``serve_s``, and
    records how many were open as each one arrived."""

    def __init__(self, serve_s):
        self.serve_s = serve_s
        self.lock = threading.Lock()
        self.open = 0
        self.arrivals = []  # (time, open before it)
        self.timers = []

    def submit(self, prompt, max_new, request_id):
        from repro.core.futures import Promise

        p = Promise()
        with self.lock:
            self.arrivals.append((time.perf_counter(), self.open))
            self.open += 1

        def finish():
            with self.lock:
                self.open -= 1
            p.set_value(np.zeros(max_new, np.int32))

        t = threading.Timer(self.serve_s * (1 + request_id % 3), finish)
        self.timers.append(t)
        t.start()
        return p.get_future()


def test_closed_loop_window_by_send_time_and_load_through_the_drain():
    clients = 4
    eng = _Timed(0.04)
    loop = serve.ClosedLoop(eng, _requests(400), clients)
    loop.run_until(time.perf_counter() + 0.3)
    ws = time.perf_counter()
    we = ws + 0.6
    loop.run_until(we)
    in_window = [s for s in loop.sent if ws <= s.due < we]
    assert len(in_window) == sum(1 for t, _ in eng.arrivals if ws <= t < we) > clients
    # Sent before the window and resolved in it: not the window's.
    assert any(s.sent < ws <= s.done < we for s in loop.sent if s.done is not None)
    loop.wait(in_window, we + 10.0)
    assert all(s.done is not None for s in in_window)
    after = [s for s in loop.sent if s.sent >= we]
    assert after  # the clients sent on while the window's requests drained
    last_done = max(s.done for s in in_window)
    assert all(s.sent <= last_done + 0.01 for s in after)
    # Never more than the clients in flight: each send past the first
    # ``clients`` follows a resolution.  Each request that resolved before
    # the window's last had a successor sent (one that resolved in the same
    # instant as that last one may not).  A future resolves a moment before
    # its callback stamps ``done``, so the upper count reads the futures.
    assert max(o for _, o in eng.arrivals) == clients - 1
    replaced = len(loop.sent) - clients
    resolved_before = sum(1 for s in loop.sent if s.done is not None and s.done < last_done)
    assert resolved_before - 1 <= replaced <= sum(1 for s in loop.sent if s.future.done())
    for t in eng.timers:
        t.join(timeout=5)
        assert not t.is_alive()


def test_closed_loop_that_runs_dry_stops_the_run():
    eng = _Timed(0.01)
    loop = serve.ClosedLoop(eng, _requests(6), clients=2)
    with pytest.raises(RuntimeError, match="all 6 requests were sent"):
        loop.run_until(time.perf_counter() + 1.0)
    for t in eng.timers:
        t.join(timeout=5)
