"""A whole run of each cell at a tiny size on the CPU, past the harness's
look for a chip: the timed path as it is comes out ``correct``, and with
the timed path broken underneath it comes out not correct, once for each
fault a serving cell can have, and once with the weights stored in
bfloat16 where the configuration states float32.  The fp8 control, put in
the program's place and judged by the configuration's limits, comes out
not correct at the model's widths cut to a size the CPU holds.

Also: without a TPU, or without the system under test beside ``bench/``,
``run.py`` exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
# The benchmark's two cells.
CELLS = [("olmo-1b", "conv"), ("mamba2-130m", "chat-burst")]
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
    traffic.update(rate_per_s=6.0, lead_in_s=1.0, drain_limit_s=30)
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


def _run(cell, fault=None, control=False, seed=2**31 + 7, size=tiny):
    spec, wl, cfg, traffic = size(*cell)
    return run.run_cell(spec, wl, cfg, traffic, seed=seed, seconds=2.0, trace=False,
                        device=jax.devices()[0], peak=PEAK, fault=fault, control=control)


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
    # The result line carries the cell's own end-to-end metrics, and the
    # compared numbers last.
    spec = run.load_json(ROOT / "BENCHMARK.json")
    wl = next(w for w in spec["workloads"] if (w["config"], w["traffic"]) == cell)
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
    dev = jax.devices()[0]
    eng = serve.build(cfg, dev, serve.make_weights(cfg, jax.random.PRNGKey(3), dev), name=wl["name"])
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
