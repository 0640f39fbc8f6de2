"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` ``workloads``: a configuration
(``bench/configs/<config>.json``) served under a traffic mix
(``bench/traffic/<traffic>.json``).  One run:

1. puts the checkout's ``src/`` on the path and turns on JAX's persistent
   compilation cache (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache``);
2. fails, printing no result, unless JAX finds a TPU whose ``device_kind``
   is in ``bench/peaks.json``, with as many chips as the cell asks for;
3. makes the weights from the seed on the first of the cell's chips and
   builds ``PagedServeEngine.from_config`` over all of them (one weights
   replica, page pool and decode lane on each, requests placed round robin)
   with the configuration's engine settings;
4. warms every prefill and decode shape the traffic reaches on every chip,
   then runs a lead-in of the cell's own traffic (all of this is ``setup_s``);
5. measures for ``--seconds``: an open loop submits each request at its due
   time, a closed loop keeps its clients' requests in flight; with
   ``--trace 1`` the profiler records the first ``TRACE_SECONDS`` of the
   window;
6. waits for the requests of the window (due in it; for a closed loop, sent
   in it, its clients sending on meanwhile), frees the engine, and checks a
   seeded sample of them against the plain float32 reference, and the
   dtypes of the weights, pages and state the steps were handed against
   the configuration's;
7. prints ``BENCH <part> {...}`` lines, the compared numbers with their
   limits as the last lines of standard error, and one JSON result as the
   last line of standard output.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, each read by ``bench/metrics/<name>.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import Counter  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
TRACE_SECONDS = 10.0


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def cell(workload: str):
    """(benchmark spec, workload entry, configuration, traffic mix)."""
    spec = load_json(ROOT / "BENCHMARK.json")
    wl = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == wl["config"])
    return spec, wl, load_json(ROOT / conf["file"]), load_json(BENCH / "traffic" / f"{wl['traffic']}.json")


def metrics_for(spec: dict, workload: str, kind: str) -> "list[dict]":
    return [m for m in spec[kind] if workload in m.get("workloads", [workload])]


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """Counts XLA backend compiles that JAX's monitoring reports."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def cache_every_program(jax) -> None:
    """Keep every compiled program in the persistent cache, the small and
    fast ones too, so that a run after the first compiles nothing."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; ``inf`` stands for a request that never finished."""
    xs = sorted(values)
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


@dataclass
class RunData:
    """What a per-layer metric reader gets."""
    cfg: dict
    workload: dict
    trace: object            # bench.xplane.Trace, or None
    decodes: list            # Probe.decodes: (padded rows, first pages, lengths, device)
    prefills: list           # Probe prefill prompt lengths, by call index
    samples: list            # (time, in flight, used pages of each pool) within the window
    pool_pages: int          # pages of one pool
    peak: dict
    work: object
    itemsize: int


def counted_since(now: dict, before: dict) -> dict:
    """The engine's plain counters' growth from ``before`` to ``now``."""
    return {k: v - before.get(k, 0) for k, v in now.items() if not isinstance(v, dict)}


def run_cell(spec, wl, cfg, traffic, *, seed: int, seconds: float, trace: bool, devices,
             peak: dict, rate: "float | None" = None, clients: "int | None" = None,
             control: bool = False, fault=None, t_start: float = T_START) -> dict:
    """One run of one cell on ``devices`` (JAX devices); returns the result's parts.
    A request belongs to the window when it is due in it; a closed loop's
    request is due when it is sent, and its latency runs from then."""
    import jax
    import numpy as np

    from bench import check, loadgen, serve
    from bench import xplane as tr

    clock = CompileClock()
    key = jax.random.PRNGKey(int(loadgen.rng_for(seed, 0).integers(2**31 - 1)))
    weights = serve.make_weights(cfg, key, devices[0])
    eng = serve.build(cfg, devices, weights, name=wl["name"])
    if fault is not None:  # tests: break the timed path underneath the probe
        fault(eng)
    probe = serve.Probe()
    probe.wrap(eng)
    e = cfg["engine"]
    reqs = loadgen.schedule(traffic, seed=seed, seconds=seconds, vocab=cfg["model"]["vocab_size"],
                            max_seq_len=e["max_seq_len"], rate=rate)
    serve.warm_up(eng, probe, traffic["prompt_tokens"]["palette"])
    probe.clear()
    pools = list(eng.kv.pools.values())
    t_warm = time.perf_counter()

    origin = time.perf_counter() + 0.05
    closed = loadgen.is_closed(traffic)
    if closed:
        clients = int(clients or traffic["arrival"]["clients"])
        loop = serve.ClosedLoop(eng, reqs, clients)
    else:
        loop = serve.OpenLoop(eng, reqs, origin)
    ws = origin + float(traffic["lead_in_s"])
    we = ws + float(seconds)
    loop.run_until(ws, pools)
    counted_before = eng.counters()
    compiles_before = clock.count
    setup_s = ws - t_start
    t_trace = None
    if trace:
        trace_dir = str(ROOT / ".bench_out" / "trace" / wl["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window_span = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
        window_span.__enter__()
        t_trace = time.perf_counter()
        loop.run_until(min(we, t_trace + TRACE_SECONDS), pools)
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        decodes, prefills = list(probe.decodes), [p[0] if p else None for p in probe.prefills]
    loop.run_until(we, pools)
    counted = counted_since(eng.counters(), counted_before)
    compiles = clock.count - compiles_before
    in_window = [s for s in loop.sent if ws <= s.due < we]
    deadline = we + float(traffic["drain_limit_s"])
    loop.wait(in_window, deadline)
    t_drained = time.perf_counter()
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)

    # Results of the requests due in the window, with the device each was
    # prefilled on.
    prefill_logits, home = {}, {}
    for p in probe.prefills:
        if p is not None:
            key = np.asarray(p[1])[0].tobytes()
            prefill_logits[key] = np.asarray(p[2])[0]
            home[key] = p[3]
    lat, norm, failed, finished, homes = [], [], 0, [], []
    causes = Counter()  # why the window's failed requests failed
    for s in in_window:
        out = None
        if s.future is not None and s.future.done():
            try:
                out = np.asarray(s.future.get())
            except Exception as exc:  # noqa: BLE001 - a failed request is counted
                causes[f"{type(exc).__name__}: {exc}"[:300]] += 1
                out = None
        elif s.future is not None:
            causes["unfinished at the drain limit"] += 1
        if out is None or s.done is None or out.size != s.req.max_new:
            failed += 1
            lat.append(math.inf)
            norm.append(math.inf)
            continue
        lat.append(s.done - s.due)
        norm.append((s.done - s.due) / out.size * 1e3)
        finished.append((s.req.prompt, out))
        homes.append(home.get(s.req.prompt.tobytes()))
    tokens_completed = sum(s.req.max_new for s in loop.sent[:loop.next]
                           if s.done is not None and ws <= s.done < we)
    # Every output token the steps launched inside the window produce: the
    # first of a request from its prefill, one per real row from a decode step.
    tokens_in_window = sum(n for t, n in probe.produced if ws <= t < we)
    late = sorted(s.sent - s.due for s in loop.sent[:loop.next])
    window_samples = [x for x in loop.samples if ws <= x[0] < we]
    backlog = [x[1] for x in window_samples]
    pool_pages = pools[0].num_pages - 1
    pages_peak = [max((x[2][i] for x in window_samples), default=0) for i in range(len(pools))]
    # Steps since warm-up on each device: how the placement spread the work.
    steps_by_device = {kind: dict(Counter(r[3] for r in record if r is not None))
                       for kind, record in (("prefill", probe.prefills), ("decode", probe.decodes))}
    stored_off = sorted((probe.dtypes | {str(pl.k_slab.array().dtype) for pl in eng.kv.pools.values()})
                        - {cfg["dtype"]})
    eng.close()
    for pl in eng.kv.pools.values():
        for slab in (pl.k_slab, pl.v_slab):
            slab.array().delete()
    del eng, pools
    gc.collect()

    # Correctness: a seeded sample against the plain reference.
    ref = check.Reference(cfg, serve.reference_module(cfg), weights, e["max_seq_len"])
    c = cfg["check"]
    picked = check.sample(finished, seed=seed, min_tokens=c["sample_min_tokens"],
                          max_requests=c["sample_max_requests"], homes=homes)
    got = check.compare(ref, picked, prefill_logits)
    got["stored_dtype_off"] = len(stored_off)
    numbers = check.decide(got, c["limits"])
    correct = failed == 0 and bool(picked) and check.within(numbers)
    ctl = None
    if control:  # the control in the program's place, judged by the same limits
        ctl = check.control(ref, picked)
        ctl["correct"] = bool(picked) and check.within(check.decide(ctl, c["limits"]))
    t_checked = time.perf_counter()

    out = {
        "correct": bool(correct), "attempted": len(in_window), "failed": failed,
        "setup_s": setup_s, "memory_peak_bytes": memory_peak, "check": numbers,
        "control": ctl,
        "stats": {
            "seed": seed, "clients": clients if closed else None,
            "rate_per_s": None if closed else rate if rate is not None else traffic["rate_per_s"],
            "compiles_in_window": compiles, "compile_s_total": clock.seconds,
            "warm_up_done_s": t_warm - t_start, "lead_in_s": float(traffic["lead_in_s"]),
            "due_in_window": len(in_window), "completed": len(finished), "failed": failed,
            "failed_because": dict(causes),
            "latency_samples": len(lat), "tokens_in_window": tokens_in_window,
            "tokens_completed_in_window": tokens_completed,
            "lateness_p50_s": late[len(late) // 2] if late else None,
            "lateness_max_s": late[-1] if late else None,
            "backlog_start": backlog[0] if backlog else None,
            "backlog_end": backlog[-1] if backlog else None,
            "backlog_max": max(backlog) if backlog else None,
            "backlog_tenths": [backlog[i * (len(backlog) - 1) // 10] for i in range(11)] if backlog else None,
            "offered_tokens_in_window": None if closed else sum(s.req.max_new for s in in_window),
            "counted_in_window": counted,
            "drain_s": t_drained - we, "check_s": t_checked - t_drained,
            "pages_peak": pages_peak, "pool_pages": pool_pages, "steps_by_device": steps_by_device,
            "sample_requests": got["requests"], "sample_tokens": got["tokens"],
            "stored_dtypes_off": stored_off,
        },
        "end_to_end": {
            "output_tokens_per_s": tokens_in_window / float(seconds),
            "latency_p90_s": percentile(lat, 0.9) if lat else math.inf,
            "norm_latency_p90_ms": percentile(norm, 0.9) if norm else math.inf,
            "setup_s": setup_s,
        },
    }
    if trace:
        t = tr.load(tr.find(trace_dir))
        data = RunData(cfg, wl, t, decodes, prefills, window_samples, pool_pages,
                       peak, serve.work_module(cfg), np.dtype(cfg["dtype"]).itemsize)
        layer = {}
        for m in metrics_for(spec, wl["name"], "per_layer"):
            v = _reader(m["name"])(data)
            if v is not None:
                layer[m["name"]] = {"value": v, "unit": m["unit"]}
        out["per_layer"] = layer
        out["busy_s"] = tr.busy_s(t)
        out["window_s"] = t.window_s
        out["breakdown"] = tr.breakdown(t)
    return out


def result_line(spec, wl, res: dict, jd, trace: bool) -> dict:
    units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
    if trace:
        metrics = res["per_layer"]
    else:
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]], "unit": units[m["name"]]}
                   for m in metrics_for(spec, wl["name"], "end_to_end")}
    device = {"platform": jd[0].platform, "kind": jd[0].device_kind, "count": wl["chips"],
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = res["busy_s"]
        device["window_s"] = res["window_s"]
        line["breakdown"] = res["breakdown"]
    line["check"] = res["check"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override an open loop's rate (the knee sweep); not for measured runs")
    ap.add_argument("--clients", type=int, default=None,
                    help="override a closed loop's clients (the client sweep); not for measured runs")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control over the sample (calibrate.py); not for measured runs")
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no {src / 'repro'}: this checkout lacks the system under test", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    sys.path.insert(0, str(ROOT))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    spec, wl, cfg, traffic = cell(args.workload)
    peaks = load_json(BENCH / "peaks.json")
    import jax

    cache_every_program(jax)
    jd = jax.devices()
    if jd[0].platform != "tpu" or jd[0].device_kind not in peaks or len(jd) < wl["chips"]:
        print(f"bench: needs {wl['chips']} TPU chip(s) of a kind in bench/peaks.json; JAX found "
              f"{len(jd)} {jd[0].platform} device(s) of kind {jd[0].device_kind!r}", file=sys.stderr)
        return 1
    res = run_cell(spec, wl, cfg, traffic, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=jd[:wl["chips"]], peak=peaks[jd[0].device_kind],
                   rate=args.rate, clients=args.clients, control=bool(args.control))
    print("BENCH stats " + json.dumps(res["stats"]), flush=True)
    if args.control:
        print("BENCH control " + json.dumps({"program": {k: v["value"] for k, v in res["check"].items()},
                                             "control": res["control"]}), flush=True)
    print("BENCH end_to_end " + json.dumps(res["end_to_end"]), flush=True)
    for name, v in res["check"].items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(result_line(spec, wl, res, jd, bool(args.trace))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
