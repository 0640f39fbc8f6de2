"""Smoke check that the runtime and full-width olmo-1b paged serving run
on a TPU, through the entry points a user calls.

    python chip_smoke.py            # one chip: runtime path + serving path
    python chip_smoke.py --chips 4  # four chips: serving on 1 vs 4 devices

Phases (each prints one ``SMOKE <phase> {...}`` line):

* ``runtime`` (paper Listings 1-2): ``get_all_devices`` -> ``create_buffer``
  -> ``enqueue_write`` -> ``create_program_with_file`` for the stencil and
  partition-map kernels -> ``Program.run(..., out=[...])`` ->
  ``enqueue_read`` over 2**24 float32, compared with each kernel's
  ``ref.py``; the compiled launch must hold the Pallas kernel
  (``tpu_custom_call``), i.e. it did not run in interpret mode.
* ``serving``: ``PagedServeEngine.from_config(olmo-1b)`` at published
  widths with random weights from ``init(cfg, PRNGKey(seed))`` serves 8
  greedy requests (prompts of 128 and 512 tokens, 32 new tokens each);
  the tokens are checked against a plain float32 teacher-forced
  ``transformer.forward`` at ``highest`` matmul precision on the chip.
* ``fleet`` (``--chips 4`` only): the same 8 requests served on
  ``devices[:1]`` and on all four devices in one process must give the
  same tokens, with decode steps and a weights replica on every device.

Times, memory and engine counters in the ``SMOKE`` lines are smoke output
from one cold run, not measurements.  The last stdout line is
``{"ok": true, "device": {...}}`` only when every phase passed; without a
TPU, or without the repository's ``src/`` beside this file, the script
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SEED = 0
N_ELEMS = 1 << 24               # runtime phase: 64 MiB of float32
STENCIL_BLOCK = 1 << 16          # (512, 128) tiles per grid step
PROMPT_LENS = (128, 512)
N_REQUESTS = 8
NEW_TOKENS = 32
MAX_SEQ_LEN = 1024
POOL_BYTES = 1 << 30             # per device: 512 olmo-1b pages, 8192 tokens

# Kernels vs ref.py, float32 elementwise: the kernel and XLA may round the
# same formula differently (operation order, fused multiply-add,
# transcendental approximations) by a few ulp of values of order 1.
KERNEL_ATOL = 1e-5
# Serving vs the float32 reference.  The engine runs at the default matmul
# precision, which on TPU rounds float32 matmul inputs to bfloat16 (2**-9
# relative); through 16 layers that moves random-weight olmo-1b logits
# (std ~0.8, max ~3.5) by a few hundredths.  A wrong cache, position or
# weight moves them by their own scale (~1).
PREFILL_LOGIT_ATOL = 0.25
# Random weights leave near-ties among the top logits, so the engine's
# greedy choice is judged by the reference logit of the token it chose:
# within this margin of the reference maximum at every position.
CHOICE_MARGIN = 0.25


def _import_repo() -> None:
    """Put this checkout's ``src/`` first on the path; a copy of this file
    without it has nothing to check."""
    if not (SRC / "repro").is_dir():
        raise ImportError(f"no {SRC / 'repro'} beside this script")
    sys.path.insert(0, str(SRC))


def _smoke(phase: str, report: dict) -> None:
    report = {"label": "smoke output from one cold run, not a measurement", **report}
    print(f"SMOKE {phase} {json.dumps(report, default=str)}", flush=True)


class _CompileClock:
    """Sums XLA backend-compile seconds reported by JAX's monitoring."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


# ---------------------------------------------------------------------------
# phase 1: runtime path (paper Listings 1-2)
# ---------------------------------------------------------------------------


def runtime_phase(n: int = N_ELEMS, stencil_block: int = STENCIL_BLOCK) -> dict:
    import jax
    import numpy as np

    from repro.core import Dim3, get_all_devices
    from repro.kernels.partition_map.ref import partition_map_ref
    from repro.kernels.stencil.ref import stencil_ref

    devices = get_all_devices(1, 0).get()
    dev = devices[0]
    host = np.random.default_rng(SEED).normal(size=n).astype(np.float32)
    inbuf = dev.create_buffer(n, np.float32).get()
    inbuf.enqueue_write(0, host).get()
    spec = jax.ShapeDtypeStruct((n,), np.float32)
    kernels_dir = SRC / "repro" / "kernels"
    report = {"platforms": sorted({d.platform for d in devices}), "n": n, "kernels": {}}
    for name, ref, block in (("stencil", stencil_ref, Dim3(stencil_block)),
                             ("partition_map", partition_map_ref, None)):
        prog = dev.create_program_with_file(str(kernels_dir / name / "ops.py")).get()
        compiled = prog.build(name, spec, block=block).get()
        out = dev.create_buffer(n, np.float32).get()
        prog.run([inbuf], name, block=block, out=[out]).get()
        got = out.enqueue_read().get()
        want = np.asarray(jax.jit(ref)(inbuf.array()))
        err = float(np.max(np.abs(got - want)))
        report["kernels"][name] = {
            "max_abs_err": err,
            "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        }
        if not err <= KERNEL_ATOL:
            raise AssertionError(f"{name}: max |kernel - ref| = {err} > {KERNEL_ATOL}")
        # On a TPU the launch must hold the compiled Pallas kernel; without
        # one the kernel runs in interpret mode and lowers to plain HLO.
        if report["kernels"][name]["tpu_custom_call"] != (jax.default_backend() == "tpu"):
            raise AssertionError(f"{name}: compiled kernel presence does not match "
                                 f"the {jax.default_backend()} backend")
    return report


# ---------------------------------------------------------------------------
# phase 2: olmo-1b paged serving against a float32 reference
# ---------------------------------------------------------------------------


def _prompts(vocab: int, lens=PROMPT_LENS, n: int = N_REQUESTS):
    import numpy as np

    rng = np.random.default_rng(SEED)
    return [rng.integers(1, vocab, size=lens[i % len(lens)]).astype(np.int32)
            for i in range(n)]


def serve(cfg, params, devices, prompts, new_tokens: int, *,
          max_seq_len: int = MAX_SEQ_LEN, pool_bytes: int = POOL_BYTES, **engine_kw):
    """Serve ``prompts`` greedily through ``PagedServeEngine.from_config``
    on ``devices`` (``engine_kw``: further engine arguments); returns
    (engine, tokens per request, engine metrics).  The engine is closed;
    its weights replicas stay readable."""
    import numpy as np

    from repro.core.scheduler import Scheduler
    from repro.serving import PagedServeEngine

    eng = PagedServeEngine.from_config(
        cfg, params=params, devices=devices, max_seq_len=max_seq_len,
        pool_bytes=pool_bytes, scheduler=Scheduler(devices, policy="round_robin"),
        name=f"smoke-{cfg.name}-{len(devices)}dev", **engine_kw)
    try:
        futs = [eng.submit(p, new_tokens, request_id=i) for i, p in enumerate(prompts)]
        outs = [np.asarray(f.get(timeout=900)) for f in futs]
        metrics = eng.metrics()
    finally:
        eng.close()
    return eng, outs, metrics


def check_against_reference(cfg, params, eng, prompts, outs) -> dict:
    """Teacher-forced float32 reference at ``highest`` precision over
    prompt + generated tokens: every greedy choice must be within
    ``CHOICE_MARGIN`` of the reference maximum, and the engine's own
    prefill logits within ``PREFILL_LOGIT_ATOL`` of the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer

    @jax.jit
    def ref_logits(p, tokens):
        with jax.default_matmul_precision("highest"):
            return transformer.forward(cfg, p, {"tokens": tokens})[0][0]

    dev_key, weights = next(iter(eng.weights.items()))
    worst_gap, agree, total = 0.0, 0, 0
    last_ref = {}
    for i, (prompt, out) in enumerate(zip(prompts, outs)):
        T = prompt.size
        seq = np.concatenate([prompt, out[:-1]])[None]
        logits = ref_logits(weights, jnp.asarray(seq))[T - 1:]   # (new, V)
        chosen = jnp.take_along_axis(logits, jnp.asarray(out)[:, None], axis=1)[:, 0]
        gaps = np.asarray(jnp.max(logits, axis=1) - chosen)
        worst_gap = max(worst_gap, float(gaps.max()))
        agree += int(np.sum(gaps == 0.0))
        total += gaps.size
        last_ref[i] = np.asarray(logits[0])
    # The engine's prefill executable on the same rows, grouped by length
    # as the prefill lane groups them.
    prefill_err = 0.0
    for T in sorted({p.size for p in prompts}):
        rows = [i for i, p in enumerate(prompts) if p.size == T]
        _, _, _, last = eng.prefill_fn(weights, jnp.stack([prompts[i] for i in rows]), None)
        last = np.asarray(last)
        for j, i in enumerate(rows):
            prefill_err = max(prefill_err, float(np.max(np.abs(last[j] - last_ref[i]))))
    report = {"reference_device": dev_key, "max_choice_gap": worst_gap,
              "choice_margin": CHOICE_MARGIN, "argmax_agreement": agree / total,
              "prefill_max_abs_err": prefill_err,
              "prefill_logit_atol": PREFILL_LOGIT_ATOL}
    if not worst_gap <= CHOICE_MARGIN:
        raise AssertionError(f"a greedy choice is {worst_gap} below the reference max")
    if not prefill_err <= PREFILL_LOGIT_ATOL:
        raise AssertionError(f"prefill logits off the reference by {prefill_err}")
    return report


def serving_phase(cfg, devices, *, prompt_lens=PROMPT_LENS,
                  new_tokens: int = NEW_TOKENS, **serve_kw) -> dict:
    import jax

    from repro.models.model import get_model

    t0 = time.perf_counter()
    clock = _CompileClock()
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(SEED))
    prompts = _prompts(cfg.vocab_size, prompt_lens)
    eng, outs, metrics = serve(cfg, params, devices, prompts, new_tokens, **serve_kw)
    if any(o.size != new_tokens for o in outs):
        raise AssertionError(f"short outputs: {[o.size for o in outs]}")
    report = {"model": cfg.name, "requests": len(prompts), "new_tokens": new_tokens,
              "engine": {k: metrics[k] for k in (
                  "requests_completed", "requests_failed", "prefill_batches",
                  "decode_steps", "padding_waste", "ttft_p99_s",
                  "token_latency_p50_s", "decode_steps_by_device")}}
    report["reference"] = check_against_reference(cfg, params, eng, prompts, outs)
    report["compile_s"] = clock.seconds
    report["wall_s"] = time.perf_counter() - t0
    stats = devices[0].jax_device.memory_stats() or {}
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    return report


# ---------------------------------------------------------------------------
# phase 3 (--chips 4): one device vs the whole fleet, same tokens
# ---------------------------------------------------------------------------


def fleet_phase(cfg, devices, *, prompt_lens=PROMPT_LENS,
                new_tokens: int = NEW_TOKENS, **serve_kw) -> dict:
    import jax
    import numpy as np

    from repro.models.model import get_model

    from repro.serving import LanePolicy

    t0 = time.perf_counter()
    clock = _CompileClock()
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(SEED))
    prompts = _prompts(cfg.vocab_size, prompt_lens)
    # A row's default-precision arithmetic on the TPU depends on the batch
    # shape it is computed in (1 vs 4 prefill rows, 1-2 vs 4-8 decode
    # rows), so both fleets prefill and decode one row at a time: a
    # difference left can only come from placement, replicas or lanes.
    serve_kw = {"prefill": LanePolicy(max_batch=1), "decode": LanePolicy(max_batch=1),
                **serve_kw}
    _, one, _ = serve(cfg, params, devices[:1], prompts, new_tokens, **serve_kw)
    eng, many, metrics = serve(cfg, params, devices, prompts, new_tokens, **serve_kw)
    steps = metrics["decode_steps_by_device"]
    homes = {}
    for key, w in eng.weights.items():
        homes[key] = sorted({str(d) for leaf in jax.tree_util.tree_leaves(w)
                             for d in leaf.devices()})
    report = {"devices": [d.key for d in devices], "decode_steps_by_device": steps,
              "weights_replica_devices": homes,
              "identical_tokens": all((a == b).all() for a, b in zip(one, many)),
              "compile_s": clock.seconds, "wall_s": time.perf_counter() - t0}
    if not report["identical_tokens"]:
        first = {i: int(np.flatnonzero(a != b)[0]) for i, (a, b) in enumerate(zip(one, many))
                 if (a != b).any()}
        raise AssertionError(f"tokens differ between 1 and all devices; request: "
                             f"first differing position {first}")
    idle = [d.key for d in devices if steps.get(d.key, 0) == 0]
    if idle:
        raise AssertionError(f"no decode steps on {idle}")
    want = {d.key: [str(d.jax_device)] for d in devices}
    if homes != want:
        raise AssertionError(f"weights replicas {homes}, want one per device {want}")
    return report


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: runtime + serving phases; 4: the fleet phase only")
    args = ap.parse_args(argv)
    try:
        _import_repo()
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot load the repository: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax

    from repro.configs import get_config
    from repro.core import get_all_devices

    jd = jax.devices()
    if jd[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {jd[0].platform}", file=sys.stderr)
        return 1
    if len(jd) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(jd)}", file=sys.stderr)
        return 1
    devices = get_all_devices().get()[:args.chips]
    cfg = get_config("olmo-1b")
    if args.chips == 1:
        phases = [("runtime", runtime_phase),
                  ("serving", lambda: serving_phase(cfg, devices))]
    else:
        phases = [("fleet", lambda: fleet_phase(cfg, devices))]
    failed = []
    for name, fn in phases:
        try:
            _smoke(name, {"status": "passed", **fn()})
        except Exception:  # noqa: BLE001 - report every phase, then fail
            traceback.print_exc()
            _smoke(name, {"status": "FAILED"})
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": jd[0].platform, "kind": jd[0].device_kind, "count": len(jd)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
