"""Benchmark driver — one module per paper figure/table.

Prints ``name,us_per_call,derived`` CSV rows (paper §5 protocol: 11
iterations, first discarded, mean of the remaining 10).  The overhead
module's rows are additionally written to ``BENCH_overhead.json``, the
fig6 multi-device rows (incl. per-policy scheduler rows) to
``BENCH_multidevice.json``, the fig7 remote-transport rows (local vs
loopback vs cluster launch) to ``BENCH_remote.json``, the fig8
stream-overlap rows (1-stream serialized vs 2-stream double-buffered
pipeline) to ``BENCH_overlap.json``, the fig9 serving rows
(continuous batching vs per-request serial, 1 and 8 devices) to
``BENCH_serving.json``, and the fig10 elastic-training rows (tokens/s at
1→4 localities, with and without a mid-run worker kill) to
``BENCH_training.json`` so the native/futurized/graph gap, the
1→4-device scaling trajectory, the parcel-transport tax, the
transfer–compute overlap win, the batching throughput win and the
kill-and-recover training property are all tracked per-PR.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig3,...]

One process per chip: this script never imports JAX.  Each module runs
in a process of its own, one at a time, so at most one process holds the
accelerator (some figures start their own JAX child and must find the
chip free).  All of them share the persistent compilation cache that
``enable_compile_cache`` chooses.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from repro.launch.compile_cache import enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODULES = [
    ("overhead", "benchmarks.overhead"),
    ("fig3", "benchmarks.fig3_stencil"),
    ("fig4", "benchmarks.fig4_partition"),
    ("fig5", "benchmarks.fig5_mandelbrot"),
    ("fig6", "benchmarks.fig6_multidevice"),
    ("fig7", "benchmarks.fig7_remote"),
    ("fig8", "benchmarks.fig8_overlap"),
    ("fig9", "benchmarks.fig9_serving"),
    ("fig10", "benchmarks.fig10_training"),
    ("roofline", "benchmarks.roofline_table"),
]


def _fig6_speedups(rows) -> None:
    """Append ``speedup_vs_dev1`` to every fig6 partition row's derived
    field (the scaling trajectory CI gates on), computed against the
    same-size dev1 row."""
    base = {}
    for r in rows:
        name = str(r.get("name", ""))
        if name.startswith("fig6/partition_n") and name.endswith("_dev1") and r["s"] > 0:
            base[name[: -len("_dev1")]] = r["s"]
    for r in rows:
        name = str(r.get("name", ""))
        stem, sep, _ = name.rpartition("_dev")
        if not (sep and name.startswith("fig6/partition_n")):
            continue
        b = base.get(stem)
        if b and r["s"] > 0:
            d = str(r.get("derived", ""))
            r["derived"] = f"{d};speedup_vs_dev1={b / r['s']:.2f}" if d else f"speedup_vs_dev1={b / r['s']:.2f}"


def _run_module(modname: str, quick: bool) -> "list[dict]":
    """Run one module's ``run(quick)`` in a fresh process; its rows come
    back as JSON on the last stdout line."""
    code = (
        "import importlib, json\n"
        f"rows = importlib.import_module({modname!r}).run(quick={quick!r})\n"
        "print('ROWS ' + json.dumps(rows), flush=True)\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, cwd=ROOT)
    lines = [l for l in proc.stdout.splitlines() if l.startswith("ROWS ")]
    if proc.returncode != 0 or not lines:
        err = (proc.stderr.strip().splitlines() or ["no output"])[-1]
        raise RuntimeError(f"exit {proc.returncode}: {err}")
    return json.loads(lines[-1][len("ROWS "):])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sizes/iterations")
    ap.add_argument("--only", default="", help="comma-separated subset of module tags")
    args = ap.parse_args()
    only = {t for t in args.only.split(",") if t}

    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = 0
    for tag, modname in MODULES:
        if only and tag not in only:
            continue
        try:
            rows = _run_module(modname, args.quick)
            if tag == "fig6":
                _fig6_speedups(rows)
            # Subprocess-based modules report breakage as a */FAILED data
            # row; that must fail the driver (and CI), not pass silently.
            if any(str(r.get("name", "")).endswith("/FAILED") for r in rows):
                failed += 1
            for r in rows:
                derived = str(r.get("derived", "")).replace(",", ";")
                print(f"{r['name']},{r['s'] * 1e6:.1f},{derived}", flush=True)
            json_out = {
                "overhead": "BENCH_overhead.json",
                "fig6": "BENCH_multidevice.json",
                "fig7": "BENCH_remote.json",
                "fig8": "BENCH_overlap.json",
                "fig9": "BENCH_serving.json",
                "fig10": "BENCH_training.json",
            }.get(tag)
            if json_out:
                payload = {
                    "quick": args.quick,
                    "rows": [
                        {"name": r["name"], "us": r["s"] * 1e6, "derived": str(r.get("derived", ""))}
                        for r in rows
                    ],
                }
                with open(json_out, "w") as fh:
                    json.dump(payload, fh, indent=2)
        except Exception as e:  # noqa: BLE001
            failed += 1
            msg = str(e).replace(",", ";")
            print(f"{tag}/CRASHED,-1,{msg}", flush=True)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
