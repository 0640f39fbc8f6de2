"""Knee sweep: run one cell at a list of offered rates, or of clients, one
process per point.

    python3 bench/sweep.py --workload <cell> --rates 0.5,1,1.5,2 --seconds 51 --seed 7
    python3 bench/sweep.py --workload <cell> --clients 4,8,16,24,32 --seconds 51 --seed 7

Each rate is a ``run.py --rate`` run with the cell's own arrival process and
lengths.  Prints, per rate, the backlog (requests submitted and not yet
finished) at the window's start and end, its peak and its value at each
tenth of the window, how long the requests due in the window took to drain
after it closed, the failures, the share of the output tokens that the
window's requests ask for that the window made (``made_share``), and the
end-to-end metrics.

The knee is the highest rate at which no request fails and ``made_share``
is at least ``MADE_SHARE``.  A rate that the system sustains makes a little
under all of its offer: the tokens of the requests due near the window's
close come after it, and a lead-in shorter than a request's latency
carries fewer into it.  A system a twentieth short of the rate makes about
a twentieth less again.  The backlog is printed, not judged: where a
request takes longer than the lead-in, it is still filling as the window
opens, so it grows over the window at any rate.  This process never
touches JAX, so each child has the chip to itself.

A closed loop (``--clients``, ``run.py --clients``) holds its load at the
number of clients, so it has no backlog to grow: per client count it
prints the end-to-end metrics, the failures, the share of decode-lane rows
that sat a step out (``decode_sitout_share``) and the pages spilled and
refetched in the window, from the engine's counters, and by how much the
tokens per second rose over the previous count (``gain``).  The cell takes
the smallest count from which the next adds under ``SATURATED``.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"
MADE_SHARE = 0.9
SATURATED = 0.03


def child(workload: str, seed: int, seconds: float, *extra: str) -> dict:
    """One ``run.py --trace 0`` process: its ``BENCH <part> {...}`` lines
    merged into one dict, ``correct`` from its result line."""
    p = subprocess.run([sys.executable, str(RUN), "--workload", workload, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", "0", *extra],
                       capture_output=True, text=True)
    row = {"seed": seed, "rc": p.returncode}
    for line in p.stdout.splitlines():
        if line.startswith("BENCH "):
            row.update(json.loads(line.split(" ", 2)[2]))
        elif line.startswith("{"):
            row["correct"] = json.loads(line)["correct"]
    if p.returncode:
        row["stderr"] = p.stderr[-2000:]
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    points = ap.add_mutually_exclusive_group(required=True)
    points.add_argument("--rates", help="offered rates of an open loop, req/s")
    points.add_argument("--clients", help="client counts of a closed loop")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    if args.clients:
        return clients_sweep(args)
    cols = ("rate", "due_in_window", "failed", "backlog_start", "backlog_end", "backlog_max",
            "drain_s", "output_tokens_per_s", "made_share", "latency_p90_s", "norm_latency_p90_ms",
            "correct", "holds")
    print("SWEEP " + " | ".join(cols), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        row = {"rate": rate, **child(args.workload, args.seed, args.seconds, "--rate", str(rate))}
        if row.get("offered_tokens_in_window"):
            row["made_share"] = row["tokens_in_window"] / row["offered_tokens_in_window"]
            row["holds"] = row["failed"] == 0 and row["made_share"] >= MADE_SHARE
        print("SWEEP " + " | ".join(str(row.get(c)) for c in cols), flush=True)
        print("SWEEPROW " + json.dumps(row), flush=True)
    return 0


def clients_sweep(args) -> int:
    cols = ("clients", "due_in_window", "failed", "output_tokens_per_s", "gain", "latency_p90_s",
            "norm_latency_p90_ms", "decode_sitout_share", "spilled_pages", "refetched_pages",
            "compiles_in_window", "correct")
    print("SWEEP " + " | ".join(cols), flush=True)
    before, rows = None, []
    for n in (int(c) for c in args.clients.split(",")):
        row = {"clients": n, **child(args.workload, args.seed, args.seconds, "--clients", str(n))}
        counted = row.get("counted_in_window") or {}
        if counted.get("decode_active_row_steps"):
            row["decode_sitout_share"] = (100.0 * counted.get("decode_left_out_row_steps", 0)
                                          / counted["decode_active_row_steps"])
        row["spilled_pages"] = counted.get("spilled_pages", 0)
        row["refetched_pages"] = counted.get("refetched_pages", 0)
        tps = row.get("output_tokens_per_s")
        if tps and before:
            row["gain"] = tps / before - 1.0
        before = tps
        print("SWEEP " + " | ".join(str(row.get(c)) for c in cols), flush=True)
        print("SWEEPROW " + json.dumps(row), flush=True)
        rows.append(row)
    saturated = next((a["clients"] for a, b in zip(rows, rows[1:]) if b.get("gain", 1.0) < SATURATED), None)
    failing = [r["clients"] for r in rows if r.get("failed") or r.get("rc")]
    print(f"SWEEP saturated_at {saturated} failing {failing}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
