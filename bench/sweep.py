"""Knee sweep: run one cell at a list of offered rates, one process per rate.

    python3 bench/sweep.py --workload <cell> --rates 0.5,1,1.5,2 --seconds 51 --seed 7

Each rate is a ``run.py --rate`` run with the cell's own arrival process and
lengths.  Prints, per rate, the backlog (requests submitted and not yet
finished) at the window's start and end and its peak, how long the requests
due in the window took to drain after it closed, the failures, and the
end-to-end metrics.  The knee is the highest rate whose backlog does not
grow over the window and at which no request fails.  This process never
touches JAX, so each child has the chip to itself.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cols = ("rate", "due_in_window", "failed", "backlog_start", "backlog_end", "backlog_max",
            "drain_s", "output_tokens_per_s", "latency_p90_s", "norm_latency_p90_ms", "correct")
    print("SWEEP " + " | ".join(cols), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        row = {"rate": rate, **child(args.workload, args.seed, args.seconds, "--rate", str(rate))}
        print("SWEEP " + " | ".join(str(row.get(c)) for c in cols), flush=True)
        print("SWEEPROW " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
