"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds 101-104 --seconds 51

For each seed, one ``run.py --control 1`` process: a run of the cell as the
benchmark makes it, then the compared numbers of the program and of the
control over the same sample, and whether the control, judged by the
configuration's limits, came out correct (it must not).  Prints one
``CALIBRATE`` line per seed and a summary: the program's largest reading
of each number (the lower reading), the control's smallest (the upper
one), and the seeds on which the control passed.  Each seed runs in a process of
its own, so that one engine's device memory never meets the next one's;
this process never touches JAX, so each child has the chip to itself.
"""
from __future__ import annotations

import argparse
import json
import sys

from sweep import child


def seeds(text: str) -> "list[int]":
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi) + 1) if hi else [int(lo)])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-104 or 5,9,2147483650")
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    rows = []
    for seed in seeds(args.seeds):
        row = child(args.workload, seed, args.seconds, "--control", "1")
        row = {k: row[k] for k in ("seed", "rc", "correct", "program", "control", "stderr") if k in row}
        if "control" in row:
            rows.append(row)
        print("CALIBRATE " + json.dumps(row), flush=True)
    if rows:
        summary = {k: {"lower": max(r["program"][k] for r in rows),
                       "upper": min(r["control"][k] for r in rows)}
                   for k in rows[0]["program"] if k in rows[0]["control"]}
        summary["control_correct_on"] = [r["seed"] for r in rows if r["control"]["correct"]]
        print("CALIBRATE summary " + json.dumps(summary), flush=True)
    return 0 if rows else 1


if __name__ == "__main__":
    sys.exit(main())
