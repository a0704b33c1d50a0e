"""Runs of one cell in sets, and the spread of each metric.

    python3 bench/spread.py --workload tgn-taobao.train \\
        --seeds 11 12 13 14 15 16 --sets 2 --seconds 30 \\
        --out sets

Each run is its own process (``bench/run.py``), as the check makes them;
every set uses the same seeds.  For each metric and set it prints the
spread: the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median, and the
median.  A bound is about five times the widest spread over the cells.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def spread(values: list[float]) -> tuple[float, float]:
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med, med


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    log = out / f"{args.workload}.jsonl"
    sets = []
    for k in range(args.sets):
        rows = []
        for seed in args.seeds:
            p = subprocess.run(
                [sys.executable, str(BENCH / "run.py"), "--workload",
                 args.workload, "--seed", str(seed), "--seconds",
                 str(args.seconds), "--trace", str(args.trace)],
                capture_output=True, text=True, timeout=400)
            line = p.stdout.strip().splitlines()[-1] if p.stdout.strip() \
                else None
            res = json.loads(line) if p.returncode == 0 and line else None
            rec = {"set": k, "seed": seed, "rc": p.returncode,
                   "result": res,
                   "stderr_tail": p.stderr[-1500:] if res is None else ""}
            with open(log, "a") as f:
                f.write(json.dumps(rec) + "\n")
            print(json.dumps(rec), flush=True)
            rows.append(res)
        sets.append(rows)
    for k, rows in enumerate(sets):
        ok = [r for r in rows if r]
        names = sorted({m for r in ok for m in r["metrics"]})
        for m in names:
            vals = [r["metrics"][m]["value"] for r in ok if m in r["metrics"]]
            if len(vals) >= 2 and statistics.median(vals) > 0:
                s, med = spread(vals)
                print(json.dumps({"set": k, "metric": m, "n": len(vals),
                                  "spread": s, "median": med,
                                  "values": vals}), flush=True)
        print(json.dumps({"set": k, "correct": [bool(r and r["correct"])
                                               for r in rows]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
