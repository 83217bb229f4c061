"""Runs of one cell, one process each, and the spread of their metrics.

    python3 bench/tools/series.py --workload <name> --seeds 1,2,3 \\
        --seconds <s> [--trace 0|1] [--out <file.jsonl>] [--cold-first DIR]

Each seed is one ``bench/run.py`` process, started after the one before
has ended (a chip belongs to one process at a time; this process never
touches JAX).  For each run it appends one JSON line to ``--out``: the
seed, the exit code, the wall seconds, the result line and the driver's
counters (from the run's log on stderr).  At the end it prints, for
each metric, the values, their median and the quartile spread
(``statistics.quantiles(n=4)``: the third quartile less the first, over
the median), from which the bounds in ``BENCHMARK.json`` are set.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values: list[float]) -> float | None:
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def counters_of(stderr: str) -> dict:
    for line in reversed(stderr.splitlines()):
        if line.startswith("{") and '"what": "counters"' in line:
            return json.loads(line)
    return {}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", default=None)
    ap.add_argument("--cold-first", default=None, metavar="DIR",
                    help="run the first seed with an empty compile cache "
                    "in DIR (made anew), as a fresh checkout's first run")
    args = ap.parse_args()
    runs = []
    for i, seed in enumerate(args.seeds.split(",")):
        env = dict(os.environ)
        if i == 0 and args.cold_first:
            shutil.rmtree(args.cold_first, ignore_errors=True)
            os.makedirs(args.cold_first)
            env["JAX_COMPILATION_CACHE_DIR"] = os.path.abspath(
                args.cold_first)
        t = time.monotonic()
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", args.workload,
             "--seed", seed, "--seconds", args.seconds, "--trace",
             args.trace], cwd=ROOT, capture_output=True, text=True, env=env)
        wall = time.monotonic() - t
        lines = p.stdout.strip().splitlines()
        result = json.loads(lines[-1]) if p.returncode == 0 and lines \
            else None
        run = {"workload": args.workload, "seed": int(seed),
               "cold": bool(i == 0 and args.cold_first),
               "rc": p.returncode, "wall_s": wall, "result": result,
               "counters": counters_of(p.stderr),
               "log": [ln for ln in p.stderr.splitlines()
                       if ln.startswith('{"at"')]}
        if result is None or not result["correct"]:
            run["stderr_tail"] = p.stderr[-4000:]
        runs.append(run)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(run) + "\n")
        print(json.dumps({k: run[k] for k in ("seed", "rc", "wall_s")}
                         | {"correct": result and result["correct"],
                            "metrics": result and {
                                k: v["value"] for k, v in
                                result["metrics"].items()}}), flush=True)
    names = sorted({k for r in runs if r["result"]
                    for k in r["result"]["metrics"]})
    for name in names:
        vals = [r["result"]["metrics"][name]["value"] for r in runs
                if r["result"] and name in r["result"]["metrics"]]
        print(json.dumps({"metric": name, "values": vals,
                          "median": statistics.median(vals),
                          "spread": spread(vals)}), flush=True)
    return 0 if all(r["rc"] == 0 for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
