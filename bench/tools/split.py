#!/usr/bin/env python3
"""One traced run of a cell, with the device's idle split by the program's
spans.

    python3 bench/tools/split.py --workload <name> --seed <n> \\
        --seconds <s> [--out <file.jsonl>]

Runs ``bench/run.py --trace 1`` in this process and reduces the window's
trace a second time with ``bench.spans.reduce`` before the harness removes
it.  After the harness's result line it prints one JSON line: the window,
the device's busy seconds, the idle by span (benchmark and program spans,
innermost first; ``bench.spans`` says which span an idle gap goes to) and
each span's seconds and summed metadata in the window.  ``--out`` appends
that line to a file too.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up counts from here, as in bench/run.py

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, spans, tracing  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    got: dict = {}
    load = tracing.load

    def load_and_split(path):
        got["split"] = spans.reduce(spans.load(path))
        return load(path)

    tracing.load = load_and_split
    rc = harness.run(["--workload", args.workload, "--seed", args.seed,
                      "--seconds", args.seconds, "--trace", "1"],
                     t_start=T_START)
    red = got.get("split")
    if red is None:
        return rc or 4
    out = {"workload": args.workload, "seed": int(args.seed),
           "window_s": red["window_s"], "busy_s": red["busy_s"],
           "idle_gaps": sorted(red["idle_gaps"].items(),
                               key=lambda kv: -kv[1]),
           "spans": red["spans"]}
    line = json.dumps({"split": out})
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
