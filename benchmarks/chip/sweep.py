#!/usr/bin/env python3
"""Find a cell's knee on the chip, in one process.

    python3 benchmarks/chip/sweep.py --workload m1.steady --seed 11 \\
        --seconds 20 --fractions 0.7,0.8,0.9,1.0

First one backlog run of the cell's traffic (every query due when the window
opens) gives the capacity, the queries completed per second. Then one
fixed-rate run at each fraction of it. Each point prints one JSON line:
completed rate, latency quantiles, and the dispatch lag (how late chunks
started after their last query arrived) in the first and last quarter of
the window. The knee is the highest rate at which the lag does not grow
over the window; a cell offered below it writes its rate into its mix file.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def point(c, arrival: dict, seed: int, seconds: float) -> dict:
    c = copy.copy(c)
    c.mix = dict(c.mix, arrival=arrival)
    if arrival["process"] == "backlog":     # no latencies to report
        c.e2e = [m for m in c.e2e if m["name"] in ("queries_per_s",
                                                   "setup_s")]
    out = run.report(c, run.measure(c, seed, seconds, False,
                                    t_start=time.perf_counter()))
    got = {k: v["value"] for k, v in out["metrics"].items()}
    return {"arrival": arrival, "correct": out["correct"],
            "queries_per_s": got.get("queries_per_s"),
            "query_p50_ms": got.get("query_p50_ms"),
            "query_p95_ms": got.get("query_p95_ms"),
            "setup_s": got.get("setup_s"), "lag_ms": out["dispatch_lag_ms"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fractions", default="0.7,0.8,0.9,1.0")
    ap.add_argument("--max-qps", type=float, default=5000.0,
                    help="queries made per second of the backlog run")
    args = ap.parse_args(argv)
    c = run.resolve(run.load_json(os.path.join(run.ROOT, "BENCHMARK.json")),
                    args.workload)
    cap = point(c, {"process": "backlog", "max_qps": args.max_qps},
                args.seed, args.seconds)
    print(json.dumps(cap), flush=True)
    for i, f in enumerate(float(x) for x in args.fractions.split(",")):
        rate = f * cap["queries_per_s"]
        print(json.dumps(dict(point(
            c, {"process": "poisson", "rate_qps": rate},
            args.seed + 1 + i, args.seconds), fraction=f)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
