#!/usr/bin/env python3
"""Readings that the logit limit of ``correct`` is set from, in one process,
for a cell whose configuration serves ``rank``.

    python3 benchmarks/chip/control_rank.py --workload m1rank.backlog \\
        --seeds 1,2 --seconds 10

For each seed it runs the cell (set-up, warm-up, a window) and prints one
JSON line with the program's widest logit gap from the reference, beside
the control's: the reference's dense half at ``default`` matmul precision
(one pass of bfloat16 on a TPU, the next precision below the
configuration's ``highest``), put in the program's place on the same
sampled chunks. The lower reading of the limit is the program's largest;
the upper is the control's smallest.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    c = run.resolve(run.load_json(os.path.join(run.ROOT, "BENCHMARK.json")),
                    args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        m = run.measure(c, seed, args.seconds, False,
                        t_start=time.perf_counter())
        out = run.report(c, m)
        control = 0.0
        for hi, lo in c.served.reference_logits(
                c.cfg, m.tr, seed, [k for k, _ in m.sample],
                ("highest", "default")):
            control = max(control, float(np.abs(lo - hi).max()))
        print(json.dumps({
            "seed": seed, "chunks_checked": len(m.sample),
            "logit_gap": out["checks"]["logit_gap"]["value"],
            "control_logit_gap": control,
            "limit": out["checks"]["logit_gap"]["limit"],
            "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
