#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process,
for a cell whose configuration serves ``user_bags``.

    python3 benchmarks/chip/control.py --workload m1.steady \\
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control 3 --seconds 5

For each seed it runs the cell (set-up, warm-up, a window at the cell's own
load) and prints one JSON line with the numbers ``run.py`` compares: the
program's widest pooled gap from the reference and its SCM read
mismatches, beside the run's end-to-end metrics. For the first ``--control`` seeds it also reads the control:
the reference computed with rows held in 4 bits, put in the program's
place, on the same served chunks. The lower reading of a limit is the
program's largest; the upper is the control's smallest.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import reference  # noqa: E402
import run  # noqa: E402
import tables  # noqa: E402


def control_gap(c, m, bits: int = 4) -> float:
    """Widest gap of the ``bits``-bit reference from the 8-bit one, over
    the chunks whose pooled bags the run checks."""
    tr, B = m.tr, m.tr.chunk
    T, D = tr.lens.shape[1], c.cfg["dim"]
    first = tr.warmup // B
    offsets = tables.row_offsets(c.cfg["tables"]["rows"])
    gap = 0.0
    for k, _ in m.sample:
        q0 = (first + k) * B
        _, t, r, starts = reference.chunk_lookups(tr, q0, q0 + B)
        ref = reference.pool(m.seed, offsets, t, r, starts, B * T, D)
        low = reference.pool(m.seed, offsets, t, r, starts, B * T, D, bits)
        gap = max(gap, float(np.abs(low - ref).max()))
    return gap


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    c = run.resolve(run.load_json(os.path.join(run.ROOT, "BENCHMARK.json")),
                    args.workload)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        m = run.measure(c, seed, args.seconds, False,
                        t_start=time.perf_counter())
        out = run.report(c, m)
        line = {"seed": seed, "chunks_checked": len(m.sample),
                "pooled_gap": out["checks"]["pooled_gap"]["value"],
                "sm_ios_mismatch": out["checks"]["sm_ios_mismatch"]["value"],
                "correct": out["correct"],
                "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        if i < args.control:
            line["control_pooled_gap"] = control_gap(c, m)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
