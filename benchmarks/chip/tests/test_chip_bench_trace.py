"""Reduction of a profiler trace to the per-layer metrics: on a hand-made
trace whose answers are known, and on a small trace recorded on a TPU v5e
(m1.steady, a few chunks) kept beside this file."""
import json
import os
from types import SimpleNamespace

import pytest

from chip_bench_testlib import DATA
import run
import tracefile
import work

DEV = "/device:TPU:0"
MS = 1_000_000      # ns


def hand_made():
    """A 10 ms window: two chunks, each 1 ms of host work, then a step of
    two kernels and a sort; a wait between them."""
    spans = [["bench.window", 0, 10 * MS],
             ["bench.serve_chunk", 0, 4 * MS],
             ["bench.wait", 4 * MS, 2 * MS],
             ["bench.serve_chunk", 6 * MS, 4 * MS]]
    ops, modules = [], []
    for t0 in (0, 6 * MS):
        s = t0 + 1 * MS
        ops += [[DEV, "cache_probe", s, MS // 2],
                [DEV, "gather_pool", s + MS // 2, 2 * MS],
                [DEV, "sort.3", s + 5 * MS // 2, MS // 4]]
        modules.append([DEV, "jit_step(7)", s, 11 * MS // 4])
    return {"ops": ops, "modules": modules, "spans": spans}


def test_hand_made_trace():
    t = tracefile.Summary(hand_made())
    assert t.window_s == pytest.approx(0.010)
    assert t.busy_s() == pytest.approx(2 * 2.75e-3)
    assert t.op_seconds("gather_pool") == pytest.approx(4e-3)
    assert t.op_seconds("cache_probe") == pytest.approx(1e-3)
    assert t.module_seconds("jit_step") == pytest.approx(5.5e-3)
    assert t.host_only_s() == pytest.approx([1.25e-3, 1.25e-3])
    assert t.top_ops()[0] == ["gather_pool", pytest.approx(4e-3)]
    gaps = dict(t.idle_gaps())
    assert gaps["bench.wait"] == pytest.approx(2e-3)
    assert gaps["bench.serve_chunk"] == pytest.approx(2.5e-3)


def test_busy_merges_overlapping_ops():
    b = tracefile.Busy([(0, 5), (3, 8), (10, 12)])
    assert b.iv == [[0, 8], [10, 12]]
    assert b.within(4, 11) == 4 + 1
    assert b.gaps(0, 15) == [(8, 10), (12, 15)]


def readers_on(ex, counts):
    cfg = {"dim": 128, "row_header_bytes": 8, "cache_ways": 8}
    peak = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    r = SimpleNamespace(cfg=cfg, peak=peak, counts=counts,
                        step_work=work.step,
                        trace=tracefile.Summary(ex), hits=1, misses=1,
                        window_reads=1, window_queries=1)
    names = ["host_ms_per_chunk", "step_mfu", "gather_pool_roofline",
             "cache_probe_roofline", "device_idle_share"]
    return {n: run.reader(n)(r) for n in names}


def test_readers_on_hand_made_trace():
    c = {"bags": 64, "lookups": 1000, "hits": 600, "unique": 500,
         "unique_misses": 300}
    got = readers_on(hand_made(), [c, c])
    assert got["host_ms_per_chunk"] == pytest.approx(1.25)
    assert got["device_idle_share"] == pytest.approx(45.0)
    least = 2 * (400 * 136 + 64 * 128 * 4) / 819e9
    assert got["gather_pool_roofline"] == pytest.approx(100 * least / 4e-3)
    assert 0 < got["step_mfu"] < 100 and 0 < got["cache_probe_roofline"] < 100


RECORDED = os.path.join(DATA, "trace_m1_steady.json")


def test_recorded_chip_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    got = readers_on(rec["trace"], rec["counts"])
    for name, want in rec["metrics"].items():
        assert got[name] == pytest.approx(want, rel=1e-9), name
    for name in ("step_mfu", "gather_pool_roofline", "cache_probe_roofline"):
        assert 0 < got[name] <= 100
