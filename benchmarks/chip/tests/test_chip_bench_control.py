"""The control of ``correct`` at a size a test run holds, under a fixed
pooling and under drifting hot sets with spread pooling: the reference
with rows in 4 bits, put in the program's place, reads far above the
pooled-gap limit, and the program reads far below it."""
import time

import pytest

from chip_bench_testlib import DATA, on_cpu, tiny_bench


@pytest.mark.parametrize("seed,workload", [
    pytest.param(seed, w, id=f"{seed}{suffix}")
    for w, suffix in (("tiny.steady", ""), ("tiny.drift_backlog", "-drift"))
    for seed in (7, 2 ** 32 + 1)])
def test_control_fails_where_the_program_passes(monkeypatch, seed, workload):
    import os
    run = on_cpu(monkeypatch)
    import control
    import reference
    c = run.resolve(tiny_bench(), workload, os.path.join(DATA, "traffic"))
    m = run.measure(c, seed, 0.5, False, t_start=time.perf_counter())
    checks, _ = c.served.check(c.cfg, m.tr, seed, m.served, m.prog_reads,
                               m.sample)
    assert checks["sm_ios_mismatch"][0] == 0
    assert checks["pooled_gap"][0] < reference.POOLED_GAP_LIMIT / 100
    assert control.control_gap(c, m) > 10 * reference.POOLED_GAP_LIMIT
