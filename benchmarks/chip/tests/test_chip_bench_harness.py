"""End-to-end runs of the chip benchmark's harness on the CPU, with the
Pallas kernels interpreted: the printed last line, the exit without a TPU,
a mix and a served path added as a file alone, and faults of the timed
path that ``correct`` has to catch."""
import functools
import json
import os
import subprocess
import sys

import pytest

from chip_bench_testlib import CHIP, DATA, ROOT, TINY, on_cpu, tiny_bench

SEED = 2 ** 33 + 5      # larger than 32 signed bits hold
SECONDS = 0.5


@pytest.fixture
def run_mod(monkeypatch):
    return on_cpu(monkeypatch)


def run_main(run, monkeypatch, tmp_path, workload, trace, bench=None,
             traffic_dir=os.path.join(DATA, "traffic"), served_dir=None):
    """``run.main`` on a tiny BENCHMARK.json; returns (rc, last line)."""
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench or tiny_bench()))
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "resolve", functools.partial(
        run.resolve, traffic_dir=traffic_dir,
        served_dir=served_dir or run.SERVED_DIR))
    import io
    import contextlib
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", workload, "--seed", str(SEED),
                       "--seconds", str(SECONDS), "--trace", str(trace)])
    lines = buf.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("workload,trace,metrics", [
    ("tiny.steady", 0, {"query_p50_ms", "query_p95_ms", "queries_per_s",
                        "setup_s"}),
    ("tiny.backlog", 0, {"queries_per_s", "setup_s"}),
    ("tiny.steady", 1, {"cache_hit_rate"}),
    ("tiny.drift_backlog", 0, {"queries_per_s", "setup_s"}),
])
def test_tiny_run_prints_result_line(run_mod, monkeypatch, tmp_path,
                                     workload, trace, metrics):
    rc, out = run_main(run_mod, monkeypatch, tmp_path, workload, trace)
    assert rc == 0
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == metrics
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert out["checks"]["pooled_gap"]["value"] < 1e-5
    assert out["checks"]["sm_ios_mismatch"]["value"] == 0
    assert out["checks"]["window_compiles"]["value"] == 0
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         "m1.steady", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_mix_added_as_a_file_runs(run_mod, monkeypatch, tmp_path):
    """A new traffic mix is one data file: drift epochs with blend and a
    pooling spread, which puts several padded poolings into one run."""
    traffic = tmp_path / "traffic"
    traffic.mkdir()
    (traffic / "drift.json").write_text(json.dumps({
        "arrival": {"process": "poisson", "rate_qps": 40},
        "arrival_seed": 9, "pool_sigma": 0.5,
        "drift_period_queries": 12, "drift_blend": 0.3,
        "warmup_queries": 8}))
    bench = tiny_bench()
    bench["workloads"].append({"name": "tiny.drift", "config": "tiny",
                               "traffic": "drift", "chips": 1})
    rc, out = run_main(run_mod, monkeypatch, tmp_path, "tiny.drift", 0,
                       bench=bench, traffic_dir=str(traffic))
    assert rc == 0 and out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"queries_per_s", "setup_s"}


@pytest.mark.parametrize("check_scale,correct", [(2.0, True), (1.0, False)])
def test_served_path_added_as_a_file_runs(run_mod, monkeypatch, tmp_path,
                                          check_scale, correct):
    """A configuration names a served-path module that lies in a directory
    of its own; the harness builds, warms up, serves and checks through it
    with no edit, and its ``check`` decides ``correct``."""
    with open(TINY) as f:
        cfg = json.load(f)
    cfg.update(served="scaled_bags", output_scale=2.0,
               check_scale=check_scale)
    path = tmp_path / "scaled.json"
    path.write_text(json.dumps(cfg))
    rc, out = run_main(run_mod, monkeypatch, tmp_path, "tiny.steady", 0,
                       bench=tiny_bench(str(path)),
                       served_dir=os.path.join(DATA, "served"))
    assert rc == 0 and out["correct"] is correct, out["checks"]
    gap = out["checks"]["pooled_gap"]
    assert (gap["value"] <= gap["limit"]) is correct
    assert out["checks"]["sm_ios_mismatch"]["value"] == 0


def _broken_step(kind):
    """A ``_make_step`` whose step has the fault ``kind``."""
    from repro.runtime.engine import DeviceServingEngine
    orig = DeviceServingEngine._make_step

    def make(self):
        step = orig(self)

        def broken(state, payload, scale, bias, idx, valid):
            if kind == "half_batch":
                valid = valid.at[idx.shape[0] // 2:].set(False)
            new, pooled, miss = step(state, payload, scale, bias, idx, valid)
            if kind == "state_unchanged":
                new = state
            if kind == "answer_altered":
                pooled = pooled.at[0, 0, 0].add(1e-3)
            return new, pooled, miss
        return broken
    return make


@pytest.mark.parametrize("kind,caught_by,workload", [
    pytest.param(kind, by, w, id=f"{kind}-{by}{suffix}")
    for w, suffix in (("tiny.steady", ""), ("tiny.drift_backlog", "-drift"))
    for kind, by in (("state_unchanged", "sm_ios_mismatch"),
                     ("half_batch", "pooled_gap"),
                     ("answer_altered", "pooled_gap"))])
def test_broken_timed_path_is_not_correct(run_mod, monkeypatch, tmp_path,
                                          kind, caught_by, workload):
    from repro.runtime.engine import DeviceServingEngine
    monkeypatch.setattr(DeviceServingEngine, "_make_step", _broken_step(kind))
    rc, out = run_main(run_mod, monkeypatch, tmp_path, workload, 0)
    assert rc == 0
    assert out["correct"] is False
    chk = out["checks"][caught_by]
    assert chk["value"] > chk["limit"]
