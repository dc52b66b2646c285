"""The device engine's spans, scopes and padding counters as
``enginetrace`` reads them: from a profiler trace of the engine on the
CPU, on a hand-made trace whose answers are known, on recorded chip traces
with and without them, and through a traced run of a tiny cell."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from chip_bench_testlib import DATA, on_cpu, tiny_bench
import enginetrace
import tracefile
from repro.obs import make_telemetry
from repro.obs import tracing as names

DEV = "/device:TPU:0"
MS = 1_000_000      # ns
CHILDREN = [names.SPAN_PACK, names.SPAN_VALIDATE, names.SPAN_DISPATCH,
            names.SPAN_FETCH, names.SPAN_ACCOUNT]


def test_engine_spans_nest_once_per_call(tmp_path):
    """``serve_columnar`` under the profiler on the CPU: each engine span
    once per call, the five steps in order inside ``engine.serve``, which
    sits inside the benchmark's chunk span."""
    import jax
    from repro.core.columnar import ColumnarQueries
    from repro.core.io_sim import DEVICES
    from repro.runtime.engine import DeviceServingEngine, EngineConfig
    rng = np.random.default_rng(5)
    eng = DeviceServingEngine(
        {t: rng.standard_normal((40, 8)).astype(np.float32) for t in (0, 1)},
        DEVICES["nand_flash"], EngineConfig(use_kernels=False))
    chunk = ColumnarQueries.from_requests(
        [{0: np.array([1, 2, 3]), 1: np.array([4])},
         {1: np.array([5, 6])}]).whole()
    eng.serve_columnar(chunk)                      # compile outside the trace
    ann = jax.profiler.TraceAnnotation
    with jax.profiler.trace(str(tmp_path)):
        with ann(tracefile.WINDOW_SPAN):
            for _ in range(2):
                with ann(tracefile.CHUNK_SPAN):
                    eng.serve_columnar(chunk)
    ex = enginetrace.extract(str(tmp_path))
    assert ex["ops"] == [] and ex["scopes"] == {}   # no TPU plane
    got = sorted((s, s + d, n) for n, s, d in ex["spans"])
    assert [n for *_, n in got].count(tracefile.WINDOW_SPAN) == 1
    chunks = [(s, e) for s, e, n in got if n == tracefile.CHUNK_SPAN]
    serves = [(s, e) for s, e, n in got if n == names.SPAN_SERVE]
    assert len(chunks) == len(serves) == 2
    for (c0, c1), (s0, s1) in zip(chunks, serves):
        assert c0 <= s0 and s1 <= c1
        inner = [(s, e, n) for s, e, n in got
                 if s0 <= s and e <= s1 and n != names.SPAN_SERVE]
        assert [n for *_, n in inner] == CHILDREN
        assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))
    summary = enginetrace.EngineSummary(ex)
    assert summary.serves == serves and summary.idle_gaps() == []


def hand_made():
    """A 10 ms window: two chunks, each an engine call with the five steps
    inside it and seven device ops in its fetch, one scoped op of each
    region plus a cache part, a fill scatter and an unscoped copy; a wait
    between the chunks."""
    spans = [["bench.window", 0, 10 * MS], ["bench.wait", 4 * MS, 2 * MS]]
    ops, scopes = [], {
        "cache_probe": "engine.probe",
        "fusion.14": "engine.probe/cache.rematch",
        "gather_pool": "engine.gather", "sort.3": "engine.dedupe",
        "fusion.3": "engine.fill", "scatter.1": "engine.fill/cache.scatter"}
    for t0 in (0, 6 * MS):
        q = MS // 4                                   # 0.25 ms
        spans += [["bench.serve_chunk", t0, 4 * MS],
                  [names.SPAN_SERVE, t0 + q, 14 * q],
                  [names.SPAN_PACK, t0 + q, 3 * q],
                  [names.SPAN_VALIDATE, t0 + 4 * q, q],
                  [names.SPAN_DISPATCH, t0 + 5 * q, q],
                  [names.SPAN_FETCH, t0 + 6 * q, 7 * q],
                  [names.SPAN_ACCOUNT, t0 + 13 * q, 2 * q]]
        e = MS // 8
        for name, s, d in [("cache_probe", 12 * e, 4 * e),
                           ("fusion.14", 16 * e, 2 * e),
                           ("gather_pool", 18 * e, 4 * e),
                           ("sort.3", 22 * e, e), ("fusion.3", 23 * e, e),
                           ("scatter.1", 24 * e, e), ("copy.1", 25 * e, e)]:
            ops.append([DEV, name, t0 + s, d])
    return {"ops": ops, "modules": [], "spans": spans, "labels": {},
            "scopes": scopes}


def engine_run(ex, counters=None):
    tel = None
    if counters is not None:
        tel = make_telemetry(True)
        for k, v in counters.items():
            tel.registry.inc(k, v)
    return SimpleNamespace(trace=enginetrace.EngineSummary(ex), telemetry=tel)


def test_hand_made_engine_trace():
    t = enginetrace.EngineSummary(hand_made())
    self_ms = {k: 1e3 * t.self_s(k) for k in
               ["bench.serve_chunk", names.SPAN_SERVE] + CHILDREN}
    assert self_ms == pytest.approx({
        "bench.serve_chunk": 1.0, names.SPAN_SERVE: 0.0,
        names.SPAN_PACK: 1.5, names.SPAN_VALIDATE: 0.5,
        names.SPAN_DISPATCH: 0.5, names.SPAN_FETCH: 3.5,
        names.SPAN_ACCOUNT: 1.0})
    assert t.scope_s("engine.probe") == pytest.approx(1.5e-3)
    assert t.scope_s("cache.rematch") == pytest.approx(0.5e-3)
    assert t.scope_s("engine.fill") == pytest.approx(0.5e-3)
    assert t.scope_s("engine.nothing") is None
    assert t.scope_table()["unscoped"] == pytest.approx(0.25e-3)
    # idle time goes to the innermost span, and adds up to the window's
    gaps = dict(t.idle_gaps())
    assert gaps == pytest.approx({
        "bench.wait": 2e-3, "bench.serve_chunk": 1e-3,
        names.SPAN_PACK: 1.5e-3, names.SPAN_VALIDATE: 0.5e-3,
        names.SPAN_DISPATCH: 0.5e-3, names.SPAN_ACCOUNT: 1e-3})
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s())
    base = dict(tracefile.Summary(hand_made()).idle_gaps())
    assert sum(base.values()) > t.window_s      # nested spans counted twice
    longest = t.longest_serves(1)[0]
    assert longest["ms"] == pytest.approx(3.5)
    assert longest["busy_ms"] == pytest.approx(1.75)
    assert longest["self_ms"][names.SPAN_FETCH] == pytest.approx(1.75)


@pytest.mark.parametrize("name,want", [
    ("pack_ms_per_chunk", 0.75), ("account_ms_per_chunk", 0.5),
    ("probe_ms_per_chunk", 0.75), ("fill_ms_per_chunk", 0.25),
    ("pad_share", 61.0),
])
def test_readers_on_hand_made_engine_trace(name, want):
    run = engine_run(hand_made(), {"engine.positions": 1000,
                                   "engine.valid_positions": 390})
    assert enginetrace.READERS[name](run) == pytest.approx(want)


PLAIN_TRACE = os.path.join(DATA, "trace_m1_steady.json")


def test_readers_silent_without_engine_instrumentation():
    """A trace and a run without the engine's spans, scopes and handle (the
    program before them) read as nothing, and the idle split is the plain
    summary's, exactly."""
    with open(PLAIN_TRACE) as f:
        ex = json.load(f)["trace"]
    run = engine_run(ex)
    assert {k: f(run) for k, f in enginetrace.READERS.items()} == dict.fromkeys(
        enginetrace.READERS)
    assert run.trace.idle_gaps() == tracefile.Summary(ex).idle_gaps()
    plain = SimpleNamespace(trace=tracefile.Summary(ex), telemetry=None)
    assert all(f(plain) is None for f in enginetrace.READERS.values())


def test_traced_tiny_run_reads_engine(monkeypatch):
    """A traced run of a tiny cell on the CPU: the counters cover the
    window's chunks only, and the padded share equals the reference's
    from its lookups, exactly."""
    run = on_cpu(monkeypatch)
    c = run.resolve(tiny_bench(), "tiny.backlog",
                    traffic_dir=os.path.join(DATA, "traffic"))
    out, ex = enginetrace.trace_cell(c, 2 ** 33 + 7, 0.5)
    assert out["correct"] is True, out["checks"]
    eng = out["engine"]
    spans = [n for n, *_ in ex["spans"]]
    assert spans.count(names.SPAN_SERVE) == eng["chunks"] > 0
    assert eng["counters"]["engine.batches"] == eng["chunks"]
    assert eng["metrics"]["pad_share.sat"] == eng["reference_pad_share"]
    assert 0 < eng["reference_pad_share"] < 100


ENGINE_METRICS = ["pack_ms_per_chunk", "account_ms_per_chunk",
                  "probe_ms_per_chunk", "fill_ms_per_chunk", "pad_share"]


@pytest.mark.parametrize("workload,suffix", [("tiny.steady", ""),
                                             ("tiny.backlog", ".sat")])
def test_traced_tiny_run_line_carries_engine_metrics(monkeypatch, workload,
                                                     suffix):
    """The benchmark's own traced run hands the readers the engine's spans
    and counters: its line carries the host-span readings and the padded
    share, equal to the reference's exactly. The CPU's trace has no device
    plane, so the scope readings read nothing and are left out."""
    run = on_cpu(monkeypatch)
    bench = tiny_bench()
    bench["per_layer"] += [{"name": n + suffix, "unit": "x",
                            "moves": "queries_per_s", "workloads": [workload]}
                           for n in ENGINE_METRICS]
    c = run.resolve(bench, workload,
                    traffic_dir=os.path.join(DATA, "traffic"))
    m = run.measure(c, 2 ** 33 + 9, 0.5, True)
    out = run.report(c, m)
    assert out["correct"] is True, out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"pack_ms_per_chunk" + suffix, "account_ms_per_chunk" + suffix,
            "pad_share" + suffix} <= set(got)
    assert not {"probe_ms_per_chunk" + suffix,
                "fill_ms_per_chunk" + suffix} & set(got)
    assert got["pack_ms_per_chunk" + suffix] > 0
    assert got["pad_share" + suffix] == enginetrace.reference_pad_share(
        m.tr, m.served_k)
    # the handle went on at the window's opening: one batch per window chunk
    assert m.telemetry.registry.counters["engine.batches"] == len(m.served_k)


def test_metric_names_follow_the_cell():
    ex = hand_made()
    run = engine_run(ex, {"engine.positions": 4, "engine.valid_positions": 3})
    assert set(enginetrace.engine_report(run, True)["metrics"]) == {
        k + ".sat" for k in enginetrace.READERS}
    rep = enginetrace.engine_report(run, False)
    assert set(rep["metrics"]) == set(enginetrace.READERS)
    assert rep["chunks"] == 2
    assert sum(v for _, v in rep["idle_gaps"]) == pytest.approx(6.5e-3)


ENGINE_TRACE = os.path.join(DATA, "trace_m1_steady_engine.json")


def test_recorded_engine_chip_trace():
    """A TPU v5e trace of m1.steady with the engine's spans, scopes and
    counters: the readers give what they gave when it was recorded, the
    padded share is the reference's, and the idle time inside the
    benchmark's chunk spans lies with the engine's spans."""
    with open(ENGINE_TRACE) as f:
        rec = json.load(f)
    run = engine_run(rec["trace"], rec["counters"])
    for name, want in rec["metrics"].items():
        assert enginetrace.READERS[name](run) == pytest.approx(want,
                                                               rel=1e-9)
    assert rec["metrics"]["pad_share"] == rec["reference_pad_share"]
    t = run.trace
    assert {p.split("/")[0] for p in t.scopes.values()} == set(
        names.ENGINE_SCOPES)
    gaps = dict(t.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s())
    bench_only = dict(rec["trace"], spans=[
        sp for sp in rec["trace"]["spans"] if sp[0].startswith("bench.")])
    in_chunks = dict(tracefile.Summary(bench_only).idle_gaps())[
        tracefile.CHUNK_SPAN]
    in_engine = sum(v for k, v in gaps.items() if k.startswith("engine."))
    assert 0.9 * in_chunks <= in_engine <= in_chunks
    # the probe and the fill hold most of the step's time outside the
    # gather-pool kernel
    rest = t.module_seconds("jit_step") - t.op_seconds("gather_pool")
    assert t.scope_s("engine.probe") + t.scope_s("engine.fill") > rest / 2
