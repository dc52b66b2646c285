#!/usr/bin/env python3
"""Chip benchmark of the device serving path: one run of one cell.

    python3 benchmarks/chip/run.py --workload m1.steady --seed 7 \\
        --seconds 20 --trace 0

The cell (``BENCHMARK.json``) names a configuration file and a traffic mix
file; nothing in this file belongs to one cell. One process:

1. makes the run's traffic from the seed (``traffic.py``) and the tables on
   the device (``tables.py``), and hands the tables to
   ``DeviceServingEngine``;
2. warms up by serving the leading chunks of the same traffic untimed, plus
   one chunk of each ``[B, T, P]`` shape the window holds that the warm-up
   did not (set-up ends here: ``setup_s``);
3. serves the window open loop, one chunk per ``serve_columnar`` call. A
   chunk is dispatched once its last query has arrived, or at once when the
   engine has fallen behind; a query's latency runs from its arrival to the
   moment its chunk's pooled bags are on the host. A fixed-rate window
   serves every query due in it, however long after the close; a backlog
   window has every query due at its opening and counts what completed by
   its close;
4. checks what the window served against the plain reference
   (``reference.py``) and prints the result as the last line of stdout.

With ``--trace 1`` the window runs under the profiler and the line carries
the cell's per-layer metrics, read by ``metrics/<name>.py`` (a name with a
``.variant`` suffix falls back to the reader of its base name).

With no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import peaks as peaks_mod  # noqa: E402
import reference  # noqa: E402
import tables  # noqa: E402
import tracefile  # noqa: E402
import traffic as traffic_mod  # noqa: E402

POOLED_SAMPLE_CHUNKS = 32     # window chunks whose pooled bags are checked
DRAIN_LIMIT_S = 60.0          # a window query unanswered this long after
                              # the close counts as failed
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    pass


class RanOut(RuntimeError):
    pass


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def resolve(bench: dict, workload: str,
            traffic_dir: str = os.path.join(HERE, "traffic")):
    """The cell ``workload`` of ``bench`` with its configuration, mix and
    metric entries. Mixes are ``<traffic_dir>/<traffic>.json``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    mix = load_json(os.path.join(traffic_dir, cell["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload] if m["moves"] in names
                                  else [])]
    return SimpleNamespace(name=workload, cell=cell, cfg=cfg, mix=mix,
                           e2e=e2e, layer=layer)


def reader(name: str):
    """``read(run)`` of ``metrics/<name>.py``, or of its base name's."""
    for stem in dict.fromkeys((name, name.split(".")[0])):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "chip_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r}")


def accelerators(chips: int):
    """The first ``chips`` TPU devices; raises when there are not so many."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU found (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"{len(devs)} TPU devices, the cell needs {chips}")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR``, or a
    fixed directory in the checkout."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def build_engine(cfg: dict, seed: int):
    from repro.core.io_sim import DEVICES
    from repro.runtime.engine import DeviceServingEngine, EngineConfig
    tabs = tables.device_tables(seed, cfg["tables"]["rows"], cfg["dim"])
    engine = DeviceServingEngine(
        tabs, DEVICES[cfg["sm_device"]],
        EngineConfig(hbm_cache_bytes=cfg["hbm_cache_bytes"],
                     ways=cfg["cache_ways"]))
    del tabs
    geo = engine.cache.geo
    if (geo.num_sets, geo.ways) != (cfg["cache_sets"], cfg["cache_ways"]):
        raise ValueError(f"engine cache {geo.num_sets} sets x {geo.ways} "
                         f"ways, configuration {cfg['cache_sets']} x "
                         f"{cfg['cache_ways']}")
    return engine


def program_chunks(tr):
    """The traffic as the program's columnar chunks."""
    from repro.core.columnar import ColumnarQueries
    cq = ColumnarQueries(tr.values, tr.seg_offsets, tr.seg_table,
                         tr.query_seg)
    B = tr.chunk
    return [cq.chunk(s, s + B, B) for s in range(0, tr.n_queries, B)]


class GcPauses:
    """Collections of the garbage collector, and their seconds, while
    ``on`` is set."""

    def __init__(self):
        self.on, self.count, self.seconds, self.longest = False, 0, 0.0, 0.0
        self._t = None
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on and self._t is not None:
            dt = time.perf_counter() - self._t
            self.count += 1
            self.seconds += dt
            self.longest = max(self.longest, dt)

    def close(self):
        gc.callbacks.remove(self._event)


class CompileCounter:
    """Counts XLA compilations (or loads from the persistent cache) while
    ``on`` is set."""

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.count += 1


def warm_up(engine, chunks, tr):
    """Serve the warm-up chunks, then one window chunk of each padded
    pooling the warm-up lacked. Returns the served chunk indices and the
    program's reads of each."""
    B = tr.chunk
    first = tr.warmup // B
    order = list(range(first))
    have = {traffic_mod.padded_pooling(tr, k * B, k * B + B) for k in order}
    for k in range(first, len(chunks)):
        p = traffic_mod.padded_pooling(tr, k * B, k * B + B)
        if p not in have:
            have.add(p)
            order.append(k)
    reads = [engine.serve_columnar(chunks[k])[2] for k in order]
    return order, reads


def serve_window(engine, chunks, tr, seconds, rng):
    """The timed window. Returns per window chunk its start and end (s after
    the window opened, NaN where unserved), the program's reads, and a
    uniform sample of served chunks with their pooled bags."""
    import jax
    ann = jax.profiler.TraceAnnotation
    first = tr.warmup // tr.chunk
    win = chunks[first:]
    n = len(win)
    ready = (np.zeros(n) if tr.backlog else traffic_mod.chunk_ready_s(tr))
    start, done = np.full(n, np.nan), np.full(n, np.nan)
    reads, sample = {}, []
    t0 = time.perf_counter()
    with ann(tracefile.WINDOW_SPAN):
        for k in range(n):
            now = time.perf_counter() - t0
            if now >= seconds + (0.0 if tr.backlog else DRAIN_LIMIT_S):
                break
            if ready[k] > now:
                with ann("bench.wait"):
                    time.sleep(ready[k] - now)
            start[k] = time.perf_counter() - t0
            with ann(tracefile.CHUNK_SPAN):
                pooled, _, io = engine.serve_columnar(win[k])
            done[k] = time.perf_counter() - t0
            reads[k] = io
            # reservoir sample of the served chunks' pooled bags
            if len(sample) < POOLED_SAMPLE_CHUNKS:
                sample.append((k, pooled))
            else:
                j = int(rng.integers(len(reads)))
                if j < POOLED_SAMPLE_CHUNKS:
                    sample[j] = (k, pooled)
    if tr.backlog and np.isfinite(done[-1]) and done[-1] < seconds:
        raise RanOut(f"served all {n * tr.chunk} pre-made queries "
                     f"{seconds - done[-1]:.3f} s before the window closed")
    return start, done, reads, sample


def check(cfg, tr, seed, served, prog_reads, sample):
    """Compare what was served with the reference. Returns the checks and
    the per-serving counts of useful work."""
    want, counts = reference.expected_reads(cfg, tr, served)
    mismatch = sum(int(np.sum(np.asarray(p) != w))
                   for p, w in zip(prog_reads, want))
    B, T, D = tr.chunk, tr.lens.shape[1], cfg["dim"]
    first = tr.warmup // B
    offsets = tables.row_offsets(cfg["tables"]["rows"])
    gap = 0.0
    for k, pooled in sample:
        q0 = (first + k) * B
        _, t, r, starts = reference.chunk_lookups(tr, q0, q0 + B)
        ref = reference.pool(seed, offsets, t, r, starts, B * T, D)
        gap = max(gap, float(np.abs(pooled.reshape(B * T, D) - ref).max()))
    return ({"pooled_gap": (gap, reference.POOLED_GAP_LIMIT),
             "sm_ios_mismatch": (mismatch, reference.SM_IOS_MISMATCH_LIMIT)},
            counts)


def measure(c, seed: int, seconds: float, trace: bool,
            t_start: float = T_START) -> SimpleNamespace:
    """Set up, warm up and serve one window of resolved cell ``c``; the
    engine is freed before this returns. ``setup_s`` runs from
    ``t_start``."""
    import jax
    phases = [("start", time.perf_counter())]
    dev = accelerators(c.cell["chips"])[0]
    m = SimpleNamespace(dev=dev, peak=peaks_mod.peaks(dev.device_kind),
                        cache_dir=use_compile_cache(), seed=seed,
                        seconds=seconds, log_dir=None)
    compiles = CompileCounter()
    phases.append(("devices", time.perf_counter()))
    m.tr = traffic_mod.generate(c.cfg, c.mix, seed, seconds)
    phases.append(("traffic", time.perf_counter()))
    engine = build_engine(c.cfg, seed)
    phases.append(("tables and engine", time.perf_counter()))
    chunks = program_chunks(m.tr)
    m.warm_order, m.warm_reads = warm_up(engine, chunks, m.tr)
    phases.append(("warm-up", time.perf_counter()))
    m.phases = [(name, t1 - t0) for (_, t0), (name, t1)
                in zip(phases, phases[1:])]
    rng = np.random.default_rng(traffic_mod.seed_words(seed) + [3])
    if trace:
        m.log_dir = tempfile.mkdtemp(prefix="chip_trace_")
        jax.profiler.start_trace(m.log_dir)
    hits0, misses0 = int(engine.state["hits"]), int(engine.state["misses"])
    # objects made in set-up (traffic, chunks, engine) are moved out of the
    # collector's reach, so the window's collections walk only its own
    gc.collect()
    gc.freeze()
    m.setup_s = time.perf_counter() - t_start
    pauses = GcPauses()
    compiles.on = pauses.on = True
    m.start, m.done, m.win_reads, m.sample = serve_window(
        engine, chunks, m.tr, seconds, rng)
    compiles.on = pauses.on = False
    pauses.close()
    gc.unfreeze()
    m.compiles, m.gc = compiles.count, pauses
    m.hits = int(engine.state["hits"]) - hits0
    m.misses = int(engine.state["misses"]) - misses0
    if trace:
        jax.profiler.stop_trace()
    m.mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    del engine, chunks
    gc.collect()
    B = m.tr.chunk
    first = m.tr.warmup // B
    m.served_k = sorted(m.win_reads)
    m.served = [(k * B, k * B + B) for k in m.warm_order] + [
        ((first + k) * B, (first + k + 1) * B) for k in m.served_k]
    m.prog_reads = m.warm_reads + [m.win_reads[k] for k in m.served_k]
    return m


def report(c, m) -> dict:
    """Check a measured run against the reference and build the result
    line's object; prints what was compared on stderr."""
    cfg, tr, B = c.cfg, m.tr, m.tr.chunk
    t_ref = time.perf_counter()
    checks, counts = check(cfg, tr, m.seed, m.served, m.prog_reads, m.sample)
    t_ref = time.perf_counter() - t_ref
    start, done, seconds = m.start, m.done, m.seconds
    n_win = len(done)
    unanswered = 0 if tr.backlog else int(np.sum(~np.isfinite(done)))
    checks["unanswered"] = (unanswered * B, 0)
    checks["window_compiles"] = (m.compiles, 0)
    correct = all(v <= lim for v, lim in checks.values())

    served = np.isfinite(done)
    # the window closes at the last completion, on or after ``seconds``
    values = {"setup_s": m.setup_s,
              "queries_per_s": B * int(served.sum())
              / max(seconds, float(done[served].max()))}
    info = [f"device {m.dev.device_kind}, compile cache {m.cache_dir}",
            f"traffic {tr.n_queries} queries made, warm-up "
            f"{len(m.warm_order)} chunks, window {len(m.served_k)} of "
            f"{n_win} chunks served",
            f"set-up {m.setup_s:.3f} s (" + ", ".join(
                f"{k} {v:.3f}" for k, v in m.phases) + "), window compiles "
            f"{m.compiles}, reference {t_ref:.3f} s",
            f"window gc: {m.gc.count} collections, {m.gc.seconds:.4f} s, "
            f"longest {m.gc.longest:.4f} s"]
    if not tr.backlog:
        ok = np.isfinite(done)
        lat_ms = 1e3 * (np.repeat(done[ok], B) - tr.due_s[np.repeat(ok, B)])
        values["query_p50_ms"] = float(np.percentile(lat_ms, 50))
        values["query_p95_ms"] = float(np.percentile(lat_ms, 95))
        info.append(f"latency ms: p50 {values['query_p50_ms']:.3f} p95 "
                    f"{values['query_p95_ms']:.3f} p99 "
                    f"{np.percentile(lat_ms, 99):.3f} max {lat_ms.max():.3f} "
                    f"over {len(lat_ms)} queries")
    ready = np.zeros(n_win) if tr.backlog else traffic_mod.chunk_ready_s(tr)
    lag_ms = 1e3 * (start - ready)[served]
    quarter = max(1, len(lag_ms) // 4)
    lag = {"mean": float(lag_ms.mean()), "max": float(lag_ms.max()),
           "first_quarter": float(lag_ms[:quarter].mean()),
           "last_quarter": float(lag_ms[-quarter:].mean())}
    serve_ms = 1e3 * (done - start)[served]
    info.append("dispatch lag ms (start - ready): " + " ".join(
        f"{k} {v:.3f}" for k, v in lag.items()))
    info.append(f"serve_columnar ms: median {np.median(serve_ms):.3f} mean "
                f"{serve_ms.mean():.3f} longest "
                + " ".join(f"{v:.1f}" for v in np.sort(serve_ms)[-5:][::-1]))

    run = SimpleNamespace(
        cfg=cfg, peak=m.peak, counts=counts[len(m.warm_order):], trace=None,
        hits=m.hits, misses=m.misses,
        window_reads=sum(int(np.sum(m.win_reads[k])) for k in m.served_k),
        window_queries=B * len(m.served_k))
    device = {"platform": m.dev.platform, "kind": m.dev.device_kind,
              "count": c.cell["chips"], "memory_peak_bytes": m.mem}
    out = {"correct": correct,
           "attempted": B * (len(m.served_k) if tr.backlog else n_win),
           "failed": unanswered * B}
    if m.log_dir:
        run.trace = tracefile.Summary(tracefile.extract(m.log_dir))
        shutil.rmtree(m.log_dir, ignore_errors=True)
        out["metrics"] = {}
        for spec in c.layer:
            v = reader(spec["name"])(run)
            if v is not None:
                out["metrics"][spec["name"]] = {"value": v,
                                                "unit": spec["unit"]}
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        out["device"] = device
        out["breakdown"] = {"device_ops": run.trace.top_ops(),
                            "idle_gaps": run.trace.idle_gaps()}
    else:
        out["metrics"] = {spec["name"]: {"value": values[spec["name"]],
                                         "unit": spec["unit"]}
                          for spec in c.e2e}
        out["device"] = device
    out["dispatch_lag_ms"] = lag
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for line in info:
        print(line, file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k} {v} limit {lim}", file=sys.stderr)
    return out


def run_cell(c, seed: int, seconds: float, trace: bool) -> dict:
    """One run of resolved cell ``c``; returns the result line's object."""
    return report(c, measure(c, seed, seconds, trace))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        c = resolve(load_json(os.path.join(ROOT, "BENCHMARK.json")),
                    args.workload)
        out = run_cell(c, args.seed, args.seconds, bool(args.trace))
    except (NoAccelerator, RanOut, KeyError, OSError, ValueError,
            ImportError) as e:
        print(f"run.py: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
