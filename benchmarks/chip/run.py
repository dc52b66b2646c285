#!/usr/bin/env python3
"""Chip benchmark of the device serving path: one run of one cell.

    python3 benchmarks/chip/run.py --workload m1.steady --seed 7 \\
        --seconds 20 --trace 0

The cell (``BENCHMARK.json``) names a configuration file and a traffic mix
file; the configuration names its served path (``"served"``, by default
``user_bags``), a module ``served/<name>.py`` that builds the engine, turns
traffic into the program's inputs, makes the one timed call and checks its
output against the plain reference. Nothing in this file belongs to one
cell or one kind of query. One process:

1. makes the run's traffic from the seed (``traffic.py``), and the engine
   and the program's input of each chunk through the served path;
2. warms up by serving the leading chunks of the same traffic untimed, plus
   one chunk of each compile shape the window holds that the warm-up did
   not (set-up ends here: ``setup_s``);
3. serves the window open loop, one chunk per timed call. A chunk is
   dispatched once its last query has arrived, or at once when the engine
   has fallen behind; a query's latency runs from its arrival to the
   moment its chunk's output is on the host. A fixed-rate window serves
   every query due in it, however long after the close; a backlog window
   has every query due at its opening and counts what completed by its
   close;
4. checks what the window served with the served path's ``check`` and
   prints the result as the last line of stdout.

With ``--trace 1`` the window runs under the profiler with a telemetry
handle on the engine, and the line carries the cell's per-layer metrics,
read by ``metrics/<name>.py`` (a name with a ``.variant`` suffix falls back
to the reader of its base name) from the trace, the engine's spans and
counters, and the run's counts.

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
SERVED_DIR = os.path.join(HERE, "served")
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import enginetrace  # noqa: E402
import peaks as peaks_mod  # noqa: E402
import tracefile  # noqa: E402
import traffic as traffic_mod  # noqa: E402

DEFAULT_SERVED = "user_bags"  # served path of a configuration that names none
OUTPUT_SAMPLE_CHUNKS = 32     # window chunks whose output is checked
DRAIN_LIMIT_S = 60.0          # a window query unanswered this long after
                              # the close counts as failed
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoAccelerator(RuntimeError):
    pass


class RanOut(RuntimeError):
    pass


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """The Python file ``path`` as a module named ``name``."""
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def served_module(name: str, served_dir: str = SERVED_DIR):
    """The served-path module ``<served_dir>/<name>.py``."""
    return load_module(os.path.join(served_dir, name + ".py"),
                       "chip_served_" + name)


def resolve(bench: dict, workload: str,
            traffic_dir: str = os.path.join(HERE, "traffic"),
            served_dir: str = SERVED_DIR):
    """The cell ``workload`` of ``bench`` with its configuration, mix,
    served path and metric entries. Mixes are
    ``<traffic_dir>/<traffic>.json``, served paths
    ``<served_dir>/<served>.py``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    mix = load_json(os.path.join(traffic_dir, cell["traffic"] + ".json"))
    served = served_module(cfg.get("served", DEFAULT_SERVED), served_dir)
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload] if m["moves"] in names
                                  else [])]
    return SimpleNamespace(name=workload, cell=cell, cfg=cfg, mix=mix,
                           served=served, e2e=e2e, layer=layer)


def reader(name: str):
    """``read(run)`` of ``metrics/<name>.py``, or of its base name's."""
    for stem in dict.fromkeys((name, name.split(".")[0])):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            return load_module(
                path, "chip_metric_" + stem.replace(".", "_")).read
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
    """Counts XLA compilations (or loads from the persistent cache), and
    the loads among them, while ``on`` is set."""

    def __init__(self):
        import jax
        self.on, self.count, self.cached = False, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        jax.monitoring.register_event_listener(self._hit)

    def _event(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.count += 1

    def _hit(self, event, **kw):
        if self.on and event == CACHE_HIT_EVENT:
            self.cached += 1


def warm_up(engine, xs, tr, served):
    """Serve the warm-up chunks, then one window chunk of each compile
    shape (``served.shape``) the warm-up lacked. Returns the served chunk
    indices and the program's reads of each."""
    first = tr.warmup // tr.chunk
    order = list(range(first))
    have = {served.shape(tr, k) for k in order}
    for k in range(first, len(xs)):
        key = served.shape(tr, k)
        if key not in have:
            have.add(key)
            order.append(k)
    reads = [served.serve(engine, xs[k])[1] for k in order]
    return order, reads


def serve_window(engine, xs, tr, seconds, rng, serve):
    """The timed window, one ``serve(engine, x)`` call per chunk. Returns
    per window chunk its start and end (s after the window opened, NaN
    where unserved), the program's reads, and a uniform sample of served
    chunks with their output."""
    import jax
    ann = jax.profiler.TraceAnnotation
    first = tr.warmup // tr.chunk
    win = xs[first:]
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
                output, io = serve(engine, win[k])
            done[k] = time.perf_counter() - t0
            reads[k] = io
            # reservoir sample of the served chunks' output
            if len(sample) < OUTPUT_SAMPLE_CHUNKS:
                sample.append((k, output))
            else:
                j = int(rng.integers(len(reads)))
                if j < OUTPUT_SAMPLE_CHUNKS:
                    sample[j] = (k, output)
    if tr.backlog and np.isfinite(done[-1]) and done[-1] < seconds:
        raise RanOut(f"served all {n * tr.chunk} pre-made queries "
                     f"{seconds - done[-1]:.3f} s before the window closed")
    return start, done, reads, sample


def measure(c, seed: int, seconds: float, trace: bool,
            t_start: float = T_START) -> SimpleNamespace:
    """Set up, warm up and serve one window of resolved cell ``c``; the
    engine is freed before this returns. ``setup_s`` runs from
    ``t_start``. ``m.counters`` holds how far the served path's counters
    moved over the window. Traced, the served path attaches a telemetry
    handle (``m.telemetry``) to the engine as the window opens, so the
    handle's counters cover window chunks only."""
    import jax
    phases = [("start", time.perf_counter())]
    dev = accelerators(c.cell["chips"])[0]
    m = SimpleNamespace(dev=dev, peak=peaks_mod.peaks(dev.device_kind),
                        cache_dir=use_compile_cache(), seed=seed,
                        seconds=seconds, log_dir=None, telemetry=None)
    compiles = CompileCounter()
    phases.append(("devices", time.perf_counter()))
    m.tr = traffic_mod.generate(c.cfg, c.mix, seed, seconds)
    phases.append(("traffic", time.perf_counter()))
    compiles.on = True
    engine = c.served.build(c.cfg, seed)
    phases.append(("tables and engine", time.perf_counter()))
    xs = c.served.inputs(c.cfg, m.tr, seed)
    m.warm_order, m.warm_reads = warm_up(engine, xs, m.tr, c.served)
    compiles.on = False
    m.setup_programs = (compiles.count, compiles.cached)
    compiles.count = compiles.cached = 0
    phases.append(("warm-up", time.perf_counter()))
    m.phases = [(name, t1 - t0) for (_, t0), (name, t1)
                in zip(phases, phases[1:])]
    rng = np.random.default_rng(traffic_mod.seed_words(seed) + [3])
    if trace:
        from repro.obs import make_telemetry
        m.log_dir = tempfile.mkdtemp(prefix="chip_trace_")
        jax.profiler.start_trace(m.log_dir)
        m.telemetry = make_telemetry(True)
        c.served.attach(engine, m.telemetry)
    before = c.served.counters(engine)
    # objects made in set-up (traffic, inputs, engine) are moved out of the
    # collector's reach, so the window's collections walk only its own
    gc.collect()
    gc.freeze()
    m.setup_s = time.perf_counter() - t_start
    pauses = GcPauses()
    compiles.on = pauses.on = True
    m.start, m.done, m.win_reads, m.sample = serve_window(
        engine, xs, m.tr, seconds, rng, c.served.serve)
    compiles.on = pauses.on = False
    pauses.close()
    gc.unfreeze()
    m.compiles, m.gc = compiles.count, pauses
    m.counters = {k: v - before[k]
                  for k, v in c.served.counters(engine).items()}
    if trace:
        jax.profiler.stop_trace()
    m.mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    del engine, xs
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
    line's object; prints what was compared on stderr. The readers'
    namespace is left in ``m.run``, and a traced window's
    :func:`enginetrace.extract` in ``m.extract``."""
    cfg, tr, B = c.cfg, m.tr, m.tr.chunk
    t_ref = time.perf_counter()
    checks, counts = c.served.check(cfg, tr, m.seed, m.served, m.prog_reads,
                                    m.sample)
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
                f"{k} {v:.3f}" for k, v in m.phases) + "), set-up programs "
            f"{m.setup_programs[0]} ({m.setup_programs[1]} from the compile "
            f"cache), window compiles {m.compiles}, reference {t_ref:.3f} s",
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
    info.append(f"serve call ms: median {np.median(serve_ms):.3f} mean "
                f"{serve_ms.mean():.3f} longest "
                + " ".join(f"{v:.1f}" for v in np.sort(serve_ms)[-5:][::-1]))

    m.run = run = SimpleNamespace(
        cfg=cfg, peak=m.peak, counts=counts[len(m.warm_order):],
        step_work=c.served.step_work, trace=None, telemetry=m.telemetry,
        **m.counters,
        window_reads=sum(int(np.sum(m.win_reads[k])) for k in m.served_k),
        window_queries=B * len(m.served_k))
    device = {"platform": m.dev.platform, "kind": m.dev.device_kind,
              "count": c.cell["chips"], "memory_peak_bytes": m.mem}
    out = {"correct": correct,
           "attempted": B * (len(m.served_k) if tr.backlog else n_win),
           "failed": unanswered * B}
    if m.log_dir:
        m.extract = enginetrace.extract(m.log_dir)
        run.trace = enginetrace.EngineSummary(m.extract)
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
