#!/usr/bin/env python3
"""The device engine's own instrumentation, read from a profiler trace.

``repro.runtime.engine`` marks its host path with ``engine.*`` spans
(``jax.profiler.TraceAnnotation``), the regions of its jitted step with
``jax.named_scope`` (the cache's parts scoped inside them), and, with a
telemetry handle attached, counts the positions of each dense index block
and the valid ones among them. The names are ``repro.obs.tracing``'s.
This module reads them beside :mod:`tracefile`'s reduction:

- :func:`extract` adds the engine's spans to ``spans`` and a map from each
  device op's name to its scope path (``scopes``);
- :class:`EngineSummary` adds self time per span, device time per scope,
  and idle time given to the innermost span that covers it;
- :data:`READERS` are the per-chunk readings, each ``read(run)`` on a
  namespace with ``trace`` (a summary) and ``telemetry`` (the engine's
  handle, or None), returning None where the trace or handle lacks what it
  reads. ``run.py`` builds that namespace in a traced run, and the
  benchmark's ``metrics/<name>.py`` hand it to them.

As a script it makes one traced run of a cell with the benchmark's own
functions and prints the result line with an ``engine`` entry added: self
time per span, device time per scope, the idle split and the longest
calls:

    python3 benchmarks/chip/enginetrace.py --workload m1.steady --seed 7 \\
        --seconds 20 [--record out.json]

``--record`` also writes the window's trace, counters and readings, as a
fixture for the tests.
"""
from __future__ import annotations

import bisect
import glob
import os
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import tracefile  # noqa: E402
from repro.obs import tracing as names  # noqa: E402

# the stat of a TPU op's event metadata that holds its HLO op_name, where
# named scopes show as path components
SCOPE_STAT = "tf_op"
KNOWN_SCOPES = frozenset(names.ENGINE_SCOPES + names.CACHE_SCOPES)
POSITIONS, VALID_POSITIONS = "engine.positions", "engine.valid_positions"


def scope_path(text: str) -> str:
    """The engine and cache scopes in an op_name, outermost first:
    ``jit(step)/engine.fill/cache.scatter/scatter`` ->
    ``engine.fill/cache.scatter``; ``""`` where there are none."""
    return "/".join(c for c in text.split("/") if c in KNOWN_SCOPES)


def _varint(buf, i: int):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of a serialized protobuf
    message: an int for a varint, the bytes for the other wire types."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def event_metadata(path: str, plane_prefix: str = "/device:TPU:"):
    """``{event name: {stat name: value}}`` of the event metadata of the
    planes under ``plane_prefix`` in an ``.xplane.pb`` file. The metadata's
    stats (an op's HLO ``op_name`` among them) are not among the stats
    ``jax.profiler.ProfileData`` gives its events, so this reads the
    ``XSpace`` message itself: planes (field 1); a plane's name (2), event
    metadata (4) and stat metadata (5), both maps of id to message (entry
    value 2); an event metadata's name (2) and stats (5); a stat's
    metadata id (1) and string (5) or interned-string reference (7)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in fields if k == 2), "")
        if not name.startswith(plane_prefix):
            continue
        stat_names = {}
        for k, entry in fields:
            if k == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        for k, entry in fields:
            if k != 4:
                continue
            ev_name, stats = "", {}
            for ek, ev in _fields(dict(_fields(entry))[2]):
                if ek == 2:
                    ev_name = bytes(ev).decode()
                elif ek == 5:
                    stat = dict(_fields(ev))
                    if 5 in stat:
                        value = bytes(stat[5]).decode()
                    elif 7 in stat:
                        value = stat_names.get(stat[7], "")
                    else:
                        continue
                    stats[stat_names.get(stat.get(1, 0), "")] = value
            out.setdefault(ev_name, stats)
    return out


def op_scopes(path: str):
    """``{op name: scope path}`` for the TPU ops of an ``.xplane.pb`` file
    whose :data:`SCOPE_STAT` carries an engine or cache scope."""
    out = {}
    for text, stats in event_metadata(path).items():
        scope = scope_path(stats.get(SCOPE_STAT, ""))
        if scope:
            out.setdefault(tracefile.op_name(text), scope)
    return out


def extract(log_dir: str) -> dict:
    """:func:`tracefile.extract` of the trace under ``log_dir``, with the
    engine's host spans added to ``spans`` and ``scopes``: ``{op name:
    scope path}`` for the device ops that carry one. The step's programs
    at each padded pooling are one function, so a name has one scope."""
    import jax

    out = tracefile.extract(log_dir)
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"].extend([e.name, e.start_ns, e.duration_ns]
                                    for e in line.events
                                    if e.name in names.ENGINE_SPANS)
    ran = {name for _, name, _, _ in out["ops"]}
    out["scopes"] = {k: v for k, v in op_scopes(path).items() if k in ran}
    return out


def innermost(spans):
    """Nested ``(start, end, name)`` spans of one thread as disjoint
    ``(start, end, name)`` pieces, each named for the innermost span that
    covers it. Spans that do not nest come back as they are."""
    out, stack, cursor = [], [], 0

    def emit(upto):
        nonlocal cursor
        if upto > cursor:
            out.append((cursor, upto, stack[-1][2]))
        cursor = upto

    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(s)
        cursor = s
        stack.append((s, e, n))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


class EngineSummary(tracefile.Summary):
    """A :class:`tracefile.Summary` that also reads the engine's spans and
    scopes. Span times are host seconds; scope times device seconds,
    averaged over the devices."""

    def __init__(self, ex: dict):
        super().__init__(ex)
        self.scopes = ex.get("scopes", {})
        self.serves = [(s, e) for s, e, n in self.spans
                       if n == names.SPAN_SERVE]
        self.pieces = innermost(self.spans)
        self.self_ns = defaultdict(float)
        for s, e, n in self.pieces:
            self.self_ns[n] += e - s

    def self_s(self, name: str) -> float:
        """Seconds in spans named ``name`` not covered by a child span."""
        return self.self_ns.get(name, 0.0) * 1e-9

    def scope_table(self):
        """Device seconds per scope path (``unscoped`` for ops that carry
        none), averaged over the devices."""
        acc = defaultdict(float)
        for _, name, s, e in self.ops:
            acc[self.scopes.get(name, "unscoped")] += e - s
        nd = max(1, len(self.devices))
        return {k: v / nd * 1e-9 for k, v in acc.items()}

    def scope_s(self, scope: str):
        """Device seconds of the ops inside ``scope``, or None where no op
        carries it."""
        table = {k: v for k, v in self.scope_table().items()
                 if scope in k.split("/")}
        return sum(table.values()) if table else None

    def idle_gaps(self, n: int = 10):
        """Idle time of the first device, each idle nanosecond given to the
        innermost span the host was in (``host`` where it was in none). On
        spans that do not nest this is :meth:`tracefile.Summary.idle_gaps`."""
        if not self.devices:
            return []
        starts = [s for s, _, _ in self.pieces]
        acc = defaultdict(float)
        for g0, g1 in self.busy[self.devices[0]].gaps(self.lo, self.hi):
            i = max(0, bisect.bisect_right(starts, g0) - 1)
            covered = 0.0
            while i < len(self.pieces) and self.pieces[i][0] < g1:
                s, e, name = self.pieces[i]
                part = min(e, g1) - max(s, g0)
                if part > 0:
                    acc[name] += part * 1e-9
                    covered += part
                i += 1
            if g1 - g0 > covered:
                acc["host"] += (g1 - g0 - covered) * 1e-9
        return sorted(([k, v] for k, v in acc.items()),
                      key=lambda kv: -kv[1])[:n]

    def longest_serves(self, n: int = 5):
        """The ``n`` longest ``engine.serve`` spans: each one's ms, the self
        ms of every span inside it, and the device-busy ms inside it."""
        busy = self.busy[self.devices[0]] if self.devices else None
        out = []
        for s, e in sorted(self.serves, key=lambda se: se[0] - se[1])[:n]:
            parts = defaultdict(float)
            for ps, pe, pn in self.pieces:
                if s <= ps and pe <= e:
                    parts[pn] += (pe - ps) * 1e-6
            out.append({"ms": (e - s) * 1e-6, "self_ms": dict(parts),
                        "busy_ms": busy.within(s, e) * 1e-6 if busy else 0.0})
        return out


def _chunks(run):
    t = getattr(run, "trace", None)
    return len(getattr(t, "serves", ()))


def _span_ms_per_chunk(span):
    def read(run):
        n = _chunks(run)
        return 1e3 * run.trace.self_s(span) / n if n else None
    read.__doc__ = (f"Self time of ``{span}`` per served chunk, in ms "
                    "(the spans inside it left out).")
    return read


def _scope_ms_per_chunk(scope):
    def read(run):
        n = _chunks(run)
        t = run.trace.scope_s(scope) if n else None
        return 1e3 * t / n if t is not None else None
    read.__doc__ = (f"Device time of the ops inside ``{scope}`` per served "
                    "chunk, in ms.")
    return read


def pad_share(run):
    """Padded share of the dense index blocks' positions, in percent: 100 x
    (1 - ``engine.valid_positions`` / ``engine.positions``)."""
    tel = getattr(run, "telemetry", None)
    if tel is None:
        return None
    c = tel.registry.counters
    pos = c.get(POSITIONS, 0)
    return 100.0 * (1.0 - c.get(VALID_POSITIONS, 0) / pos) if pos else None


READERS = {
    "pack_ms_per_chunk": _span_ms_per_chunk(names.SPAN_PACK),
    "account_ms_per_chunk": _span_ms_per_chunk(names.SPAN_ACCOUNT),
    "probe_ms_per_chunk": _scope_ms_per_chunk(names.SCOPE_PROBE),
    "fill_ms_per_chunk": _scope_ms_per_chunk(names.SCOPE_FILL),
    "pad_share": pad_share,
}


def engine_report(run, backlog: bool) -> dict:
    """The engine's readings of one traced window: the :data:`READERS`
    (named with ``.sat`` in a backlog cell), self ms and device ms per
    chunk, the innermost idle split and the longest calls."""
    t, n = run.trace, max(1, _chunks(run))
    suffix = ".sat" if backlog else ""
    return {
        "metrics": {k + suffix: f(run) for k, f in READERS.items()},
        "chunks": _chunks(run),
        "self_ms_per_chunk": {k: 1e3 * v * 1e-9 / n
                              for k, v in sorted(t.self_ns.items())},
        "scope_ms_per_chunk": {k: 1e3 * v / n
                               for k, v in sorted(t.scope_table().items())},
        "idle_gaps": t.idle_gaps(20),
        "counters": (dict(run.telemetry.registry.counters)
                     if run.telemetry is not None else {}),
        "longest_serves": t.longest_serves(),
    }


def reference_pad_share(tr, served_k) -> float:
    """The padded share of the window's served chunks from the traffic:
    the reference's lookups over B x T x P of each chunk, in percent."""
    import reference
    import traffic as traffic_mod
    B, T = tr.chunk, tr.lens.shape[1]
    first = tr.warmup // B
    pos = valid = 0
    for k in served_k:
        q0 = (first + k) * B
        valid += len(reference.chunk_lookups(tr, q0, q0 + B)[2])
        pos += B * T * traffic_mod.padded_pooling(tr, q0, q0 + B)
    return 100.0 * (1.0 - valid / pos)


def trace_cell(c, seed: int, seconds: float):
    """One traced run of resolved cell ``c`` through the benchmark's
    ``measure`` and ``report``, which attach the telemetry handle when the
    window opens. Returns the result line's object, with the ``engine``
    entry added, and the window's :func:`extract`."""
    import run as bench

    m = bench.measure(c, seed, seconds, True)
    out = bench.report(c, m)
    out["engine"] = engine_report(m.run, m.tr.backlog)
    out["engine"]["reference_pad_share"] = reference_pad_share(m.tr,
                                                               m.served_k)
    return out, m.extract


def main(argv=None) -> int:
    import argparse
    import json

    import run as bench

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", help="write the window's trace, counters "
                    "and readings to this JSON file")
    args = ap.parse_args(argv)
    try:
        c = bench.resolve(bench.load_json(os.path.join(ROOT,
                                                       "BENCHMARK.json")),
                          args.workload)
        out, ex = trace_cell(c, args.seed, args.seconds)
    except (bench.NoAccelerator, bench.RanOut, KeyError, OSError,
            ValueError, ImportError) as e:
        print(f"enginetrace.py: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    if args.record:
        eng = out["engine"]
        with open(args.record, "w") as f:
            json.dump({"about": f"{out['device']['kind']} trace of "
                       f"{args.workload} (seed {args.seed}, --seconds "
                       f"{args.seconds}): the window's {eng['chunks']} "
                       "chunks with the engine's spans, op scopes and "
                       "counters; metrics as READERS computed them when "
                       "recorded",
                       "trace": ex, "counters": eng["counters"],
                       "reference_pad_share": eng["reference_pad_share"],
                       "metrics": eng["metrics"]}, f)
    sys.stdout.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
