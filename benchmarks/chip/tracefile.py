"""Reduction of a profiler trace to what the per-layer readers use.

:func:`extract` reads the ``.xplane.pb`` file that ``jax.profiler`` writes
into plain lists: device operations (``XLA Ops`` line of each TPU plane),
device programs (``XLA Modules``), and the benchmark's own host spans
(``bench.*`` annotations on the host plane). :class:`Summary` works on those
lists only, so a small recorded trace can test it anywhere.

Times are nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
CHUNK_SPAN = "bench.serve_chunk"


def op_name(text: str) -> str:
    """An ``XLA Ops`` event's HLO instruction name: ``%gather_pool.1 =
    f32[...] custom-call(...)`` -> ``gather_pool.1``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_label(text: str, width: int = 160) -> str:
    """The instruction with layouts and operand names dropped, for the
    breakdown: ``fusion.13 = f32[124928] fusion(f32[8148816],
    s32[124928])``."""
    text = re.sub(r"\{[^{}]*\}", "", text.lstrip("%"))
    text = re.sub(r"\s%[\w.-]+", "", text)
    return re.split(r", (?:kind|calls|custom_call_target)=", text)[0][:width]


def is_op(name: str, base: str) -> bool:
    """Whether instruction ``name`` is ``base`` or a numbered copy of it."""
    return re.fullmatch(re.escape(base) + r"(\.\d+)?", name) is not None


def extract(log_dir: str) -> dict:
    """The trace under ``log_dir`` as ``{"ops": [[device, name, start,
    dur], ...], "modules": [...same...], "spans": [[name, start, dur],
    ...], "labels": {op name: label}}``."""
    import jax

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {paths}")
    out = {"ops": [], "modules": [], "spans": [], "labels": {}}
    for plane in jax.profiler.ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out["modules"].extend([dev, e.name, e.start_ns,
                                           e.duration_ns]
                                          for e in line.events)
                elif line.name == "XLA Ops":
                    for e in line.events:
                        name = op_name(e.name)
                        if name not in out["labels"]:
                            out["labels"][name] = op_label(e.name)
                        out["ops"].append([dev, name, e.start_ns,
                                           e.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"].extend([e.name, e.start_ns, e.duration_ns]
                                    for e in line.events
                                    if e.name.startswith("bench."))
    return out


def merged(intervals):
    """``(start, end)`` intervals merged into sorted disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Busy:
    """Disjoint busy intervals, with the busy length of any range."""

    def __init__(self, intervals):
        self.iv = merged(intervals)
        self.starts = [s for s, _ in self.iv]

    def within(self, lo: float, hi: float) -> float:
        i = max(0, bisect.bisect_right(self.starts, lo) - 1)
        total = 0.0
        while i < len(self.iv) and self.iv[i][0] < hi:
            total += max(0.0, min(self.iv[i][1], hi) - max(self.iv[i][0], lo))
            i += 1
        return total

    def gaps(self, lo: float, hi: float):
        """Idle ``(start, end)`` ranges inside ``[lo, hi]``."""
        out, reach = [], lo
        for s, e in self.iv:
            if s > reach:
                out.append((reach, min(s, hi)))
            reach = max(reach, e)
            if reach >= hi:
                break
        if reach < hi:
            out.append((reach, hi))
        return [g for g in out if g[1] > g[0]]


class Summary:
    """One traced window: its span, the device's busy intervals in it, and
    the benchmark's chunk spans."""

    def __init__(self, ex: dict):
        wins = [(s, s + d) for n, s, d in ex["spans"] if n == WINDOW_SPAN]
        if len(wins) != 1:
            raise ValueError(f"trace holds {len(wins)} window spans, not 1")
        self.lo, self.hi = wins[0]
        inside = lambda s, d: s < self.hi and s + d > self.lo
        self.ops = [(dev, n, s, s + d) for dev, n, s, d in ex["ops"]
                    if inside(s, d)]
        self.modules = [(dev, n, s, s + d) for dev, n, s, d in ex["modules"]
                        if inside(s, d)]
        self.devices = sorted({o[0] for o in self.ops})
        self.chunks = sorted((s, s + d) for n, s, d in ex["spans"]
                             if n == CHUNK_SPAN and inside(s, d))
        self.spans = sorted((s, s + d, n) for n, s, d in ex["spans"]
                            if n != WINDOW_SPAN and inside(s, d))
        self.labels = ex.get("labels", {})
        self.busy = {dev: Busy((s, e) for d, _, s, e in self.ops if d == dev)
                     for dev in self.devices}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran on the device, averaged over
        the devices."""
        if not self.devices:
            return 0.0
        per = [b.within(self.lo, self.hi) for b in self.busy.values()]
        return sum(per) / len(per) * 1e-9

    def op_seconds(self, base: str) -> float:
        """Summed device time of the instructions named ``base`` (or a
        numbered copy of it), averaged over the devices."""
        t = sum(e - s for _, n, s, e in self.ops if is_op(n, base))
        return t / max(1, len(self.devices)) * 1e-9

    def module_seconds(self, fragment: str) -> float:
        """Summed device time of programs whose name holds ``fragment``,
        averaged over the devices."""
        t = sum(e - s for _, n, s, e in self.modules if fragment in n)
        return t / max(1, len(self.devices)) * 1e-9

    def host_only_s(self):
        """Per chunk span: its length minus the device-busy time inside it
        (first device), in seconds."""
        busy = self.busy[self.devices[0]]
        return [((e - s) - busy.within(s, e)) * 1e-9 for s, e in self.chunks]

    def top_ops(self, n: int = 10):
        """The ``n`` operation names that took most device time."""
        acc = defaultdict(float)
        for _, name, s, e in self.ops:
            acc[name] += (e - s) * 1e-9 / max(1, len(self.devices))
        return sorted(([self.labels.get(k, k), v] for k, v in acc.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10):
        """Idle time of the first device, split by the benchmark span the
        host was in (``host`` where it was in none)."""
        if not self.devices:
            return []
        starts = [s for s, _, _ in self.spans]
        acc = defaultdict(float)
        for g0, g1 in self.busy[self.devices[0]].gaps(self.lo, self.hi):
            i = max(0, bisect.bisect_right(starts, g0) - 1)
            covered = 0.0
            while i < len(self.spans) and self.spans[i][0] < g1:
                s, e, name = self.spans[i]
                part = min(e, g1) - max(s, g0)
                if part > 0:
                    acc[name] += part * 1e-9
                    covered += part
                i += 1
            if g1 - g0 > covered:
                acc["host"] += (g1 - g0 - covered) * 1e-9
        return sorted(([k, v] for k, v in acc.items()),
                      key=lambda kv: -kv[1])[:n]
