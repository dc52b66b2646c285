"""Served path ``user_bags``: the user side of a DLRM query through the
device engine. Every user table's lookup bag of a query is probed in the
HBM row cache, misses are gathered from the 8-bit store, dequantized and
pooled; the pooled bags ``[B, T, D]`` come out on the host with each
query's deduped SCM reads.

A served-path module gives the harness (``run.py``) what belongs to one
kind of served query:

- ``build(cfg, seed)``: the engine, with its tables made from the seed;
- ``inputs(cfg, tr, seed)``: the program's input of each chunk of the
  traffic ``tr``;
- ``shape(tr, k)``: chunk ``k``'s compile-shape key, so warm-up serves one
  chunk of each shape the window holds;
- ``serve(engine, x)``: the one timed call, returning ``(output,
  sm_reads)`` with the output on the host;
- ``counters(engine)``: the program's running counters, a dict of
  numbers; the harness hands readers how far each moved over the window;
- ``attach(engine, telemetry)``: puts a telemetry handle
  (``repro.obs.make_telemetry``) on the engine, so the program's own
  counters reach the readers of a traced run;
- ``check(cfg, tr, seed, served, prog_reads, sample)``: ``(checks,
  counts)``, each check ``(number, limit)`` against the plain reference,
  and the per-serving counts of useful work;
- ``step_work(cfg, c)``: the useful ``(bytes, flops)`` of one step with
  counts ``c``.
"""
import numpy as np

import reference
import tables
import traffic as traffic_mod
import work


def build(cfg: dict, seed: int):
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


def inputs(cfg: dict, tr, seed: int):
    """The traffic as the program's columnar chunks."""
    from repro.core.columnar import ColumnarQueries
    cq = ColumnarQueries(tr.values, tr.seg_offsets, tr.seg_table,
                         tr.query_seg)
    B = tr.chunk
    return [cq.chunk(s, s + B, B) for s in range(0, tr.n_queries, B)]


def shape(tr, k: int) -> int:
    """The padded pooling ``P`` of chunk ``k``'s ``[B, T, P]`` block."""
    B = tr.chunk
    return traffic_mod.padded_pooling(tr, k * B, k * B + B)


def serve(engine, x):
    pooled, _, io = engine.serve_columnar(x)
    return pooled, io


def counters(engine) -> dict:
    """The row cache's hits and misses so far."""
    return {"hits": int(engine.state["hits"]),
            "misses": int(engine.state["misses"])}


def attach(engine, telemetry) -> None:
    engine.telemetry = telemetry


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


step_work = work.step
