"""Columnar (CSR) trace plane: round-trip invariants and property-based
differential tests pinning ``serve_columnar`` / ``serve_trace`` (and the
retained legacy dict plane) to the sequential ``serve`` / ``serve_query``
oracles, across archetype traces and cache regimes.

Follows the ``test_workload_props`` pattern: every property runs under
hypothesis when installed *and* as an always-on seeded sweep.
"""
import dataclasses

import numpy as np
import pytest

from hyp_compat import given, settings, st
from test_workload_props import STORE_REGIMES, _random_spec

from repro.core import DEVICES, SDMConfig, SDMEmbeddingStore
from repro.core.columnar import ColumnarQueries
from repro.core.power import HW_SS
from repro.runtime.cluster import (ClusterConfig, ClusterSim, HostSpec,
                                   homogeneous_cluster)
from repro.runtime.serve_sched import ServeConfig, ServeScheduler
from repro.workloads import ARCHETYPES, build_trace


def _mkstore(trace, regime, seed=7):
    return SDMEmbeddingStore(
        trace.all_metas(), DEVICES["nand_flash"],
        SDMConfig(pooled_len_threshold=4, **STORE_REGIMES[regime]), seed=seed)


# -- CSR round-trip invariants ------------------------------------------------


def _check_columnar_roundtrip(seed: int) -> None:
    """dict -> columnar -> dict is the identity (keys, key order, arrays),
    and build_trace's native columnar arrays equal the from_requests form."""
    trace = build_trace(_random_spec(seed))
    cq = trace.queries
    reqs = cq.requests()
    cq2 = ColumnarQueries.from_requests(
        [{t: np.array(ix) for t, ix in r.items()} for r in reqs])
    np.testing.assert_array_equal(cq2.values, cq.values)
    np.testing.assert_array_equal(cq2.seg_offsets, cq.seg_offsets)
    np.testing.assert_array_equal(cq2.seg_table, cq.seg_table)
    np.testing.assert_array_equal(cq2.query_seg, cq.query_seg)
    for a, b in zip(reqs, cq2.requests()):
        assert list(a) == list(b)          # same tables, same dict order
        for t in a:
            np.testing.assert_array_equal(a[t], b[t])


@pytest.mark.parametrize("seed", range(4))
def test_columnar_roundtrip_seeded(seed):
    _check_columnar_roundtrip(seed)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 1 << 16))
def test_columnar_roundtrip_property(seed):
    _check_columnar_roundtrip(seed)


def test_columnar_subset_and_chunks_are_slices():
    """Route-split subsets and chunk views reproduce the dict semantics."""
    trace = build_trace(dataclasses.replace(
        ARCHETYPES["multi_tenant"], num_queries=40))
    mask = np.asarray(trace.tenant) == 1
    sub = trace.subset(mask)
    picked = [r for r, m in zip(trace.requests, mask) if m]
    assert len(sub) == int(mask.sum())
    for a, b in zip(picked, sub.requests):
        assert list(a) == list(b)
        for t in a:
            np.testing.assert_array_equal(a[t], b[t])
    # chunks partition the trace; each chunk's columnar view matches its
    # dict view
    seen = 0
    for ch in trace.chunks(7):
        assert ch.start == seen
        reqs = ch.requests
        assert ch.columnar.n_queries == len(reqs) == len(ch.arrival_us)
        for q, req in enumerate(reqs):
            np.testing.assert_array_equal(
                trace.requests[ch.start + q][list(req)[0]],
                req[list(req)[0]])
        seen += len(reqs)
    assert seen == len(trace)


@pytest.mark.parametrize("qs,qe,csize", [(0, 40, 40), (14, 21, 7),
                                           (5, 33, None), (9, 9, None)],
                         ids=["whole", "stride", "adhoc", "empty"])
def test_chunk_segments_regroup_table_views(qs, qe, csize):
    """A chunk's query-major segments hold exactly what its per-table
    views hold, in the parent's segment order within each query."""
    trace = build_trace(dataclasses.replace(
        ARCHETYPES["multi_tenant"], num_queries=40))
    cq = trace.queries
    ch = cq.chunk(qs, qe, csize)
    seg = ch.segments()
    assert len(seg.qid) == len(seg.tid) == len(seg.lens)
    assert int(seg.lens.sum()) == len(seg.vals)
    assert np.all(np.diff(seg.qid) >= 0)
    s0 = cq.query_seg[qs]
    np.testing.assert_array_equal(seg.tid, cq.seg_table[s0:cq.query_seg[qe]])
    eoff = np.concatenate([[0], np.cumsum(seg.lens)])
    by_seg = {(int(q), int(t)): seg.vals[eoff[i]:eoff[i + 1]]
              for i, (q, t) in enumerate(zip(seg.qid, seg.tid))}
    by_view = {}
    for v in ch.table_views():
        for j, q in enumerate(v.qid.tolist()):
            by_view[(q, v.tid)] = v.vals[v.eoff[j]:v.eoff[j + 1]]
    assert sorted(by_seg) == sorted(by_view)
    for k, vals in by_view.items():
        np.testing.assert_array_equal(by_seg[k], vals)


# -- serve_trace / serve_columnar differential --------------------------------


def _check_columnar_differential(seed: int, regime: str) -> None:
    """serve_trace == sequential serve == legacy dict plane, down to
    QueryResult streams, the latency list, the in-flight ledger, stats and
    cache state — including a second replay on the same store/scheduler so
    the cached plan factorizations and resident-chunk plans are exercised."""
    spec = _random_spec(seed)
    trace = build_trace(spec)
    s_seq = _mkstore(trace, regime)
    s_col = _mkstore(trace, regime)
    s_leg = _mkstore(trace, regime)
    cfg = ServeConfig(item_compute_us=150.0)
    sch_seq = ServeScheduler(s_seq, dataclasses.replace(cfg))
    sch_col = ServeScheduler(s_col, dataclasses.replace(cfg))
    sch_leg = ServeScheduler(s_leg, dataclasses.replace(cfg))
    chunk = int(np.random.default_rng(seed + 1).integers(3, 17))
    for _replay in range(2):
        r_seq = [sch_seq.serve(q, bg_iops=3_000, at_us=at)
                 for q, at in zip(trace.requests, trace.arrival_us)]
        r_col = sch_col.serve_trace(trace, chunk, bg_iops=3_000, collect=True)
        r_leg = []
        for ch in trace.chunks(chunk):
            r_leg += sch_leg.serve_batch_dict(ch.requests, bg_iops=3_000,
                                              arrivals_us=ch.arrival_us)
        assert r_seq == r_col == r_leg
    assert sch_seq.p_lat == sch_col.p_lat == sch_leg.p_lat
    assert sch_seq.inflight == sch_col.inflight == sch_leg.inflight
    assert sch_seq.deferred == sch_col.deferred == sch_leg.deferred
    for other in (s_col, s_leg):
        assert dataclasses.asdict(s_seq.stats) == \
            dataclasses.asdict(other.stats)
        assert (s_seq.row_cache.hits, s_seq.row_cache.misses) == \
            (other.row_cache.hits, other.row_cache.misses)
        if s_seq.pooled_cache is not None:
            pa, pb = s_seq.pooled_cache, other.pooled_cache
            assert (pa.hits, pa.misses, pa.skipped, pa.used) == \
                (pb.hits, pb.misses, pb.skipped, pb.used)
            assert list(pa.store) == list(pb.store)  # same keys, same LRU


@pytest.mark.parametrize("regime", sorted(STORE_REGIMES))
@pytest.mark.parametrize("seed", [0, 1])
def test_columnar_differential_seeded(seed, regime):
    _check_columnar_differential(seed, regime)


@pytest.mark.slow
@pytest.mark.parametrize("regime", sorted(STORE_REGIMES))
@pytest.mark.parametrize("seed", range(2, 7))
def test_columnar_differential_seeded_deep(seed, regime):
    _check_columnar_differential(seed, regime)


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1 << 16), st.sampled_from(sorted(STORE_REGIMES)))
def test_columnar_differential_property(seed, regime):
    _check_columnar_differential(seed, regime)


def test_vectorized_ledger_saturation_falls_back_exactly():
    """When admission control would defer queries, the per-chunk vectorized
    ledger must replay through the exact per-query path."""
    trace = build_trace(dataclasses.replace(
        ARCHETYPES["bursty"], num_queries=60))
    mk = lambda: SDMEmbeddingStore(  # noqa: E731
        trace.all_metas(), DEVICES["nand_flash"],
        SDMConfig(fm_cache_bytes=32 << 20), seed=7)
    cfg = ServeConfig(item_compute_us=150.0, max_inflight_ios=48)
    a = ServeScheduler(mk(), dataclasses.replace(cfg))
    b = ServeScheduler(mk(), dataclasses.replace(cfg))
    r1 = [a.serve(q, at_us=at)
          for q, at in zip(trace.requests, trace.arrival_us)]
    r2 = b.serve_trace(trace, chunk=16, collect=True)
    assert r1 == r2
    assert a.deferred == b.deferred > 0
    assert a.p_lat == b.p_lat and a.inflight == b.inflight


# -- cluster simulator: columnar vs dict replay --------------------------------


@pytest.mark.parametrize("mk", [
    lambda: homogeneous_cluster(
        HostSpec("ss", HW_SS, device="nand_flash")),
    lambda: ClusterSim(ClusterConfig(
        (HostSpec("h", HW_SS, count=3, pooled_cache_bytes=1 << 20),),
        routing="per_tenant")),
], ids=["single_host", "per_tenant_pooled"])
def test_cluster_columnar_matches_dict(mk):
    trace = build_trace(dataclasses.replace(
        ARCHETYPES["multi_tenant"], num_queries=96))
    rd = mk().run(trace, passes=2, warmup=True, columnar=False)
    rc = mk().run(trace, passes=2, warmup=True, columnar=True)
    assert (rd.p50_us, rd.p95_us, rd.p99_us) == (rc.p50_us, rc.p95_us,
                                                 rc.p99_us)
    for h_d, h_c in zip(rd.hosts, rc.hosts):
        assert dataclasses.asdict(h_d) == dataclasses.asdict(h_c)


def test_host_report_surfaces_and_resets_batch_fallbacks():
    """Warmup fallback counts must not leak into steady-state reports, and
    HostReport must expose the measured-pass fallback count."""
    trace = build_trace(dataclasses.replace(
        ARCHETYPES["zipf_steady"], num_queries=96))
    spec = HostSpec("ss", HW_SS, device="nand_flash", fm_cache_bytes=1 << 18)
    cold = homogeneous_cluster(spec).run(trace).hosts[0]
    assert cold.batch_fallbacks > 0       # tiny cache: eviction fallbacks
    from repro.runtime.cluster import HostSim
    sim = HostSim(spec, trace.all_metas(), 10_000.0)
    sim.run_trace(trace, 32, 0.0)
    assert sim.store.batch_fallbacks > 0
    sim.reset_measurement()
    assert sim.store.batch_fallbacks == 0
