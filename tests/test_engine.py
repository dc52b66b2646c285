"""Device serving engine: Pallas-kernel data plane vs numpy oracle, HBM cache
behaviour, and IO accounting."""
import numpy as np
import pytest

from repro.core.io_sim import DEVICES
from repro.core.locality import TableMeta
from repro.core.sdm import SDMConfig, SDMEmbeddingStore
from repro.runtime.engine import (DeviceServingEngine, EngineConfig,
                                  dense_from_chunk)


@pytest.fixture(scope="module")
def engine_and_idx():
    rng = np.random.default_rng(0)
    tables = {i: rng.standard_normal((256, 24)).astype(np.float32)
              for i in range(4)}
    eng = DeviceServingEngine(tables, DEVICES["nand_flash"],
                              EngineConfig(hbm_cache_bytes=1 << 18))
    idx = rng.integers(0, 256, (6, 4, 8)).astype(np.int32)
    return eng, idx


def test_pooled_output_matches_numpy_reference(engine_and_idx):
    eng, idx = engine_and_idx
    pooled, _ = eng.serve_batch(idx)
    np.testing.assert_allclose(pooled, eng.reference_pool(idx), atol=1e-5)


def test_cache_warms_and_ios_drop(engine_and_idx):
    eng, idx = engine_and_idx
    _, cold = eng.serve_batch(idx)        # may already be warm from the
    pooled, warm = eng.serve_batch(idx)   # previous test; warm is warmer
    assert sum(s.sm_ios for s in warm) < sum(s.sm_ios for s in cold) or \
        sum(s.sm_ios for s in warm) == 0
    assert eng.hit_rate > 0.3
    # numerics unchanged once rows are served from the HBM cache
    np.testing.assert_allclose(pooled, eng.reference_pool(idx), atol=1e-5)


def test_latency_accounting(engine_and_idx):
    eng, idx = engine_and_idx
    _, stats = eng.serve_batch(idx, bg_iops=10_000)
    for s in stats:
        assert s.latency_us >= eng.cfg.item_time_us     # Eq. 3 overlap
        assert s.sm_time_us >= 0.0
    total = sum(s.sm_ios for s in stats)
    assert eng.io.total_ios >= total


def test_kernel_and_reference_paths_agree():
    rng = np.random.default_rng(1)
    tables = {0: rng.standard_normal((128, 16)).astype(np.float32),
              1: rng.standard_normal((64, 16)).astype(np.float32)}
    idx = np.stack([rng.integers(0, 128, (5, 8)),
                    rng.integers(0, 64, (5, 8))], axis=1).astype(np.int32)
    outs = []
    for use_kernels in (True, False):
        eng = DeviceServingEngine(
            tables, DEVICES["optane_ssd"],
            EngineConfig(hbm_cache_bytes=1 << 16, use_kernels=use_kernels))
        pooled, stats = eng.serve_batch(idx)
        outs.append((pooled, [s.sm_ios for s in stats]))
    np.testing.assert_allclose(outs[0][0], outs[1][0], atol=1e-5)
    assert outs[0][1] == outs[1][1]       # identical miss accounting


def test_rejects_mismatched_dims_and_bad_indices():
    rng = np.random.default_rng(2)
    with pytest.raises(ValueError):
        DeviceServingEngine({0: rng.standard_normal((8, 4)),
                             1: rng.standard_normal((8, 6))},
                            DEVICES["nand_flash"])
    eng = DeviceServingEngine({0: rng.standard_normal((8, 4)).astype(np.float32)},
                              DEVICES["nand_flash"])
    with pytest.raises(ValueError):
        eng.serve_batch(np.full((1, 1, 2), 9, np.int32))    # row 9 of 8


def test_default_config_not_shared_between_engines():
    """Regression: a mutable default EngineConfig instance must not be
    shared by engines constructed without an explicit config."""
    rng = np.random.default_rng(3)
    tables = {0: rng.standard_normal((16, 4)).astype(np.float32)}
    a = DeviceServingEngine(tables, DEVICES["nand_flash"])
    b = DeviceServingEngine(tables, DEVICES["nand_flash"])
    assert a.cfg is not b.cfg
    a.cfg.item_time_us = 999.0
    assert b.cfg.item_time_us != 999.0


def test_duplicate_misses_cost_one_io():
    """Regression: repeated missed keys in one batch must cost one SM IO
    (charged to the first occurrence), not one per occurrence — the
    double-count broke ``sm_ios`` parity with the host plane's unique-miss
    coalescing (``BatchedRowCache``)."""
    rng = np.random.default_rng(5)
    tables = {0: rng.standard_normal((64, 8)).astype(np.float32)}
    eng = DeviceServingEngine(tables, DEVICES["nand_flash"],
                              EngineConfig(hbm_cache_bytes=1 << 20,
                                           use_kernels=False))
    # cold cache; query 0 pools row 7 four times, query 1 pools it again
    idx = np.array([[[7, 7, 7, 7]], [[7, 3, 3, 5]]], np.int32)
    _, stats = eng.serve_batch(idx)
    assert stats[0].sm_ios == 1          # row 7 once, not 4x
    assert stats[1].sm_ios == 2          # rows 3 and 5; row 7 already filled
    assert eng.io.total_ios == 3
    # and the fill happened exactly once: everything hits next batch
    _, warm = eng.serve_batch(idx)
    assert sum(s.sm_ios for s in warm) == 0


def test_engine_matches_host_store_accounting():
    """Differential vs the host plane on an identical stream: per-query
    ``sm_ios`` exactly equal, and per-query ``latency_us`` (Eq. 3:
    ``max(item_time, sm_lat)``) equal too — so are the store-level totals."""
    rng = np.random.default_rng(7)
    rows = [200, 150, 300]
    tables = {t: rng.standard_normal((r, 16)).astype(np.float32)
              for t, r in enumerate(rows)}
    eng = DeviceServingEngine(
        tables, DEVICES["nand_flash"],
        EngineConfig(hbm_cache_bytes=8 << 20, num_devices=2,
                     use_kernels=False))
    metas = [TableMeta(table_id=t, num_rows=r, dim_bytes=eng.row_bytes,
                       pooling_factor=4, zipf_alpha=1.05, kind="user")
             for t, r in enumerate(rows)]
    store = SDMEmbeddingStore(
        metas, DEVICES["nand_flash"],
        SDMConfig(fm_cache_bytes=8 << 20, num_devices=2,
                  item_time_us=eng.cfg.item_time_us))
    for rep in range(3):
        idx = np.stack([rng.integers(0, r, (32, 4)) for r in rows],
                       axis=1).astype(np.int32)
        _, stats = eng.serve_batch(idx, bg_iops=1e5)
        host = [store.serve_query({t: idx[b, t] for t in range(3)},
                                  bg_iops=1e5) for b in range(32)]
        assert [s.sm_ios for s in stats] == [q.sm_ios for q in host], rep
        np.testing.assert_allclose([s.latency_us for s in stats],
                                   [q.latency_us for q in host])
    assert eng.stats.sm_ios == store.stats.sm_ios
    np.testing.assert_allclose(eng.stats.latency_us, store.stats.latency_us)


def test_degenerate_batches():
    """B=0, P=1, and pre-serving ``hit_rate`` must not crash."""
    rng = np.random.default_rng(8)
    eng = DeviceServingEngine(
        {0: rng.standard_normal((16, 4)).astype(np.float32)},
        DEVICES["nand_flash"], EngineConfig(use_kernels=False))
    assert eng.hit_rate == 0.0                    # no lookups yet
    pooled, stats = eng.serve_batch(np.zeros((0, 1, 4), np.int32))
    assert pooled.shape == (0, 1, 4) and stats == []
    assert eng.stats.sm_ios == 0                  # empty batch costs nothing
    pooled, stats = eng.serve_batch(np.zeros((2, 1, 1), np.int32))  # P=1
    assert pooled.shape == (2, 1, 4) and len(stats) == 2


def test_valid_mask_and_columnar_entry():
    """Padded positions (valid=False) pool nothing, cost no IO, and never
    perturb the cache; serve_columnar round-trips through dense_from_chunk
    with the same accounting as serve_batch."""
    from repro.core.columnar import ColumnarQueries
    rng = np.random.default_rng(9)
    tables = {3: rng.standard_normal((32, 8)).astype(np.float32),
              5: rng.standard_normal((48, 8)).astype(np.float32)}
    eng = DeviceServingEngine(tables, DEVICES["nand_flash"],
                              EngineConfig(use_kernels=False))
    reqs = [{3: np.array([1, 2, 3]), 5: np.array([4])},
            {5: np.array([4, 7, 7, 9, 11])}]       # ragged + a repeat
    chunk = ColumnarQueries.from_requests(reqs).whole()
    idx, valid = dense_from_chunk(chunk, eng.table_slot, 2)
    assert idx.shape[2] == 8                       # P=5 padded to pow2
    assert valid.sum() == 9
    pooled, tm, ios = eng.serve_columnar(chunk)
    np.testing.assert_allclose(pooled, eng.reference_pool(idx, valid),
                               atol=1e-5)
    assert ios.tolist() == [4, 3]                  # 7 deduped; 4 re-hits
    assert int(eng.state["hits"]) + int(eng.state["misses"]) == 9
    # empty chunk
    empty = ColumnarQueries.from_requests([]).whole()
    pooled, tm, ios = eng.serve_columnar(empty)
    assert pooled.shape == (0, 2, 8) and len(tm) == 0 and len(ios) == 0


def test_coalesced_io_matches_per_table_submit():
    """serve_batch's single submit_batch_multi over the [batch, tables]
    miss block must match per-table submit_batch calls bit for bit (same
    per-query latencies, same IO totals)."""
    rng = np.random.default_rng(4)
    tables = {i: rng.standard_normal((64, 8)).astype(np.float32)
              for i in range(3)}
    eng = DeviceServingEngine(tables, DEVICES["nand_flash"],
                              EngineConfig(hbm_cache_bytes=1 << 16))
    idx = rng.integers(0, 64, (7, 3, 5)).astype(np.int32)
    _, stats = eng.serve_batch(idx, bg_iops=8_000)
    assert eng.io.total_ios == sum(s.sm_ios for s in stats)
    # the flattened-multi and per-table submissions share one latency model:
    # identical per-element results for any miss-count block
    from repro.core.io_sim import IOEngine
    miss = rng.integers(0, 40, (7, 3))
    io_a = IOEngine(eng.io.device, eng.cfg.num_devices, eng.cfg.io_queue)
    io_b = IOEngine(eng.io.device, eng.cfg.num_devices, eng.cfg.io_queue)
    lat_multi, _ = io_a.submit_batch_multi(
        miss.reshape(-1), np.full(miss.size, eng.row_bytes, np.int64), 8_000)
    sm_multi = lat_multi.reshape(miss.shape).max(axis=1)
    sm_ref = np.zeros(miss.shape[0], np.float64)
    for t in range(miss.shape[1]):
        lats, _ = io_b.submit_batch(miss[:, t], eng.row_bytes, 8_000)
        np.maximum(sm_ref, lats, out=sm_ref)
    np.testing.assert_array_equal(sm_multi, sm_ref)
    assert (io_a.total_ios, io_a.total_bus_bytes, io_a.total_wanted_bytes) \
        == (io_b.total_ios, io_b.total_bus_bytes, io_b.total_wanted_bytes)


@pytest.mark.parametrize("layout", [None, "row", "table"])
def test_step_takes_the_store_as_arguments(layout):
    # arrays a jitted function closes over are embedded in the program as
    # constants: a 1 GiB store would become a 1 GiB literal in the step
    from repro.launch.mesh import make_embed_mesh
    from repro.runtime.sharded_engine import ShardedServingEngine
    rng = np.random.default_rng(3)
    tables = {i: rng.standard_normal((100, 8)).astype(np.float32)
              for i in range(2)}
    cfg = EngineConfig(hbm_cache_bytes=1 << 16)
    eng = (DeviceServingEngine(tables, DEVICES["nand_flash"], cfg)
           if layout is None else
           ShardedServingEngine(tables, DEVICES["nand_flash"], cfg,
                                mesh=make_embed_mesh(1), layout=layout))
    idx = rng.integers(0, 100, (3, 2, 4)).astype(np.int32)
    text = eng.lower_step(idx, np.ones(idx.shape, bool)).as_text()
    store = "x".join(map(str, eng.payload.shape)) + "xui8"
    main = next(line for line in text.splitlines() if "@main(" in line)
    assert store in main
    assert "constant dense" not in "\n".join(
        line for line in text.splitlines() if store in line)


def test_step_regions_carry_their_scopes():
    """The jitted step's regions are named scopes, so each device op's
    op_name metadata says which region (and cache part) it belongs to."""
    from repro.obs import tracing as names
    rng = np.random.default_rng(6)
    eng = DeviceServingEngine(
        {i: rng.standard_normal((50, 8)).astype(np.float32) for i in range(2)},
        DEVICES["nand_flash"], EngineConfig(hbm_cache_bytes=1 << 14))
    idx = rng.integers(0, 50, (3, 2, 4)).astype(np.int32)
    lowered = eng.lower_step(idx, np.ones(idx.shape, bool))
    text = lowered.as_text(debug_info=True)
    for scope in names.ENGINE_SCOPES:
        assert f'"jit(step)/{scope}/' in text, scope
    for scope in names.CACHE_SCOPES:
        # the ranking step's regions are not in the user step
        assert (f"/{scope}/" in text) != (scope in names.RANK_SCOPES), scope
    assert lowered.compile().as_text().count("engine.") > 0
    assert eng._step.__wrapped__.__name__ == "step"      # program jit_step


def test_padding_counters_and_invisible_without_handle():
    """With a telemetry handle the engine counts each block's positions
    (B x T x P) and the valid ones (the chunk's lookups); without one it
    records nothing and serves bit-identically."""
    from repro.core.columnar import ColumnarQueries
    from repro.obs import make_telemetry
    rng = np.random.default_rng(10)
    tables = {t: rng.standard_normal((60, 8)).astype(np.float32)
              for t in (2, 4, 7)}
    reqs = [{t: rng.integers(0, 60, rng.integers(1, 6)) for t in tables
             if rng.random() < 0.8} for _ in range(5)]
    chunk = ColumnarQueries.from_requests(reqs).whole()
    lookups = sum(len(v) for r in reqs for v in r.values())
    engines = [DeviceServingEngine(tables, DEVICES["nand_flash"],
                                   EngineConfig(hbm_cache_bytes=1 << 13))
               for _ in range(2)]
    engines[1].telemetry = make_telemetry(True)
    outs = [[e.serve_columnar(chunk) for _ in range(2)] for e in engines]
    idx, valid = dense_from_chunk(chunk, engines[0].table_slot, 3)
    counters = engines[1].telemetry.registry.counters
    assert counters["engine.positions"] == 2 * idx.size
    assert counters["engine.valid_positions"] == 2 * lookups == 2 * valid.sum()
    assert engines[0].telemetry is None
    for a, b in zip(*outs):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    for k in engines[0].state:
        np.testing.assert_array_equal(engines[0].state[k], engines[1].state[k])


def _dense_reference(chunk, table_slot, num_tables):
    """Plain per-query packing of ``chunk.requests()``: the rule
    ``dense_from_chunk`` follows, one request at a time."""
    reqs = chunk.requests()
    P = max([len(v) for r in reqs for v in r.values()] + [1])
    P = 1 << (P - 1).bit_length()
    idx = np.zeros((len(reqs), num_tables, P), np.int32)
    valid = np.zeros((len(reqs), num_tables, P), bool)
    for b, req in enumerate(reqs):
        for tid, v in req.items():
            idx[b, table_slot[tid], :len(v)] = v
            valid[b, table_slot[tid], :len(v)] = True
    return idx, valid


def _random_requests(seed, n, tids, max_len):
    rng = np.random.default_rng(seed)
    return [{int(t): rng.integers(0, 1000, rng.integers(0, max_len + 1))
             for t in rng.permutation(tids) if rng.random() < 0.7}
            for _ in range(n)]


def _cq(reqs):
    from repro.core.columnar import ColumnarQueries
    return ColumnarQueries.from_requests(reqs)


_SORTED3 = {0: 0, 1: 1, 2: 2}


@pytest.mark.parametrize("make,P", [
    # ragged pooling: the longest bag (5) rounds up to 8
    (lambda: (_cq([{0: np.array([1, 2, 3]), 2: np.array([4])},
                   {1: np.arange(5)}]).whole(), _SORTED3), 8),
    # every bag of one index: P = 1
    (lambda: (_cq([{0: np.array([7])}, {1: np.array([3]),
                                        2: np.array([0])}]).whole(),
              _SORTED3), 1),
    # a query that touches no table; table 3 that no query touches
    (lambda: (_cq([{0: np.array([5, 6])}, {}, {2: np.array([9])}]).whole(),
              {0: 0, 1: 1, 2: 2, 3: 3}), 2),
    # zero-length segments, one of them the only segment of its query
    (lambda: (_cq([{0: np.array([], np.int64), 1: np.array([2, 3, 4])},
                   {2: np.array([], np.int64)}]).whole(), _SORTED3), 4),
    # a uniform-stride chunk from the middle of a trace
    (lambda: (_cq(_random_requests(1, 12, range(4), 6)).chunk(4, 8, 4),
              {t: t for t in range(4)}), None),
    # an ad-hoc [qs, qe) range
    (lambda: (_cq(_random_requests(2, 12, range(4), 9)).chunk(3, 10),
              {t: t for t in range(4)}), None),
    # slots in another order than the sorted table ids
    (lambda: (_cq(_random_requests(3, 6, [2, 5, 7], 4)).whole(),
              {7: 0, 2: 1, 5: 2}), None),
    # an empty chunk, alone and cut from a trace
    (lambda: (_cq([]).whole(), _SORTED3), 1),
    (lambda: (_cq(_random_requests(4, 6, range(3), 4)).chunk(3, 3),
              _SORTED3), 1),
], ids=["ragged", "p1", "untouched", "zero_len", "stride_mid", "adhoc",
        "slot_order", "empty", "empty_range"])
def test_dense_from_chunk_matches_per_query_packing(make, P):
    chunk, table_slot = make()
    T = len(table_slot)
    idx, valid = dense_from_chunk(chunk, table_slot, T)
    ref_idx, ref_valid = _dense_reference(chunk, table_slot, T)
    assert idx.dtype == np.int32 and valid.dtype == bool
    assert np.array_equal(idx, ref_idx) and np.array_equal(valid, ref_valid)
    if P is not None:
        assert idx.shape == (chunk.n_queries, T, P)


def test_dense_from_chunk_rejects_unknown_table():
    chunk = _cq([{0: np.array([1])}, {4: np.array([2, 3])}]).whole()
    with pytest.raises(KeyError):
        dense_from_chunk(chunk, {0: 0, 1: 1}, 2)
