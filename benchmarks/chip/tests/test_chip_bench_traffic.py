"""The benchmark's vectorized traffic generator against the statistics of
``repro.workloads``: pooling per table, the padded pooling of each chunk,
drift epochs, and the arrivals every seed shares."""
import dataclasses

import numpy as np
import pytest

import chip_bench_testlib  # noqa: F401  (paths)
import traffic
from repro.workloads import ARCHETYPES, build_trace

CHUNK = 32


def repro_trace(arch: str, n: int = 512):
    spec = ARCHETYPES[arch]
    spec = dataclasses.replace(spec, num_queries=n, seed=3, tenants=tuple(
        dataclasses.replace(t, num_user_tables=12, num_item_tables=2,
                            table_bytes=2e7) for t in spec.tenants))
    tr = build_trace(spec)
    metas = [m for m in tr.all_metas() if m.kind == "user"]
    cfg = {"chunk_queries": CHUNK, "tables": {
        "rows": [m.num_rows for m in metas],
        "pooling": [m.pooling_factor for m in metas],
        "zipf_alpha": [m.zipf_alpha for m in metas]}}
    lens = np.diff(tr.queries.seg_offsets).reshape(n, len(metas))
    return spec.tenants[0], cfg, tr, lens


def mix(**kw):
    base = {"arrival": {"process": "backlog", "max_qps": 1024},
            "warmup_queries": 0}
    return dict(base, **kw)


def padded(lens):
    return [1 << (int(lens[s:s + CHUNK].max()) - 1).bit_length()
            for s in range(0, len(lens), CHUNK)]


@pytest.mark.parametrize("arch", ["zipf_steady", "zipf_drift"])
def test_pooling_and_padding_match_repro(arch):
    ten, cfg, _, want = repro_trace(arch)
    got = traffic.generate(cfg, mix(pool_sigma=ten.pool_sigma), 11, 1.0)
    if ten.pool_sigma == 0:
        assert (got.lens == np.asarray(cfg["tables"]["pooling"])).all()
        assert (want == got.lens[:len(want)]).all()
    else:
        ratio = got.lens.mean(axis=0) / want.mean(axis=0)
        assert np.all(np.abs(ratio - 1) < 0.1), ratio
    # the padded pooling of 32-query chunks is the same power of two
    assert np.median(padded(got.lens)) == np.median(padded(want))


def test_drift_epochs_rotate_the_hot_rows_like_repro():
    """Within each epoch the hottest row of a table is the one
    ``repro.workloads.trace.zipf_indices_drift`` ranks first then."""
    from repro.workloads.trace import zipf_indices_drift
    _, cfg, _, _ = repro_trace("zipf_steady")
    period = 256
    got = traffic.generate(cfg, mix(drift_period_queries=period,
                                    drift_blend=0.3), 5, 1.0)
    T = len(cfg["tables"]["rows"])
    t = int(np.argmax(cfg["tables"]["zipf_alpha"]))
    rows = cfg["tables"]["rows"][t]
    for epoch in range(got.n_queries // period):
        segs = np.arange(epoch * period, (epoch + 1) * period) * T + t
        vals = np.concatenate([got.values[got.seg_offsets[s]:
                                          got.seg_offsets[s + 1]]
                               for s in segs])
        hot = np.bincount(vals, minlength=rows).argmax()
        rank0 = zipf_indices_drift(np.random.default_rng(0), rows,
                                   cfg["tables"]["zipf_alpha"][t], 4000,
                                   epoch=epoch)
        assert hot == np.bincount(rank0, minlength=rows).argmax()


def test_same_seed_same_traffic_and_every_seed_the_same_gaps():
    _, cfg, _, _ = repro_trace("zipf_steady")
    m = mix(arrival={"process": "poisson", "rate_qps": 1024},
            arrival_seed=4, warmup_queries=64)
    a = traffic.generate(cfg, m, 2 ** 31 + 9, 2.0)
    b = traffic.generate(cfg, m, 2 ** 31 + 9, 2.0)
    c = traffic.generate(cfg, m, 17, 2.0)
    assert (a.values == b.values).all() and (a.due_s == b.due_s).all()
    assert not (a.values == c.values).all()
    assert a.warmup == 64 and len(a.due_s) == 2048
    assert 0 < a.due_s.min() and a.due_s.max() < 2.0
    assert not (a.due_s == c.due_s).all()
    # the same gaps, the one after the last arrival included, in another
    # order
    gaps = lambda t: np.sort(np.diff(np.r_[0.0, t.due_s, 2.0]))
    np.testing.assert_allclose(gaps(a), gaps(c), atol=1e-9)
    assert len(traffic.chunk_ready_s(a)) == 2048 // CHUNK
