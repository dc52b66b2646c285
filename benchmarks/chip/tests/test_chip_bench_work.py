"""Useful-work counts of the roofline shares: they count valid lookups,
never the padding of the dense block or the 8-row groups the kernel
moves, so a change of padding leaves them as they are."""
import numpy as np

import chip_bench_testlib  # noqa: F401  (paths)
import reference
import traffic
import work

CFG = {"dim": 128, "row_header_bytes": 8, "cache_ways": 8, "cache_sets": 16,
       "chunk_queries": 4,
       "tables": {"rows": [500, 300, 1000], "pooling": [3, 5, 2],
                  "zipf_alpha": [1.1, 1.3, 1.2]}}
PEAK = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}


def counts_of(tr):
    B = tr.chunk
    served = [(s, s + B) for s in range(0, tr.n_queries, B)]
    return reference.expected_reads(CFG, tr, served)[1]


def test_counts_are_valid_lookups_not_padded_positions():
    m = {"arrival": {"process": "backlog", "max_qps": 64},
         "warmup_queries": 0, "pool_sigma": 0.6}
    tr = traffic.generate(CFG, m, 8, 1.0)
    B, T = tr.chunk, tr.lens.shape[1]
    for k, c in enumerate(counts_of(tr)):
        lens = tr.lens[k * B:(k + 1) * B]
        P = traffic.padded_pooling(tr, k * B, (k + 1) * B)
        assert c["lookups"] == lens.sum() < B * T * P
        assert c["bags"] == B * T
        assert c["hits"] <= c["lookups"] and c["unique"] <= c["lookups"]
        assert c["unique_misses"] <= c["unique"]


def test_work_does_not_move_with_padding():
    """The same lookups padded to twice the pooling cost the same work."""
    c = {"bags": 12, "lookups": 30, "hits": 10, "unique": 20,
         "unique_misses": 8}
    row = 128 + 8
    assert work.gather_pool(CFG, c) == (20 * row + 12 * 128 * 4,
                                        20 * 128 * 3)
    assert work.cache_probe(CFG, c) == (30 * (8 + 64) + 10 * row, 0)
    assert work.step(CFG, c) == (20 * (8 + 64 + row) + 8 * row
                                 + 12 * 128 * 4, 30 * 128 * 3)
    t, bound = work.least_seconds(work.step(CFG, c), PEAK)
    assert bound == "hbm_bytes" and t == work.step(CFG, c)[0] / 819e9


def test_totals_add_chunks():
    m = {"arrival": {"process": "backlog", "max_qps": 32},
         "warmup_queries": 0}
    counts = counts_of(traffic.generate(CFG, m, 3, 1.0))
    b, f = work.total(work.step, CFG, counts)
    assert b == sum(work.step(CFG, c)[0] for c in counts)
    assert f == 3 * 128 * sum(c["lookups"] for c in counts)
    assert np.isfinite(b) and b > 0
