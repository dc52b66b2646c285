"""Useful work of one served chunk, counted from what the algorithm needs,
whatever implements it: valid lookups only (no padding, no row-group
amplification), rows at their stored size (payload plus the 8 B scale and
bias header), the pooled output, and for the whole step each distinct row
read once.

A chunk's counts (``reference.expected_reads`` makes them) are:
``bags`` (query, table) bags, ``lookups`` valid lookups, ``hits`` lookups
that hit the row cache, ``unique`` distinct keys, ``unique_misses``
distinct missed keys (one SCM read and one cache fill each).
"""
from __future__ import annotations

OUT_BYTES = 4            # a pooled element is float32
KEY_BYTES = 8            # (table, row) int32 pair
FLOPS_PER_ELEM = 3       # dequantize (multiply, add) and pool (add)


def row_bytes(cfg: dict) -> int:
    """Stored bytes of a row: one byte per element and the header."""
    return cfg["dim"] + cfg["row_header_bytes"]


def tag_line_bytes(cfg: dict) -> int:
    return cfg["cache_ways"] * KEY_BYTES


def gather_pool(cfg: dict, c: dict):
    """(bytes, flops) of pooling the missed lookups from the store."""
    d = cfg["dim"]
    misses = c["lookups"] - c["hits"]
    return (misses * row_bytes(cfg) + c["bags"] * d * OUT_BYTES,
            misses * d * FLOPS_PER_ELEM)


def cache_probe(cfg: dict, c: dict):
    """(bytes, flops) of probing every lookup: its key and its set's tag
    line, and the row of each hit."""
    return (c["lookups"] * (KEY_BYTES + tag_line_bytes(cfg))
            + c["hits"] * row_bytes(cfg), 0)


def step(cfg: dict, c: dict):
    """(bytes, flops) of the whole step: each distinct key probed and its
    row read once, each missed row written into the cache, the pooled
    output written, every lookup dequantized and pooled."""
    d = cfg["dim"]
    return (c["unique"] * (KEY_BYTES + tag_line_bytes(cfg) + row_bytes(cfg))
            + c["unique_misses"] * row_bytes(cfg)
            + c["bags"] * d * OUT_BYTES,
            c["lookups"] * d * FLOPS_PER_ELEM)


def least_seconds(work, peak: dict):
    """Least time the chip needs for ``(bytes, flops)`` and which bound
    sets it."""
    b, f = work
    tb, tf = b / peak["hbm_bytes_per_s"], f / peak["flops_per_s"]
    return (tb, "hbm_bytes") if tb >= tf else (tf, "flops")


def total(fn, cfg: dict, counts) -> tuple:
    """Summed ``(bytes, flops)`` of ``fn`` over many chunks."""
    works = [fn(cfg, c) for c in counts]
    return (sum(w[0] for w in works), sum(w[1] for w in works))
