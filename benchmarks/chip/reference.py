"""Plain reference of the served path, and the comparison that decides
``correct``.

It imports nothing of the program and takes nothing the program made. From
the run's seed and the configuration it recomputes:

* the pooled bag of each lookup bag: every row made again from the seed
  (``tables.row_values``), quantized row-wise to 8 bits (asymmetric,
  ``q = round((x - min) / ((max - min) / 255))``), dequantized in float32
  and summed in float64;
* the deduped SCM reads of each query, from a set-associative LRU row
  cache followed chunk by chunk over everything the program served. Its
  semantics are the device engine's: a chunk probes every lookup against
  the cache as the chunk found it (a hit refreshes its way's stamp); a
  missed key is read once, charged to its first lookup in (query, table,
  position) order, and filled; new keys of one set take its ways in LRU
  order (oldest stamp first, ties to the lower way), one each in order of
  appearance, wrapping past the associativity so that the later key keeps
  the way. The set of a key is ``set_index`` of ``repro.core.cache``,
  copied here.

``pool(..., bits=4)`` is the control: the same reference with rows held in
4 bits, the next precision below the configuration's 8.
"""
from __future__ import annotations

import numpy as np

import tables as tables_mod

# Limits of the numbers compared (readings in PERF.md, "Correctness"):
# the widest gap of a pooled element from the reference, and the queries
# whose SCM read count differs from the reference's (exact).
POOLED_GAP_LIMIT = 1e-3
SM_IOS_MISMATCH_LIMIT = 0


def set_index(t: np.ndarray, r: np.ndarray, num_sets: int) -> np.ndarray:
    """32-bit mix of (table, row) -> set id (``repro.core.cache``)."""
    h = t.astype(np.uint32) * np.uint32(0x85EBCA6B)
    h = h ^ (r.astype(np.uint32) * np.uint32(0x9E3779B9))
    h = h ^ (h >> np.uint32(16))
    return (h % np.uint32(num_sets)).astype(np.int64)


class RowCache:
    """Set-associative LRU row cache over (table, row) keys."""

    def __init__(self, num_sets: int, ways: int):
        self.sets, self.ways = num_sets, ways
        self.tag = np.full((num_sets, ways), -1, np.int64)   # table << 32 | row
        self.stamp = np.zeros((num_sets, ways), np.int64)
        self.clock = 0

    def serve(self, t: np.ndarray, r: np.ndarray):
        """One chunk's lookups in (query, table, position) order. Returns
        ``(hit, read, distinct)``: which lookups hit, which are the first
        lookup of a missed key (one SCM read each), and how many distinct
        keys the chunk holds."""
        key = t << 32 | r
        uk, first, inv = np.unique(key, return_index=True,
                                   return_inverse=True)
        s = set_index(uk >> 32, uk & 0xFFFFFFFF, self.sets)
        match = self.tag[s] == uk[:, None]
        hit = match.any(axis=1)
        self.clock += 1
        self.stamp[s[hit], match[hit].argmax(axis=1)] = self.clock
        miss = np.flatnonzero(~hit)
        order = np.argsort(first[miss])             # in order of appearance
        new, ns = first[miss][order], s[miss][order]
        read = np.zeros(len(t), bool)
        read[new] = True
        order = np.argsort(ns, kind="stable")
        run = np.r_[True, ns[order][1:] != ns[order][:-1]]
        pos = np.arange(len(ns))
        rank = np.empty(len(ns), np.int64)
        rank[order] = pos - np.maximum.accumulate(np.where(run, pos, 0))
        lru = np.argsort(self.stamp[ns], axis=1, kind="stable")
        way = lru[np.arange(len(ns)), rank % self.ways]
        self.clock += 1
        # numpy assigns repeated indices in order, so the later key keeps
        # a way that a wrapped rank shares
        self.tag[ns, way] = key[new]
        self.stamp[ns, way] = self.clock
        return hit[inv.ravel()], read, len(uk)


def chunk_lookups(traffic, first: int, last: int):
    """Queries ``[first, last)`` as flat lookups in (query, table,
    position) order: ``(query, table, row, bag_starts)``, with queries
    counted from ``first`` and ``bag_starts`` the offset of each (query,
    table) bag."""
    T = traffic.lens.shape[1]
    lens = traffic.lens[first:last].ravel()
    v0 = traffic.seg_offsets[first * T]
    rows = traffic.values[v0:traffic.seg_offsets[last * T]]
    bag = np.repeat(np.arange(len(lens)), lens)
    starts = traffic.seg_offsets[first * T:last * T] - v0
    return bag // T, bag % T, rows, starts


def dequantized(seed: int, grow: np.ndarray, dim: int, bits: int = 8):
    """Rows ``grow`` made from the seed, quantized row-wise to ``bits`` and
    dequantized, float32."""
    x = tables_mod.row_values(np, tables_mod.seed_keys(seed), grow, dim)
    levels = np.float32((1 << bits) - 1)
    lo = x.min(axis=1, keepdims=True)
    hi = x.max(axis=1, keepdims=True)
    scale = np.where(hi > lo, (hi - lo) / levels, np.float32(1))
    q = np.clip(np.round((x - lo) / scale), 0, levels)
    return (q * scale + lo).astype(np.float32)


def pool(seed: int, offsets: np.ndarray, table: np.ndarray, row: np.ndarray,
         starts: np.ndarray, n_bags: int, dim: int, bits: int = 8):
    """Pooled bags ``[n_bags, dim]`` (float64) of one chunk's lookups: the
    matrix of how often each bag looks up each distinct row, times the
    dequantized rows."""
    from scipy import sparse
    grow = offsets[table] + row
    uniq, inv = np.unique(grow, return_inverse=True)
    rows = dequantized(seed, uniq, dim, bits).astype(np.float64)
    counts = sparse.csr_matrix((np.ones(len(row)), inv, np.r_[starts, len(row)]),
                               shape=(n_bags, len(uniq)))
    return counts @ rows


def expected_reads(cfg: dict, traffic, served):
    """Follow the chunks the program served, in order (``served``: list of
    ``(first, last)`` query ranges). Returns, per serving, the SCM reads of
    each query, and the counts of useful work that ``work`` prices."""
    cache = RowCache(cfg["cache_sets"], cfg["cache_ways"])
    reads, counts = [], []
    for first, last in served:
        q, t, r, _ = chunk_lookups(traffic, first, last)
        hit, read, distinct = cache.serve(t, r)
        reads.append(np.bincount(q[read], minlength=last - first))
        counts.append(dict(
            bags=(last - first) * traffic.lens.shape[1], lookups=len(r),
            hits=int(hit.sum()), unique=distinct,
            unique_misses=int(read.sum())))
    return reads, counts
