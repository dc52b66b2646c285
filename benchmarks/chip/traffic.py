"""Traffic generator of the chip benchmark: one general generator, driven by
a mix file (``traffic/<mix>.json``) and a configuration file
(``configs/<config>.json``).

It draws the distributions of ``repro.workloads`` (per-table Zipf alpha,
Table 6 pooling per table, drift epochs with blend, a lognormal pooling
spread) in vectorized form: every table's lookups for the whole run come
from one draw. The formulas are copied from ``repro.workloads.trace``
(``zipf_indices_drift_flat``) so that a change to the program cannot move
the yardstick.

A run's traffic is ``warmup`` queries, served untimed first, followed by the
window's queries. Arrival processes:

* ``poisson``: a fixed offered rate. The window's queries are exactly
  ``rate * seconds`` (rounded to whole chunks); their exponential gaps are
  drawn once from the mix's ``arrival_seed``, scaled to span the window,
  and put in an order drawn from the run's seed. Every seed thus offers the
  same set of gaps, and only their order and the rows differ.
* ``backlog``: every query is due when the window opens. ``max_qps`` times
  the window sets how many queries are made; a run that serves them all
  before the window closes has run out and fails.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

_DRIFT_SALT = np.uint64(0xA24BAED4963EE407)
_PERM_MULT = np.uint64(0x9E3779B97F4A7C15)


@dataclasses.dataclass
class Traffic:
    """A run's queries in columnar (CSR, query-major) form.

    ``values`` holds every lookup; query ``q`` owns the segments
    ``query_seg[q]:query_seg[q + 1]``, one per table in configuration order,
    and segment ``s`` owns ``values[seg_offsets[s]:seg_offsets[s + 1]]``.
    ``due_s`` is each window query's arrival, in seconds after the window
    opens (warm-up queries have none)."""
    values: np.ndarray
    seg_offsets: np.ndarray
    seg_table: np.ndarray
    query_seg: np.ndarray
    lens: np.ndarray            # [queries, tables] lookups per segment
    warmup: int                 # leading queries served before the window
    due_s: np.ndarray           # [window queries] arrival after window open
    chunk: int
    backlog: bool               # every window query due when it opens

    @property
    def n_queries(self) -> int:
        return len(self.query_seg) - 1


def seed_words(seed: int) -> list:
    """A run seed (any whole number) as 32-bit words for ``SeedSequence``."""
    s = int(seed) % (1 << 64)
    return [s & 0xFFFFFFFF, s >> 32]


def zipf_rows(rng: np.random.Generator, num_rows: int, alpha: float,
              epochs: np.ndarray, blend: float) -> np.ndarray:
    """Zipf-distributed row ids, one per entry of ``epochs``, whose hot set
    rotates with the epoch; ``blend`` sends that share of draws through the
    next epoch's permutation (``repro.workloads.trace`` formulas)."""
    n = len(epochs)
    ranks = np.minimum(rng.zipf(alpha, size=n), num_rows) - 1
    e = epochs.astype(np.uint64)
    if blend > 0.0:
        e = e + (rng.random(n) < blend)
    x = ranks.astype(np.uint64) + e * _DRIFT_SALT
    x = (x * _PERM_MULT) >> np.uint64(17)
    return (x % np.uint64(num_rows)).astype(np.int64)


def window_queries(cfg: dict, mix: dict, seconds: float) -> int:
    """Queries the window offers (poisson) or may need at most (backlog),
    in whole chunks."""
    a = mix["arrival"]
    rate = a["rate_qps"] if a["process"] == "poisson" else a["max_qps"]
    chunk = cfg["chunk_queries"]
    return chunk * max(1, round(rate * seconds / chunk))


def arrivals(mix: dict, n: int, seconds: float, seed: int) -> np.ndarray:
    a = mix["arrival"]
    if a["process"] == "backlog":
        return np.zeros(n)
    if a["process"] != "poisson":
        raise ValueError(f"unknown arrival process {a['process']!r}")
    base = np.random.default_rng(mix["arrival_seed"])
    gaps = base.exponential(1.0, size=n + 1)
    gaps *= seconds / gaps.sum()
    order = np.random.default_rng(seed_words(seed) + [2])
    return np.cumsum(order.permutation(gaps))[:n]


def generate(cfg: dict, mix: dict, seed: int, seconds: float) -> Traffic:
    """The run's queries for configuration ``cfg`` under mix ``mix``."""
    chunk = cfg["chunk_queries"]
    warm = chunk * math.ceil(mix["warmup_queries"] / chunk)
    n_win = window_queries(cfg, mix, seconds)
    n = warm + n_win
    tabs = cfg["tables"]
    rows, pools, alphas = tabs["rows"], tabs["pooling"], tabs["zipf_alpha"]
    T = len(rows)
    sigma = float(mix.get("pool_sigma", 0.0))
    period = int(mix.get("drift_period_queries", 0))
    blend = float(mix.get("drift_blend", 0.0)) if period else 0.0
    epochs_q = (np.arange(n, dtype=np.int64) // period if period
                else np.zeros(n, np.int64))

    rng = np.random.default_rng(seed_words(seed) + [1])
    lens = np.empty((n, T), np.int64)
    for t in range(T):
        if sigma > 0:
            lens[:, t] = np.maximum(1, np.round(
                pools[t] * rng.lognormal(0.0, sigma, size=n))).astype(np.int64)
        else:
            lens[:, t] = pools[t]
    seg_offsets = np.concatenate([[0], np.cumsum(lens.ravel())])
    starts = seg_offsets[:-1].reshape(n, T)
    values = np.empty(int(seg_offsets[-1]), np.int64)
    for t in range(T):
        lt = lens[:, t]
        tot = int(lt.sum())
        local = np.arange(tot, dtype=np.int64) - np.repeat(
            np.cumsum(lt) - lt, lt)
        dest = np.repeat(starts[:, t], lt) + local
        values[dest] = zipf_rows(rng, int(rows[t]), float(alphas[t]),
                                 np.repeat(epochs_q, lt), blend)
    return Traffic(
        values=values, seg_offsets=seg_offsets,
        seg_table=np.tile(np.arange(T, dtype=np.int64), n),
        query_seg=np.arange(0, n * T + 1, T, dtype=np.int64),
        lens=lens, warmup=warm, due_s=arrivals(mix, n_win, seconds, seed),
        chunk=chunk, backlog=mix["arrival"]["process"] == "backlog")


def chunk_ready_s(traffic: Traffic) -> np.ndarray:
    """When each window chunk may be dispatched: the arrival of its last
    query, in seconds after the window opens."""
    return traffic.due_s[traffic.chunk - 1::traffic.chunk]


def padded_pooling(traffic: Traffic, first: int, last: int) -> int:
    """``P`` of the dense block the engine builds for queries
    ``[first, last)``: the longest segment, rounded up to a power of two."""
    p = int(traffic.lens[first:last].max())
    return 1 << (p - 1).bit_length()
