"""Software-managed set-associative row cache (the paper's FM cache, §4.3).

Two implementations share one geometry:

* :class:`JaxRowCache` — arrays-as-state, pure-functional lookup/insert usable
  under ``jit`` and on-device (HBM). The hot lookup path is the
  ``kernels.cache_probe`` Pallas kernel; this module provides the reference
  semantics and the insert/eviction scatter.
* ``cache_sim.SimRowCache`` — fast host simulator for the trace-driven paper
  reproductions (Fig. 4/6, Tables 8–9 hit rates).

Keys are (table_id, row_id) int32 pairs (two tag planes — no int64 needed on
device). Geometry mirrors the paper's dual cache (Fig. 6): a
*memory-optimized* parameterization (more ways, 8 B metadata/row) for rows
<= 255 B and a *CPU-optimized* one (fewer ways, 40 B metadata/row) above.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.obs import tracing as names

EMPTY = -1
# Reserved query key that can never match a tag line: tags hold either EMPTY
# (-1) or real (table >= 0, row >= 0) ids, so probing (NULL, NULL) is a
# guaranteed miss. The sharded engine remaps keys it does not own to this
# before the probe, so foreign keys neither hit nor perturb the LRU stamps.
NULL_KEY = -2

MEM_OPT_ROW_LIMIT = 255  # bytes; paper: dim <= 255B -> memory-optimized cache
MEM_OPT_METADATA_B = 8
CPU_OPT_METADATA_B = 40


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    num_sets: int
    ways: int
    dim: int  # cached row payload elements

    @property
    def capacity_rows(self) -> int:
        return self.num_sets * self.ways


def make_key(table_id, row_id):
    """(table, row) int32 pair — stacked last-dim-2 array."""
    t = jnp.asarray(table_id, jnp.int32)
    r = jnp.asarray(row_id, jnp.int32)
    return jnp.stack(jnp.broadcast_arrays(t, r), axis=-1)


def set_index(tables: jax.Array, rows: jax.Array, num_sets: int) -> jax.Array:
    """Fibonacci-style 32-bit mix of (table, row) -> set id."""
    h = tables.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
    h = h ^ (rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
    h = h ^ (h >> jnp.uint32(16))
    return (h % jnp.uint32(num_sets)).astype(jnp.int32)


class JaxRowCache:
    """Functional set-associative cache; state is a pytree of arrays."""

    def __init__(self, geometry: CacheGeometry, dtype=jnp.float32):
        self.geo = geometry
        self.dtype = dtype

    def init(self) -> dict:
        g = self.geo
        return {
            "tag_table": jnp.full((g.num_sets, g.ways), EMPTY, jnp.int32),
            "tag_row": jnp.full((g.num_sets, g.ways), EMPTY, jnp.int32),
            "data": jnp.zeros((g.num_sets, g.ways, g.dim), self.dtype),
            "stamp": jnp.zeros((g.num_sets, g.ways), jnp.int32),
            "clock": jnp.zeros((), jnp.int32),
            "hits": jnp.zeros((), jnp.int32),
            "misses": jnp.zeros((), jnp.int32),
        }

    def lookup(self, state: dict, tables: jax.Array, rows: jax.Array
               ) -> Tuple[jax.Array, jax.Array, dict]:
        """tables/rows: [N] int32 -> (values [N, D], hit [N] bool, state')."""
        g = self.geo
        sets = set_index(tables, rows, g.num_sets)             # [N]
        match = ((state["tag_table"][sets] == tables[:, None]) &
                 (state["tag_row"][sets] == rows[:, None]))    # [N, W]
        hit = jnp.any(match, axis=1)
        way = jnp.argmax(match, axis=1)                        # [N]
        values = state["data"][sets, way]                      # [N, D]
        values = jnp.where(hit[:, None], values, 0)
        clock = state["clock"] + 1
        # miss entries scatter out of bounds (dropped): redirecting them to a
        # real slot with an old-value write-back races hit updates there
        stamp = state["stamp"].at[
            jnp.where(hit, sets, jnp.int32(g.num_sets)), way].set(
            clock, mode="drop")
        new_state = dict(state, stamp=stamp, clock=clock,
                         hits=state["hits"] + jnp.sum(hit, dtype=jnp.int32),
                         misses=state["misses"] + jnp.sum(~hit, dtype=jnp.int32))
        return values, hit, new_state

    def lookup_device(self, state: dict, tables: jax.Array, rows: jax.Array,
                      *, use_kernel: bool = True, valid=None
                      ) -> Tuple[jax.Array, jax.Array, dict]:
        """Probe through the ``cache_probe`` Pallas kernel (§4.3 hot path).

        The kernel performs the way match and the data movement — per query,
        a compare over the set's tag line and one DMA of the hit row — while
        the LRU metadata update (stamps, clock, hit counters) stays in plain
        XLA, matching :meth:`lookup` exactly.

        ``valid`` (bool [N], optional) masks out padded / foreign keys: they
        are probed as :data:`NULL_KEY` (guaranteed miss, no tag aliasing with
        ``EMPTY``), never touch the LRU stamps, and count toward neither hits
        nor misses. The returned ``hit`` is False for invalid entries.
        """
        from repro.kernels import ops
        g = self.geo
        if valid is not None:
            valid = jnp.asarray(valid, bool)
            tables = jnp.where(valid, tables, NULL_KEY)
            rows = jnp.where(valid, rows, NULL_KEY)
        sets = set_index(tables, rows, g.num_sets)
        values, hit_i = ops.row_cache_probe(
            state["tag_table"], state["tag_row"], state["data"],
            tables, rows, sets, use_kernel=use_kernel)
        hit = hit_i.astype(bool)
        with jax.named_scope(names.SCOPE_REMATCH):
            match = ((state["tag_table"][sets] == tables[:, None]) &
                     (state["tag_row"][sets] == rows[:, None]))
            way = jnp.argmax(match, axis=1)
        with jax.named_scope(names.SCOPE_STAMP):
            clock = state["clock"] + 1
            stamp = state["stamp"].at[
                jnp.where(hit, sets, jnp.int32(g.num_sets)), way].set(
                clock, mode="drop")
            counted_hit = hit if valid is None else (hit & valid)
            counted_miss = (~hit) if valid is None else ((~hit) & valid)
            new_state = dict(
                state, stamp=stamp, clock=clock,
                hits=state["hits"] + jnp.sum(counted_hit, dtype=jnp.int32),
                misses=state["misses"] + jnp.sum(counted_miss,
                                                 dtype=jnp.int32))
        return values.astype(self.dtype), hit, new_state

    def insert(self, state: dict, tables: jax.Array, rows: jax.Array,
               values: jax.Array, mask=None) -> dict:
        """Insert rows (LRU way eviction). mask=False entries are skipped.

        New keys landing in the same set within one batch take *distinct*
        ways: each gets its rank among the batch's new keys for that set and
        claims the rank-th least-recently-stamped way, exactly what inserting
        them one at a time would do (``cache_sim.BatchedRowCache.fill`` uses
        the same rank-within-set rounds). Without this, every cold key picks
        ``argmin(stamp)`` = way 0 and the scatter's last writer wins, so a
        batch of N set-colliding misses fills one way instead of N.
        Duplicate *identical* keys still resolve to the last writer — dedupe
        upstream (the serving engines mask duplicates before calling this).
        """
        g = self.geo
        if mask is None:
            mask = jnp.ones(tables.shape, bool)
        sets = set_index(tables, rows, g.num_sets)
        with jax.named_scope(names.SCOPE_RANK):
            match = ((state["tag_table"][sets] == tables[:, None]) &
                     (state["tag_row"][sets] == rows[:, None]))
            already = jnp.any(match, axis=1)
            # Rank each new masked key within its set (stable order of
            # appearance): sort keys by set id, number the positions inside
            # each run.
            n = tables.shape[0]
            is_new = mask & ~already
            rank_key = jnp.where(is_new, sets, jnp.int32(g.num_sets))  # park
            order = jnp.argsort(rank_key, stable=True)
            sorted_sets = rank_key[order]
            pos = jnp.arange(n, dtype=jnp.int32)
            run_start = jnp.concatenate(
                [jnp.ones((1,), bool), sorted_sets[1:] != sorted_sets[:-1]])
            start_pos = jax.lax.cummax(jnp.where(run_start, pos, 0))
            rank = jnp.zeros((n,), jnp.int32).at[order].set(pos - start_pos)
        with jax.named_scope(names.SCOPE_LRU):
            # way for a new key = its rank-th entry of the set's LRU order
            # (oldest stamp first); ranks past the associativity wrap — the
            # sequential equivalent, since rank W would evict rank 0's
            # freshly-filled way.
            lru_order = jnp.argsort(state["stamp"][sets], axis=1)  # [N, W]
            way_new = jnp.take_along_axis(
                lru_order, (rank % g.ways)[:, None], axis=1)[:, 0]
            way = jnp.where(already, jnp.argmax(match, axis=1), way_new)
        with jax.named_scope(names.SCOPE_SCATTER):
            # Masked-out entries scatter out of bounds and are dropped. (The
            # previous scheme — redirect them to (0, 0) and write the old
            # value back — raced real inserts targeting slot (0, 0) in the
            # same scatter: a later masked element re-wrote the stale EMPTY
            # tag.)
            sets_w = jnp.where(mask, sets, jnp.int32(g.num_sets))
            clock = state["clock"] + 1
            tt = state["tag_table"].at[sets_w, way].set(tables, mode="drop")
            tr = state["tag_row"].at[sets_w, way].set(rows, mode="drop")
            data = state["data"].at[sets_w, way].set(
                values.astype(self.dtype), mode="drop")
            stamp = state["stamp"].at[sets_w, way].set(clock, mode="drop")
        return dict(state, tag_table=tt, tag_row=tr, data=data,
                    stamp=stamp, clock=clock)


def dual_cache_geometry(fm_budget_bytes: int, dim: int, row_payload_bytes: int,
                        ways: int = 8) -> CacheGeometry:
    """Size a cache to an FM byte budget, with the paper's dual-cache metadata
    overheads (Fig. 6): rows <=255 B use the memory-optimized parameterization."""
    meta = MEM_OPT_METADATA_B if row_payload_bytes <= MEM_OPT_ROW_LIMIT else CPU_OPT_METADATA_B
    per_row = row_payload_bytes + meta
    rows = max(ways, fm_budget_bytes // per_row)
    num_sets = max(1, rows // ways)
    return CacheGeometry(num_sets=num_sets, ways=ways, dim=dim)
