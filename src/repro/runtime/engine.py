"""Device-resident batched serving engine (HBM row cache + Pallas kernels).

The device analogue of ``SDMEmbeddingStore.serve_batch``: embedding tables
live quantized in a (simulated) SM tier, hot dequantized rows live in an HBM
row cache (``JaxRowCache``), and one jitted step serves a whole
``[batch, tables, pooling]`` index block:

    probe   — ``cache_probe`` Pallas kernel: per query key, a scalar way
              match over the set's tag line, and one DMA of the hit row
              from the HBM cache (§4.3).
    gather  — misses are routed to the ``gather_pool`` Pallas kernel, which
              fuses gather + rowwise dequant + pooling over the quantized
              backing store (§4.4); hit positions point at a zero sentinel
              row so they contribute nothing to the miss-side pool.
    fill    — missed rows are dequantized and scattered into the cache
              (LRU way eviction), so the next batch hits in HBM.

The pooled output is the hit-side pool (from cache data) plus the miss-side
pool (from the backing store). IO accounting happens host-side through the
same analytic ``IOEngine`` the host store uses: the whole ``[batch, tables]``
miss-count block goes through one coalesced ``submit_batch_multi`` call,
giving per-query latencies under Eq. 3 overlap. On a TPU the kernels
compile; on the CPU (the tests) they run in interpret mode.

Miss accounting mirrors the host plane's unique-miss coalescing
(``BatchedRowCache.access_batch``): repeated missed ``(table, row)`` keys in
one batch cost one SM IO — charged to the first occurrence in query order,
exactly where a sequential run would take the miss before the fill makes
every later occurrence a hit — and fill the cache once (duplicates are
masked out of ``cache.insert`` so one scatter can't double-fill an LRU set).

Ranking (§2.2, Eq. 2): given item tables and dense-half weights the engine
also holds an FM-direct item store (8-bit rows resident in HBM, no row
cache, no SM reads; §4.6) and ``rank_columnar`` runs one jitted program:
the user step above, the item gather-pool, the broadcast of each query's
pooled user bags over its candidates, and the dense half
(``models.dlrm.dense_logits``: bottom MLP, dot interaction, top MLP), then
the sigmoid. SM reads and Eq. 3 accounting come from the user side alone.

Instrumentation, on the JAX profiler's clock: the host path's steps are
``jax.profiler.TraceAnnotation`` spans and the step's regions are
``jax.named_scope``s, both named in ``repro.obs.tracing``; with a telemetry
handle attached, each step adds its block's positions and valid positions
to ``engine.positions`` and ``engine.valid_positions``, and each ranking
step its item block's to ``engine.item_positions`` and
``engine.item_valid_positions`` and its candidates to
``engine.items_scored``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import CacheGeometry, JaxRowCache, dual_cache_geometry
from repro.core.columnar import ColumnarChunk
from repro.core.io_sim import DeviceModel, IOEngine, IOQueueConfig
from repro.core.quant import quantize_rows, row_bytes
from repro.core.sdm import QueryStats
from repro.kernels import ops
from repro.models import dlrm
from repro.obs import tracing as names

TraceAnnotation = jax.profiler.TraceAnnotation


@jax.jit
def _quantize_all(tables):
    return [{k: q[k] for k in ("payload", "scale", "bias")}
            for q in map(quantize_rows, tables)]


def quantize_tables(tables: Sequence) -> Tuple[List[np.ndarray], ...]:
    """Row-quantize every table to 8 bits in one compiled program (one
    compile, not one per op and table shape). Returns host copies
    ``(payloads, scales, biases)``, one entry per table."""
    qts = _quantize_all([jnp.asarray(t) for t in tables])
    return tuple([np.asarray(q[k]) for q in qts]
                 for k in ("payload", "scale", "bias"))


def dense_from_chunk(chunk: ColumnarChunk, table_slot: Dict[int, int],
                     num_tables: int) -> Tuple[np.ndarray, np.ndarray]:
    """Columnar (CSR) chunk -> dense ``[B, T, P]`` index block + valid mask.

    ``P`` is the chunk's max pooling length rounded up to a power of two
    (bounding jit recompiles across chunks); absent/padded positions get
    index 0 with ``valid=False`` — the device step routes them to the zero
    sentinel row so they contribute nothing and cost no IO.

    One vectorized pass over the chunk's query-major CSR slice: its cost
    follows the lookups, not the table count. A table of the chunk that
    ``table_slot`` lacks raises ``KeyError``.
    """
    B = chunk.n_queries
    seg = chunk.segments()
    P = max(int(seg.lens.max(initial=0)), 1)
    P = 1 << (P - 1).bit_length()
    idx = np.zeros(B * num_tables * P, np.int32)
    valid = np.zeros(B * num_tables * P, bool)
    if len(seg.tid):
        # table id -> slot: one dict lookup per distinct table
        tids, inv = np.unique(seg.tid, return_inverse=True)
        slot = np.array([table_slot[t] for t in tids.tolist()],
                        np.int64)[inv]
        # flat offset of a segment's first element, less its CSR offset
        start = (seg.qid * num_tables + slot) * P
        eoff = np.cumsum(seg.lens) - seg.lens
        flat = (np.repeat(start - eoff, seg.lens)
                + np.arange(len(seg.vals), dtype=np.int64))
        idx[flat] = seg.vals
        valid[flat] = True
    return (idx.reshape(B, num_tables, P),
            valid.reshape(B, num_tables, P))


def _build_store(tables: Sequence[np.ndarray]):
    """Row-quantize ``tables`` to 8 bits and stack them into one device
    store: ``(payload, scale, bias, offsets, sentinel, rows per table)``.
    Zero rows after the tables (the first is the sentinel) pad the store to
    whole kernel row groups, so the gather kernel needs no alignment
    copy."""
    rows = np.array([t.shape[0] for t in tables], np.int64)
    pls, scs, bss = quantize_tables(tables)
    R = int(rows.sum())
    n_store = ops.aligned_rows(R + 1)
    payload = np.zeros((n_store, pls[0].shape[1]), pls[0].dtype)
    payload[:R] = np.concatenate(pls)
    scale = np.zeros(n_store, np.float32)
    scale[:R] = np.concatenate(scs)
    bias = np.zeros(n_store, np.float32)
    bias[:R] = np.concatenate(bss)
    offsets = jnp.asarray(np.r_[0, np.cumsum(rows)[:-1]].astype(np.int32))
    return (jnp.asarray(payload), jnp.asarray(scale), jnp.asarray(bias),
            offsets, R, rows)


# matmul precision of the dense half: float32 at full precision, which on a
# TPU takes several bf16 passes; one pass misses the reference's logits
DENSE_PRECISION = "highest"


@dataclasses.dataclass
class EngineConfig:
    hbm_cache_bytes: int = 8 << 20       # HBM budget for the row cache
    ways: int = 8
    use_kernels: bool = True             # False -> pure-jnp reference paths
    num_devices: int = 2
    item_time_us: float = 200.0
    io_queue: IOQueueConfig = dataclasses.field(default_factory=IOQueueConfig)


class DeviceServingEngine:
    """Batched multi-query, multi-table serving over device kernels.

    ``tables``: {table_id: [rows, dim] float array} — every table shares one
    embedding dim (one backing store, one cache geometry). Rows are stored
    int8 row-quantized, the layout the paper's DWORD-granularity SM reads
    fetch (§4.1.1).

    ``item_tables`` ({table_id: [rows, dim]}, the user tables' dim) and
    ``dense`` (dense-half weights, ``models.dlrm.init_dense_half``), given
    together, add the ranking side that ``rank_columnar`` serves; without
    them the engine builds and compiles the user side alone.
    """

    def __init__(self, tables: Dict[int, np.ndarray], device: DeviceModel,
                 cfg: Optional[EngineConfig] = None,
                 item_tables: Optional[Dict[int, np.ndarray]] = None,
                 dense: Optional[dict] = None):
        # None sentinel: a dataclass default instance here would be shared
        # (and mutable) across every engine constructed without a config
        cfg = EngineConfig() if cfg is None else cfg
        if not tables:
            raise ValueError("need at least one table")
        dims = {t.shape[1] for t in tables.values()}
        if len(dims) != 1:
            raise ValueError(f"tables must share one embedding dim, got {dims}")
        self.cfg = cfg
        self.dim = dims.pop()
        self.table_ids: List[int] = list(tables)
        self.rows_per_table = np.array([tables[t].shape[0]
                                        for t in self.table_ids], np.int64)

        (self.payload, self.scale, self.bias, self.offsets, self.sentinel,
         _) = _build_store([tables[t] for t in self.table_ids])

        self.row_bytes = row_bytes(self.dim, bits=8)
        geo = dual_cache_geometry(cfg.hbm_cache_bytes, dim=self.dim,
                                  row_payload_bytes=self.row_bytes,
                                  ways=cfg.ways)
        self.cache = JaxRowCache(geo)
        self.state = self.cache.init()
        self.io = IOEngine(device, cfg.num_devices, cfg.io_queue)
        self.stats = QueryStats()        # store-level totals, host-plane shape
        self.telemetry = None            # obs handle; None = bit-invisible
        self.table_slot = {t: i for i, t in enumerate(self.table_ids)}
        step = self._make_step()
        self._step = jax.jit(step)
        self._rank_step = None
        if (item_tables is None) != (dense is None):
            raise ValueError("item tables and dense weights come together")
        if item_tables is not None:
            self._init_items(item_tables, dense, step)

    def _init_items(self, item_tables, dense, step):
        """The FM-direct item store, the dense weights on the device and
        the jitted ranking step around the user ``step``."""
        if {t.shape[1] for t in item_tables.values()} != {self.dim}:
            raise ValueError(f"item tables must have the user tables' dim "
                             f"{self.dim}")
        self.item_ids: List[int] = list(item_tables)
        self.item_slot = {t: i for i, t in enumerate(self.item_ids)}
        (self.item_payload, self.item_scale, self.item_bias,
         self.item_offsets, self.item_sentinel,
         self.item_rows) = _build_store([item_tables[t]
                                         for t in self.item_ids])
        self.dense = jax.tree_util.tree_map(jnp.asarray, dense)
        self.num_dense = int(dense["bottom"][0]["w"].shape[0])
        self._rank_step = jax.jit(self._make_rank_step(step))

    # -- device step ----------------------------------------------------------

    def _make_step(self):
        cache, cfg = self.cache, self.cfg

        # each region is a named scope, so the profiler's device ops carry
        # it in their op_name metadata (``repro.obs.tracing``)
        def step(state, payload, scale, bias, idx, valid):  # idx [B, T, P]
            B, T, P = idx.shape
            tids = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None, :, None], idx.shape)
            tq = tids.reshape(-1)
            rq = idx.reshape(-1)
            vq = valid.reshape(-1)
            with jax.named_scope(names.SCOPE_PROBE):
                vals, hit, state = cache.lookup_device(
                    state, tq, rq, use_kernel=cfg.use_kernels, valid=vq)
            with jax.named_scope(names.SCOPE_GATHER):
                # hit-side pool straight from HBM cache data
                pooled_hit = (vals * hit[:, None]).reshape(
                    B, T, P, -1).sum(axis=2)
                # miss-side pool fused over the quantized backing store; hits
                # and padded positions are pointed at the zero sentinel row
                grow = (self.offsets[tids] + idx).reshape(-1)
                gidx = jnp.where(hit | ~vq, self.sentinel, grow)
                gidx = gidx.reshape(B * T, P).astype(jnp.int32)
                pooled_miss = ops.embedding_gather_pool(
                    payload, scale, bias, gidx,
                    use_kernel=cfg.use_kernels).reshape(B, T, -1)
            with jax.named_scope(names.SCOPE_DEDUPE):
                # unique-miss coalescing (host parity): a repeated missed key
                # is one SM IO and one fill, charged to its first occurrence
                # in flattened (query, table, position) order — the element a
                # sequential run would miss on before its fill turns the rest
                # into hits. Group equal global rows with a stable sort; the
                # group head is the first occurrence.
                miss = vq & ~hit
                gkey = jnp.where(miss, grow, jnp.int32(-1))  # -1: one dead group
                order = jnp.argsort(gkey, stable=True)
                ks = gkey[order]
                head = jnp.concatenate(
                    [jnp.ones((1,), bool), ks[1:] != ks[:-1]])
                first = jnp.zeros(gkey.shape, bool).at[order].set(head)
                io_mask = miss & first
                miss_counts = jnp.sum(io_mask.reshape(B, T, P), axis=2)
            with jax.named_scope(names.SCOPE_FILL):
                # dequantize the fetched rows and insert (LRU eviction),
                # duplicates masked out so one scatter can't double-fill a set
                deq = (payload[grow].astype(jnp.float32)
                       * scale[grow][:, None] + bias[grow][:, None])
                state = cache.insert(state, tq, rq, deq, mask=io_mask)
            return state, pooled_hit + pooled_miss, miss_counts

        return step

    def _make_rank_step(self, step):
        cfg = self.cfg
        offsets, sentinel = self.item_offsets, self.item_sentinel

        def rank_step(state, payload, scale, bias, idx, valid, item_payload,
                      item_scale, item_bias, iidx, ivalid, dense, params):
            # the user step, exactly as serve_columnar runs it: each
            # query's user bags are served once (B_U = 1)
            state, pooled, miss_counts = step(state, payload, scale, bias,
                                              idx, valid)
            B = pooled.shape[0]
            N, Ti, P = iidx.shape                    # N = B x item batch
            with jax.named_scope(names.SCOPE_ITEM):
                # FM-direct: every item lookup gathers from the item
                # store; padded positions point at its zero sentinel row
                tids = jnp.arange(Ti, dtype=jnp.int32)[None, :, None]
                gidx = jnp.where(ivalid, offsets[tids] + iidx, sentinel)
                item_pooled = ops.embedding_gather_pool(
                    item_payload, item_scale, item_bias,
                    gidx.reshape(N * Ti, P).astype(jnp.int32),
                    use_kernel=cfg.use_kernels).reshape(N, Ti, -1)
            with jax.named_scope(names.SCOPE_DENSE):
                # Eq. 2: a query's pooled user bags, broadcast over its
                # candidates, beside each candidate's item bags
                user = jnp.broadcast_to(
                    pooled[:, None], (B, N // B) + pooled.shape[1:])
                emb = jnp.concatenate(
                    [user.reshape((N,) + pooled.shape[1:]), item_pooled],
                    axis=1)
                logits = dlrm.dense_logits(params, dense, emb,
                                           precision=DENSE_PRECISION)
                logits = logits.reshape(B, N // B)
                scores = jax.nn.sigmoid(logits)
            return state, scores, logits, miss_counts

        return rank_step

    def _step_args(self, idx, valid):
        # the store goes in as arguments: arrays a jitted function closes
        # over are embedded in the program as constants
        return (self.state, self.payload, self.scale, self.bias,
                jnp.asarray(idx), jnp.asarray(valid))

    def lower_step(self, idx: np.ndarray, valid: np.ndarray):
        """The jitted device step lowered for this ``[B, T, P]`` block, for
        inspecting what it compiles to (kernels, memory)."""
        return self._step.lower(*self._step_args(idx, valid))

    def _rank_args(self, idx, valid, iidx, ivalid, dense):
        return self._step_args(idx, valid) + (
            self.item_payload, self.item_scale, self.item_bias,
            jnp.asarray(iidx), jnp.asarray(ivalid), jnp.asarray(dense),
            self.dense)

    # -- serving --------------------------------------------------------------

    def serve_batch(self, idx: np.ndarray, bg_iops: float = 0.0,
                    valid: Optional[np.ndarray] = None
                    ) -> Tuple[np.ndarray, List[QueryStats]]:
        """idx: [B, T, P] int32 of per-table local row ids (T in the order of
        ``table_ids``). Returns (pooled [B, T, dim] f32, per-query stats).
        ``valid`` (bool [B, T, P], optional) masks padded positions out of
        pooling, caching and IO accounting."""
        idx, valid = self._checked(idx, valid)
        if idx.shape[0] == 0:            # degenerate empty batch: no device
            return (np.zeros((0, idx.shape[1], self.dim), np.float32), [])
        pooled, miss = self._run_step(idx, valid)
        with TraceAnnotation(names.SPAN_ACCOUNT):
            return pooled, self._account(miss, bg_iops)

    def _checked(self, idx, valid, rows: Optional[np.ndarray] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """``(idx, valid)`` as int32 and bool ``[B, T, P]`` blocks; raises
        on a wrong shape or a valid row index out of its table's range.
        ``rows``: rows per table, by default the user tables'."""
        rows = self.rows_per_table if rows is None else rows
        with TraceAnnotation(names.SPAN_VALIDATE):
            idx = np.asarray(idx, np.int32)
            if idx.ndim != 3:
                raise ValueError(
                    f"idx must be [B, T, P], got shape {idx.shape}")
            if idx.shape[1] != len(rows):
                raise ValueError(
                    f"idx has {idx.shape[1]} tables, engine has {len(rows)}")
            if valid is None:
                valid = np.ones(idx.shape, bool)
            live = np.where(valid, idx, 0)
            if (live < 0).any() or (live >= rows[None, :, None]).any():
                raise ValueError("row index out of range")
        return idx, valid

    def _run_step(self, idx: np.ndarray, valid: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """Run the device step on a checked, non-empty block and wait for
        it: ``(pooled [B, T, dim], deduped miss counts [B, T])`` on the
        host."""
        self._count("engine.positions", "engine.valid_positions", valid)
        return self._run(self._step, self._step_args(idx, valid))

    def _count(self, positions: str, valid_positions: str, valid) -> None:
        """With a telemetry handle, add a block's positions and valid
        positions to the two counters."""
        if self.telemetry is not None:
            reg = self.telemetry.registry
            reg.inc(positions, valid.size)
            reg.inc(valid_positions, int(np.count_nonzero(valid)))

    def _run(self, step, args) -> List[np.ndarray]:
        """Dispatch a jitted step, keep its new cache state and wait for
        the rest of its outputs on the host."""
        with TraceAnnotation(names.SPAN_DISPATCH):
            state, *outs = step(*args)
        self.state = state
        with TraceAnnotation(names.SPAN_FETCH):
            return [np.asarray(o) for o in outs]

    def serve_columnar(self, chunk: ColumnarChunk, bg_iops: float = 0.0
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Serve a columnar (CSR) chunk through the device step — the batched
        data-plane entry matching ``SDMEmbeddingStore.serve_columnar``.
        Returns ``(pooled [B, T, dim] f32, sm_time_us [B] f64, sm_ios [B]
        i64)`` with T in ``table_ids`` order (tables a query does not touch
        pool to zero)."""
        T = len(self.table_ids)
        with TraceAnnotation(names.SPAN_SERVE):
            if chunk.n_queries == 0:
                return (np.zeros((0, T, self.dim), np.float32),
                        np.zeros(0, np.float64), np.zeros(0, np.int64))
            with TraceAnnotation(names.SPAN_PACK):
                idx, valid = dense_from_chunk(chunk, self.table_slot, T)
            pooled, miss = self._run_step(*self._checked(idx, valid))
            with TraceAnnotation(names.SPAN_ACCOUNT):
                return (pooled,) + self._account_arrays(miss, bg_iops)

    def rank_columnar(self, user_chunk: ColumnarChunk,
                      item_chunk: ColumnarChunk, dense: np.ndarray):
        """Rank a chunk's candidates in one device step (§2.2, Eq. 2).

        ``user_chunk``: B queries over the user tables. ``item_chunk``: one
        "query" per candidate over the item tables, ``B x item_batch`` of
        them, query ``b``'s candidates in rows ``b * item_batch`` on.
        ``dense``: ``[B x item_batch, num_dense]`` float32 features.
        Returns ``(scores [B, item_batch] f32, logits [B, item_batch] f32,
        sm_time_us [B] f64, sm_ios [B] i64)``; the SM reads and their
        latency are the user side's, exactly as ``serve_columnar`` gives
        them for ``user_chunk``."""
        if self._rank_step is None:
            raise ValueError("this engine has no item side")
        B, N = user_chunk.n_queries, item_chunk.n_queries
        dense = np.asarray(dense, np.float32)
        if B == 0 or N % B or dense.shape != (N, self.num_dense):
            raise ValueError(
                f"{N} candidates and dense features {dense.shape} do not "
                f"rank {B} queries with {self.num_dense} dense features")
        with TraceAnnotation(names.SPAN_SERVE):
            with TraceAnnotation(names.SPAN_PACK):
                idx, valid = dense_from_chunk(user_chunk, self.table_slot,
                                              len(self.table_ids))
            with TraceAnnotation(names.SPAN_ITEM_PACK):
                iidx, ivalid = dense_from_chunk(item_chunk, self.item_slot,
                                                len(self.item_ids))
            idx, valid = self._checked(idx, valid)
            iidx, ivalid = self._checked(iidx, ivalid, self.item_rows)
            self._count("engine.positions", "engine.valid_positions", valid)
            self._count("engine.item_positions",
                        "engine.item_valid_positions", ivalid)
            if self.telemetry is not None:
                self.telemetry.registry.inc("engine.items_scored", N)
            scores, logits, miss = self._run(
                self._rank_step,
                self._rank_args(idx, valid, iidx, ivalid, dense))
            with TraceAnnotation(names.SPAN_ACCOUNT):
                return (scores, logits) + self._account_arrays(miss, 0.0)

    def _account_arrays(self, miss: np.ndarray, bg_iops: float):
        """:meth:`_account` as per-query arrays ``(sm_time_us f64, sm_ios
        i64)``."""
        stats = self._account(miss, bg_iops)
        return (np.array([s.sm_time_us for s in stats], np.float64),
                np.array([s.sm_ios for s in stats], np.int64))

    def _account(self, miss: np.ndarray, bg_iops: float) -> List[QueryStats]:
        """Per-query IO + Eq. 3 latency accounting for a ``[B, T]`` block of
        deduped miss counts; accumulates store-level ``stats`` exactly like
        the host plane's ``serve_query`` running totals."""
        # one coalesced submission across all (query, table) pairs — the
        # same cross-table flattening the host plane uses; per-element
        # latency is identical to per-table submit_batch calls
        rb = np.full(miss.size, self.row_bytes, np.int64)
        lats, _ = self.io.submit_batch_multi(miss.reshape(-1), rb, bg_iops)
        sm_lat = lats.reshape(miss.shape).max(axis=1)
        if self.telemetry is not None:
            self.telemetry.registry.inc("engine.batches")
            self.telemetry.registry.observe_many("engine.sm_time_us", sm_lat)
        stats = []
        for b in range(miss.shape[0]):
            # Eq. 3: user-side SM time overlaps item-side compute; only the
            # excess surfaces — identical to core/sdm.py serve_query
            q = QueryStats(latency_us=max(self.cfg.item_time_us, sm_lat[b]),
                           sm_ios=int(miss[b].sum()),
                           sm_time_us=float(sm_lat[b]))
            self.stats.latency_us += q.latency_us
            self.stats.sm_ios += q.sm_ios
            stats.append(q)
        return stats

    def reference_pool(self, idx: np.ndarray,
                       valid: Optional[np.ndarray] = None) -> np.ndarray:
        """Numpy oracle for :meth:`serve_batch`'s pooled output."""
        idx = np.asarray(idx)
        offs = np.asarray(self.offsets)
        grow = offs[None, :, None] + idx                     # [B, T, P]
        payload = np.asarray(self.payload)
        deq = (payload[grow].astype(np.float32)
               * np.asarray(self.scale)[grow][..., None]
               + np.asarray(self.bias)[grow][..., None])
        if valid is not None:
            deq = deq * np.asarray(valid)[..., None]
        return deq.sum(axis=2)

    # -- reporting ------------------------------------------------------------

    @property
    def hit_rate(self) -> float:
        h = int(self.state["hits"])
        m = int(self.state["misses"])
        return h / (h + m) if h + m else 0.0
