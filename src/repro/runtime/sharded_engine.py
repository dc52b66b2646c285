"""Sharded multi-device serving engine — the paper's scale-out regime (§7,
Table 9) on the JAX/Pallas plane.

The quantized backing store is spread across a 1-D ``('shard',)`` device
mesh (``launch.mesh.make_embed_mesh``) in one of two layouts
(``launch.sharding.EMBED_LAYOUTS``):

* **row** — every device owns a contiguous row slice of *every* table
  (slice size ``ceil(rows_t / n)``). A query key ``(t, r)`` belongs to
  shard ``r // slice_t``; each shard probes its own HBM row cache and
  gathers its owned misses from its local store slice, pooling partial
  sums that combine with one ``lax.psum`` (all-reduce) over 'shard'.
* **table** — every device owns whole tables (contiguous blocks of
  ``ceil(T / n)`` table slots). Each shard pools its tables completely and
  the per-table outputs are exchanged with ``lax.all_gather``; the owner
  column is selected per table.

Both layouts run the *same* per-shard step the single-device
``DeviceServingEngine`` uses — the ``cache_probe`` and ``gather_pool``
Pallas kernels plus the unique-miss dedupe — under ``shard_map``/``jit``:
non-owned and padded keys are masked to the cache's NULL key (never hit,
never counted) and pointed at the local zero sentinel row (pool nothing).
Because ownership partitions keys across shards, the union of per-shard
first-occurrence dedupes equals the single-device global dedupe, so summed
``sm_ios`` match the single-device engine exactly; quantization happens on
whole tables before slicing, so pooled outputs match bit-for-bit up to
f32 summation order (<= 1e-5).

IO accounting: the per-shard ``[B, T]`` miss blocks go host-side through
one coalesced ``IOEngine.submit_batch_multi`` over all (shard, query,
table) elements — each shard drains its misses through its own queue
wave, so a query's SM time is the max over shards and tables, and its
``sm_ios`` the sum — the same ``QueryStats`` path the host plane uses.

On CPU, run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
to get a real 8-way mesh (see ``tests/test_sharded_engine.py``).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.cache import JaxRowCache, dual_cache_geometry
from repro.core.columnar import ColumnarChunk
from repro.core.io_sim import DeviceModel, IOEngine
from repro.core.quant import row_bytes
from repro.core.sdm import QueryStats
from repro.kernels import ops
from repro.launch.mesh import make_embed_mesh
from repro.launch.sharding import (EMBED_LAYOUTS, embed_batch_specs,
                                   embed_cache_specs, embed_store_specs)
from repro.runtime.engine import (EngineConfig, dense_from_chunk,
                                  quantize_tables)


def _shard_store(n: int, rows: int, dim: int, dtype):
    """Zeroed per-shard store of ``rows`` table rows plus zero rows (the
    first is the sentinel at index ``rows``) up to whole kernel row groups.
    Returns (payload [n, L, dim], scale [n, L], bias [n, L])."""
    L = ops.aligned_rows(rows + 1)
    return (np.zeros((n, L, dim), dtype), np.zeros((n, L), np.float32),
            np.zeros((n, L), np.float32))


class ShardedServingEngine:
    """Batched serving over a device mesh; drop-in ``serve_batch`` /
    ``serve_columnar`` shape-compatible with ``DeviceServingEngine``."""

    def __init__(self, tables: Dict[int, np.ndarray], device: DeviceModel,
                 cfg: Optional[EngineConfig] = None, *,
                 mesh=None, layout: str = "row"):
        cfg = EngineConfig() if cfg is None else cfg
        if layout not in EMBED_LAYOUTS:
            raise ValueError(
                f"layout must be one of {EMBED_LAYOUTS}, got {layout!r}")
        if not tables:
            raise ValueError("need at least one table")
        dims = {t.shape[1] for t in tables.values()}
        if len(dims) != 1:
            raise ValueError(f"tables must share one embedding dim, got {dims}")
        self.cfg = cfg
        self.layout = layout
        self.mesh = make_embed_mesh() if mesh is None else mesh
        if self.mesh.axis_names != ("shard",):
            raise ValueError("mesh must have the single axis ('shard',)")
        self.n = self.mesh.shape["shard"]
        self.dim = dims.pop()
        self.table_ids: List[int] = list(tables)
        self.table_slot = {t: i for i, t in enumerate(self.table_ids)}
        self.rows_per_table = np.array([tables[t].shape[0]
                                        for t in self.table_ids], np.int64)
        T = len(self.table_ids)

        # quantize whole tables first (bit-identical to the single-device
        # store), then slice rows into shards
        pls, scs, bss = quantize_tables([tables[t] for t in self.table_ids])
        # global row ids (offsets into the unsharded concatenation) key the
        # cross-shard miss dedupe; they never index device memory here
        self.g_offsets = np.r_[0, np.cumsum(self.rows_per_table)[:-1]].astype(
            np.int64)

        if layout == "row":
            # shard k owns rows [k*slice_t, (k+1)*slice_t) of every table
            self.slice_rows = np.array(
                [max(1, math.ceil(r / self.n)) for r in self.rows_per_table],
                np.int64)
            loff = np.r_[0, np.cumsum(self.slice_rows)[:-1]]
            L = int(self.slice_rows.sum())
            payload, scale, bias = _shard_store(self.n, L, self.dim,
                                                pls[0].dtype)
            for ti in range(T):
                s = int(self.slice_rows[ti])
                for k in range(self.n):
                    lo = k * s
                    hi = min(lo + s, int(self.rows_per_table[ti]))
                    if lo >= hi:
                        continue
                    dst = int(loff[ti])
                    payload[k, dst:dst + hi - lo] = pls[ti][lo:hi]
                    scale[k, dst:dst + hi - lo] = scs[ti][lo:hi]
                    bias[k, dst:dst + hi - lo] = bss[ti][lo:hi]
            self.local_offsets = loff
            self.owner_of_table = None
            self.sentinel = L
        else:  # table layout: shard k owns table slots [k*Tl, (k+1)*Tl)
            Tl = max(1, math.ceil(T / self.n))
            self.owner_of_table = np.minimum(
                np.arange(T, dtype=np.int64) // Tl, self.n - 1)
            loff = np.zeros(T, np.int64)
            shard_rows = np.zeros(self.n, np.int64)
            for ti in range(T):
                k = int(self.owner_of_table[ti])
                loff[ti] = shard_rows[k]
                shard_rows[k] += int(self.rows_per_table[ti])
            L = int(shard_rows.max())
            payload, scale, bias = _shard_store(self.n, L, self.dim,
                                                pls[0].dtype)
            for ti in range(T):
                k = int(self.owner_of_table[ti])
                dst = int(loff[ti])
                r = int(self.rows_per_table[ti])
                payload[k, dst:dst + r] = pls[ti]
                scale[k, dst:dst + r] = scs[ti]
                bias[k, dst:dst + r] = bss[ti]
            self.slice_rows = None
            self.local_offsets = loff
            self.sentinel = L

        store_sh = {k: jax.sharding.NamedSharding(self.mesh, s)
                    for k, s in embed_store_specs(layout).items()}
        self.payload = jax.device_put(payload, store_sh["payload"])
        self.scale = jax.device_put(scale, store_sh["scale"])
        self.bias = jax.device_put(bias, store_sh["bias"])

        self.row_bytes = row_bytes(self.dim, bits=8)
        geo = dual_cache_geometry(cfg.hbm_cache_bytes, dim=self.dim,
                                  row_payload_bytes=self.row_bytes,
                                  ways=cfg.ways)
        self.cache = JaxRowCache(geo)
        cache_sh = {k: jax.sharding.NamedSharding(self.mesh, s)
                    for k, s in embed_cache_specs().items()}
        # replicate on the host: a device-side broadcast would build all n
        # copies on one device before slicing them out
        one = self.cache.init()
        self.state = {k: jax.device_put(
            np.broadcast_to(np.asarray(v)[None], (self.n,) + v.shape),
            cache_sh[k]) for k, v in one.items()}
        self.io = IOEngine(device, cfg.num_devices, cfg.io_queue)
        self.stats = QueryStats()
        self.telemetry = None          # obs handle; None = bit-invisible
        self._step = jax.jit(self._make_step())

    # -- device step ----------------------------------------------------------

    def _make_step(self):
        cache, cfg, layout = self.cache, self.cfg, self.layout
        n = self.n
        g_off = jnp.asarray(self.g_offsets, jnp.int32)         # [T]
        l_off = jnp.asarray(self.local_offsets, jnp.int32)     # [T]
        sentinel = jnp.int32(self.sentinel)
        if layout == "row":
            slice_rows = jnp.asarray(self.slice_rows, jnp.int32)
        else:
            owner_t = jnp.asarray(self.owner_of_table, jnp.int32)
        b_specs = embed_batch_specs()

        def shard_step(state_st, payload, scale, bias, idx, valid):
            # per-shard blocks arrive with a leading axis of 1
            state = jax.tree.map(lambda x: x[0], state_st)
            payload, scale, bias = payload[0], scale[0], bias[0]
            my = jax.lax.axis_index("shard")
            B, T, Pf = idx.shape
            tids = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None, :, None], idx.shape)
            if layout == "row":
                own = (idx // slice_rows[None, :, None].astype(jnp.int32)) == my
                lrow = l_off[tids] + idx % slice_rows[None, :, None].astype(
                    jnp.int32)
            else:
                own = owner_t[tids] == my
                lrow = l_off[tids] + idx
            v = (valid & own).reshape(-1)
            tq = tids.reshape(-1)
            rq = idx.reshape(-1)
            vals, hit, state = cache.lookup_device(
                state, tq, rq, use_kernel=cfg.use_kernels, valid=v)
            pooled_hit = (vals * hit[:, None]).reshape(B, T, Pf, -1).sum(axis=2)
            lr = lrow.reshape(-1)
            gidx = jnp.where(hit | ~v, sentinel, lr)
            gidx = gidx.reshape(B * T, Pf).astype(jnp.int32)
            pooled_miss = ops.embedding_gather_pool(
                payload, scale, bias, gidx,
                use_kernel=cfg.use_kernels).reshape(B, T, -1)
            # per-shard unique-miss dedupe over *global* row ids; ownership
            # partitions keys, so the shard-wise dedupes union to exactly
            # the single-device global dedupe
            miss = v & ~hit
            grow = (g_off[tq] + rq).astype(jnp.int32)
            gkey = jnp.where(miss, grow, jnp.int32(-1))
            order = jnp.argsort(gkey, stable=True)
            ks = gkey[order]
            head = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
            first = jnp.zeros(gkey.shape, bool).at[order].set(head)
            io_mask = miss & first
            deq = (payload[lr].astype(jnp.float32)
                   * scale[lr][:, None] + bias[lr][:, None])
            state = cache.insert(state, tq, rq, deq, mask=io_mask)
            part = pooled_hit + pooled_miss
            if layout == "row":
                pooled = jax.lax.psum(part, "shard")
            else:
                g = jax.lax.all_gather(part, "shard")       # [n, B, T, D]
                pooled = g[owner_t, :, jnp.arange(T)].transpose(1, 0, 2)
            miss_counts = jnp.sum(io_mask.reshape(B, T, Pf), axis=2)
            return (jax.tree.map(lambda x: x[None], state), pooled,
                    miss_counts[None])

        state_specs = embed_cache_specs()
        return jax.shard_map(
            shard_step, mesh=self.mesh,
            in_specs=(state_specs, P("shard", None, None), P("shard", None),
                      P("shard", None), b_specs["idx"], b_specs["valid"]),
            out_specs=(state_specs, b_specs["pooled"], b_specs["miss"]),
            check_vma=False)

    def _step_args(self, idx, valid):
        # the store goes in as arguments: arrays a jitted function closes
        # over are embedded in the program as constants
        return (self.state, self.payload, self.scale, self.bias,
                jnp.asarray(idx), jnp.asarray(valid))

    def lower_step(self, idx: np.ndarray, valid: np.ndarray):
        """The jitted device step lowered for this ``[B, T, P]`` block, for
        inspecting what it compiles to (kernels, memory)."""
        return self._step.lower(*self._step_args(idx, valid))

    # -- serving --------------------------------------------------------------

    def serve_batch(self, idx: np.ndarray, bg_iops: float = 0.0,
                    valid: Optional[np.ndarray] = None
                    ) -> Tuple[np.ndarray, List[QueryStats]]:
        """Same contract as ``DeviceServingEngine.serve_batch``; IO charges
        each shard's misses separately (a query waits on its slowest shard)."""
        idx = np.asarray(idx, np.int32)
        if idx.ndim != 3:
            raise ValueError(f"idx must be [B, T, P], got shape {idx.shape}")
        if idx.shape[1] != len(self.table_ids):
            raise ValueError(
                f"idx has {idx.shape[1]} tables, engine has "
                f"{len(self.table_ids)}")
        if valid is None:
            valid = np.ones(idx.shape, bool)
        live = np.where(valid, idx, 0)
        if (live < 0).any() or (live >= self.rows_per_table[None, :, None]).any():
            raise ValueError("row index out of range")
        if idx.shape[0] == 0:
            return (np.zeros((0, idx.shape[1], self.dim), np.float32), [])
        state, pooled, miss = self._step(*self._step_args(idx, valid))
        self.state = state
        return np.asarray(pooled), self._account(np.asarray(miss), bg_iops)

    def serve_columnar(self, chunk: ColumnarChunk, bg_iops: float = 0.0
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Columnar chunk entry — shape-compatible with the host plane's
        ``serve_columnar``: returns ``(pooled [B, T, dim], sm_time_us [B],
        sm_ios [B])``."""
        T = len(self.table_ids)
        if chunk.n_queries == 0:
            return (np.zeros((0, T, self.dim), np.float32),
                    np.zeros(0, np.float64), np.zeros(0, np.int64))
        idx, valid = dense_from_chunk(chunk, self.table_slot, T)
        pooled, stats = self.serve_batch(idx, bg_iops, valid=valid)
        return (pooled,
                np.array([s.sm_time_us for s in stats], np.float64),
                np.array([s.sm_ios for s in stats], np.int64))

    def _account(self, miss: np.ndarray, bg_iops: float) -> List[QueryStats]:
        """``miss``: [n, B, T] per-shard deduped miss counts. One coalesced
        submission covers every (shard, query, table) element; per query,
        SM time is the max wave over shards x tables (Eq. 3 overlap against
        item time) and ``sm_ios`` the sum — per-shard accounting summed into
        the same ``QueryStats``/``IOEngine`` path the host plane uses."""
        rb = np.full(miss.size, self.row_bytes, np.int64)
        lats, _ = self.io.submit_batch_multi(miss.reshape(-1), rb, bg_iops)
        sm_lat = lats.reshape(miss.shape).max(axis=(0, 2))     # [B]
        ios_q = miss.sum(axis=(0, 2))                          # [B]
        if self.telemetry is not None:
            reg = self.telemetry.registry
            reg.inc("engine.batches")
            reg.observe_many("engine.sm_time_us", sm_lat)
            for k, v in enumerate(miss.sum(axis=(1, 2)).tolist()):
                reg.inc(f"engine.shard{k}.sm_ios", int(v))
        stats = []
        for b in range(miss.shape[1]):
            q = QueryStats(latency_us=max(self.cfg.item_time_us, sm_lat[b]),
                           sm_ios=int(ios_q[b]),
                           sm_time_us=float(sm_lat[b]))
            self.stats.latency_us += q.latency_us
            self.stats.sm_ios += q.sm_ios
            stats.append(q)
        return stats

    def reference_pool(self, idx: np.ndarray,
                       valid: Optional[np.ndarray] = None) -> np.ndarray:
        """Numpy oracle: dequantize-and-pool over the *unsharded* quantized
        store (rebuilt from the shard packing, so it is exactly the
        single-device store's arithmetic)."""
        idx = np.asarray(idx)
        B, T, Pf = idx.shape
        payload = np.asarray(self.payload)
        scale = np.asarray(self.scale)
        bias = np.asarray(self.bias)
        out = np.zeros((B, T, self.dim), np.float32)
        for ti in range(T):
            if self.layout == "row":
                s = int(self.slice_rows[ti])
                k = idx[:, ti] // s
                lr = int(self.local_offsets[ti]) + idx[:, ti] % s
            else:
                k = np.full(idx[:, ti].shape,
                            int(self.owner_of_table[ti]), np.int64)
                lr = int(self.local_offsets[ti]) + idx[:, ti]
            deq = (payload[k, lr].astype(np.float32)
                   * scale[k, lr][..., None] + bias[k, lr][..., None])
            if valid is not None:
                deq = deq * valid[:, ti][..., None]
            out[:, ti] = deq.sum(axis=1)
        return out

    # -- reporting ------------------------------------------------------------

    @property
    def hit_rate(self) -> float:
        h = int(np.asarray(self.state["hits"]).sum())
        m = int(np.asarray(self.state["misses"]).sum())
        return h / (h + m) if h + m else 0.0
