#!/usr/bin/env python3
"""Chip smoke test: serve dlrm-m1-width traffic through the device engine.

One TPU (the default) runs the main serving path at Table 6 widths:

* a ``zipf_steady`` dlrm-m1 trace (61 user tables at their Table 6 pooling,
  average 42) is served in chunks of 32 queries through
  ``DeviceServingEngine.serve_columnar``: one uint8 row width of 128 plus the
  8 B scale/bias header (136 B, inside M1's 90-172 B), a backing store of at
  least 1 GiB on the device behind a 4 MiB HBM row cache (a fifth of the
  trace's working set);
* the compiled engine step must hold the Pallas kernels (``tpu_custom_call``);
* pooled outputs are checked against the engine's numpy oracle;
* each chunk's first query ranks an item batch of 50 (30 item tables,
  pooling 9) through a 31-layer MLP stack of width 300
  (``dlrm.score_items``), checked against a float32 run at ``highest``
  matmul precision.

``--chips 4`` runs only the sharded path: ``ShardedServingEngine`` in the
``row`` and ``table`` layouts on a 4-device mesh, against the single-device
engine in the same process (per-query ``sm_ios`` equal, pooled outputs
within 1e-5).

Run from the repository root::

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips, sharded path only

Progress lines go to stdout; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
With no TPU, or when a phase fails, the script exits non-zero without it.
Weights and traffic come from fixed seeds. The persistent compile cache is
``$JAX_COMPILATION_CACHE_DIR`` or ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

CHUNK = 32                  # queries per engine call
NUM_CHUNKS = 16             # the second half measures the warm hit rate
TABLE_BYTES = 1.5e9         # M1 inventory share: ~8.1M user rows, >= 1 GiB
MIN_STORE_BYTES = 1 << 30
CACHE_BYTES = 4 << 20       # ~29k rows: a fifth of the trace's working set
SHARD_CACHE_BYTES = 256 << 20   # --chips 4: no set ever overflows
DIM = 128                   # uint8 payload per row (+ 8 B scale/bias)
ITEM_BATCH = 50             # Table 6 M1 item batch
ITEM_POOL = 9               # Table 6 M1 item pooling (average)
NUM_DENSE = 13
EMBED_STD = 0.02            # layers.embed_init's scale
BOTTOM_MLP = (300,) * 7 + (DIM,)   # 8 + 23 = 31 layers of ~300 (Table 6)
TOP_MLP = (300,) * 22 + (1,)
# Pooled bags reach |2.5| (Zipf repeats hot rows within a bag); device and
# numpy sums differ only in order, a few ulps (2.4e-7 at 2.5) each.
POOL_TOL = 1e-5
# The tested run uses the default matmul precision. Emulating one bf16 pass
# per matmul on the CPU (same widths and weights, real pooled bags) moved
# scores by at most 0.019; 0.05 allows that and still catches a wrong user
# embedding, which moved them by 0.16-0.33.
SCORE_TOL = 0.05
MIN_SCORE_STD = 0.02        # the scores must spread for the check to bite
SEED = 0


class PhaseError(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def build_trace(num_queries: int, table_bytes: float):
    from repro.workloads import ARCHETYPES, build_trace as _build
    spec = ARCHETYPES["zipf_steady"]
    spec = dataclasses.replace(
        spec, num_queries=num_queries, seed=SEED,
        tenants=tuple(dataclasses.replace(
            t, model="dlrm-m1", num_user_tables=61, num_item_tables=30,
            table_bytes=table_bytes) for t in spec.tenants))
    return _build(spec)


def build_model(trace, bottom=BOTTOM_MLP, top=TOP_MLP):
    """DLRM whose tables follow the trace inventory. Embeddings are drawn at
    ``layers.embed_init``'s scale (std 0.02): Zipf traffic repeats hot rows
    within a bag, and larger rows would saturate the interaction and so
    every score. MLP weights are He-scaled so activations neither vanish nor
    blow up over 31 layers."""
    import jax
    import numpy as np
    from repro.models import dlrm
    users = [m for m in trace.all_metas() if m.kind == "user"]
    items = [m for m in trace.all_metas() if m.kind == "item"]
    arch = dlrm.DLRMArch(num_dense=NUM_DENSE, embed_dim=DIM,
                         user_tables=tuple(m.num_rows for m in users),
                         item_tables=tuple(m.num_rows for m in items),
                         pooling=ITEM_POOL, bottom_mlp=bottom, top_mlp=top)

    def init(key):
        p = dlrm.init_params(arch, key)
        p["tables"] = [t * (EMBED_STD * np.sqrt(DIM)) for t in p["tables"]]
        for k in ("bottom", "top"):
            p[k] = [dict(l, w=l["w"] * np.sqrt(2.0)) for l in p[k]]
        return p

    params = jax.jit(init)(jax.random.PRNGKey(SEED))    # one compile
    tables = {m.table_id: params["tables"][i] for i, m in enumerate(users)}
    return arch, params, tables


def compiled_kernels(engine, chunk) -> int:
    """Compile the engine step for this chunk's shapes; count the Pallas
    kernels (``tpu_custom_call``) in the compiled program."""
    from repro.runtime.engine import dense_from_chunk
    idx, valid = dense_from_chunk(chunk, engine.table_slot,
                                  len(engine.table_ids))
    text = engine.lower_step(idx, valid).compile().as_text()
    return sum("tpu_custom_call" in line and "custom-call(" in line
               for line in text.splitlines())


def store_bytes(engine) -> int:
    return int(engine.payload.nbytes + engine.scale.nbytes + engine.bias.nbytes)


def working_set(chunks, table_slot) -> int:
    """Distinct (table, row) keys the chunks touch."""
    import numpy as np
    keys = [np.int64(table_slot[v.tid]) << 32 | v.vals.astype(np.int64)
            for ch in chunks for v in ch.table_views()]
    return len(np.unique(np.concatenate(keys)))


def one_chip(num_chunks=NUM_CHUNKS, table_bytes=TABLE_BYTES,
             min_store=MIN_STORE_BYTES, cache_bytes=CACHE_BYTES,
             bottom=BOTTOM_MLP, top=TOP_MLP, want_kernels=True):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.io_sim import DEVICES
    from repro.models import dlrm
    from repro.runtime.engine import (DeviceServingEngine, EngineConfig,
                                      dense_from_chunk)

    t0 = time.perf_counter()
    trace = build_trace(num_chunks * CHUNK, table_bytes)
    arch, params, tables = build_model(trace, bottom, top)
    engine = DeviceServingEngine(tables, DEVICES["nand_flash"],
                                 EngineConfig(hbm_cache_bytes=cache_bytes))
    del tables
    chunks = [ch.columnar for ch in trace.chunks(CHUNK)]
    T = len(engine.table_ids)
    pools = [m.pooling_factor for m in trace.all_metas() if m.kind == "user"]
    check(T == 61, f"{T} user tables, want 61")
    ws = working_set(chunks, engine.table_slot)
    cap = engine.cache.geo.capacity_rows
    check(store_bytes(engine) >= min_store,
          f"store holds {store_bytes(engine)} B, want >= {min_store}")
    check(2 * cap <= ws, f"cache holds {cap} rows, working set only {ws}")
    log(f"setup: {T} user tables (mean pooling {np.mean(pools):.1f}), "
        f"{int(engine.rows_per_table.sum())} rows, store "
        f"{store_bytes(engine)} B, cache {cap} rows for a working set of "
        f"{ws}, {time.perf_counter() - t0:.1f} s")

    t0 = time.perf_counter()
    n_kernels = compiled_kernels(engine, chunks[0])
    log(f"compile: engine step {time.perf_counter() - t0:.1f} s, "
        f"{n_kernels} tpu_custom_call")
    if want_kernels:
        check(n_kernels >= 2, "compiled engine step holds no Pallas kernels")

    score = jax.jit(lambda p, u, it, d: dlrm.score_items(p, u, it, d, arch))

    def score_ref_fn(p, u, it, d):
        with jax.default_matmul_precision("highest"):
            return dlrm.score_items(p, u, it, d, arch)

    score_ref = jax.jit(score_ref_fn)
    item_rows = np.asarray(arch.item_tables)
    rng = np.random.default_rng(SEED)
    pool_err = score_err = 0.0
    served = 0
    warm = None
    t0 = time.perf_counter()
    for i, ch in enumerate(chunks):
        if i == len(chunks) // 2:
            warm = (int(engine.state["hits"]), int(engine.state["misses"]))
        pooled, _, _ = engine.serve_columnar(ch)
        idx, valid = dense_from_chunk(ch, engine.table_slot, T)
        ref = engine.reference_pool(idx, valid)
        pool_err = max(pool_err, float(np.abs(pooled - ref).max()))
        served += ch.n_queries
        items = jnp.asarray(np.stack(
            [rng.integers(0, r, (ITEM_BATCH, ITEM_POOL)) for r in item_rows]),
            jnp.int32)
        dense = jnp.asarray(rng.standard_normal((ITEM_BATCH, NUM_DENSE)),
                            jnp.float32)
        s = np.asarray(score(params, jnp.asarray(pooled[0]), items, dense))
        s_ref = np.asarray(score_ref(params, jnp.asarray(ref[0]), items, dense))
        check(s.shape == (ITEM_BATCH,) and np.isfinite(s).all(),
              f"scores {s.shape}, finite={np.isfinite(s).all()}")
        check(float(s_ref.std()) >= MIN_SCORE_STD,
              f"reference scores barely spread (std {s_ref.std():.2e})")
        score_err = max(score_err, float(np.abs(s - s_ref).max()))
    serve_s = time.perf_counter() - t0
    hits = int(engine.state["hits"]) - warm[0]
    misses = int(engine.state["misses"]) - warm[1]
    warm_hit = hits / max(1, hits + misses)
    log(f"serve: {served} queries in {len(chunks)} chunks, "
        f"{serve_s:.1f} s host clock incl. checks, sm_ios "
        f"{engine.stats.sm_ios}, hit rate {engine.hit_rate:.4f} "
        f"(warm half {warm_hit:.4f})")
    log(f"check: max |pooled - oracle| {pool_err:.3e} (tol {POOL_TOL}), "
        f"max |score - f32 highest| {score_err:.3e} (tol {SCORE_TOL})")
    check(served == len(trace), f"served {served} of {len(trace)} queries")
    check(pool_err <= POOL_TOL, f"pooled error {pool_err:.3e} > {POOL_TOL}")
    check(score_err <= SCORE_TOL, f"score error {score_err:.3e} > {SCORE_TOL}")
    check(warm_hit > 0, "no warm cache hits")


def four_chips(num_chunks=NUM_CHUNKS, table_bytes=TABLE_BYTES,
               cache_bytes=SHARD_CACHE_BYTES, n=4, want_kernels=True):
    """Sharded engine (row and table layouts) vs the single-device engine.
    Every shard has its own cache of ``cache_bytes``, so the engines agree
    on sm_ios only while no cache set overflows: the cache is sized so, and
    the single engine must miss each distinct key exactly once."""
    import jax
    import numpy as np
    from repro.core.io_sim import DEVICES
    from repro.launch.mesh import make_embed_mesh
    from repro.runtime.engine import DeviceServingEngine, EngineConfig
    from repro.runtime.sharded_engine import ShardedServingEngine

    check(len(jax.devices()) >= n, f"{len(jax.devices())} devices, want {n}")
    t0 = time.perf_counter()
    trace = build_trace(num_chunks * CHUNK, table_bytes)
    # the f32 tables (4 GiB) stay on the host: on the first device they
    # would crowd out its shard of every engine built after them
    tables = {t: np.asarray(v)
              for t, v in build_model(trace, (DIM,), (1,))[2].items()}
    cfg = EngineConfig(hbm_cache_bytes=cache_bytes)
    chunks = [ch.columnar for ch in trace.chunks(CHUNK)]
    single = DeviceServingEngine(tables, DEVICES["nand_flash"], cfg)
    ws = working_set(chunks, single.table_slot)
    base = [single.serve_columnar(ch) for ch in chunks]
    ios_single = single.stats.sm_ios
    log(f"single: {len(trace)} queries, sm_ios {ios_single} for a working "
        f"set of {ws}, hit rate {single.hit_rate:.4f}, "
        f"{time.perf_counter() - t0:.1f} s")
    check(ios_single == ws, "single engine evicted: sm_ios != working set")
    del single
    gc.collect()        # an engine's jitted step holds it in a cycle
    for layout in ("row", "table"):
        t0 = time.perf_counter()
        eng = ShardedServingEngine(tables, DEVICES["nand_flash"], cfg,
                                   mesh=make_embed_mesh(n), layout=layout)
        n_kernels = compiled_kernels(eng, chunks[0])
        if want_kernels:
            check(n_kernels >= 2, f"{layout}: no Pallas kernels compiled")
        err = 0.0
        same_ios = True
        for ch, (p0, _, ios0) in zip(chunks, base):
            p, _, ios = eng.serve_columnar(ch)
            err = max(err, float(np.abs(p - p0).max()))
            same_ios &= bool(np.array_equal(ios, ios0))
        log(f"{layout}: {n} shards, {n_kernels} tpu_custom_call, sm_ios "
            f"{eng.stats.sm_ios} vs {ios_single} (per query equal: "
            f"{same_ios}), max |pooled - single| {err:.3e} (tol 1e-5), "
            f"hit rate {eng.hit_rate:.4f}, {time.perf_counter() - t0:.1f} s")
        check(eng.stats.sm_ios == ios_single and same_ios,
              f"{layout}: sm_ios differ")
        check(err <= 1e-5, f"{layout}: pooled error {err:.3e} > 1e-5")
        del eng
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {dev.device_kind} x{len(devices)}, compile cache "
        f"{enable_compile_cache()}")
    try:
        if args.chips == 4:
            four_chips()
        else:
            one_chip()
    except PhaseError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    log(f"memory: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
