"""Served path ``rank``: a whole DLRM query through the device engine
(paper §2.2, Eq. 2). A chunk's queries each rank ``item_batch``
candidates in one device step: every user bag is probed in the HBM row
cache, misses gathered from the 8-bit store and pooled (the ``user_bags``
step, once per query); every candidate's item bags are gathered from the
FM-direct item store and pooled; each query's pooled user bags are
broadcast over its candidates; the dense half (bottom MLP, dot
interaction, top MLP) scores them. The scores and logits ``[B,
item_batch]`` come out on the host with each query's deduped SCM reads,
which the user side alone makes.

The candidate model (Table 6 gives none; the configuration's
``assumed``): a catalog of ``catalog_items`` items, each with fixed bags in
the item tables, drawn once from the run's seed with each table's pooling
and Zipf alpha; each query ranks ``item_batch`` distinct catalog items
drawn Zipf(``candidate_zipf_alpha``) over the catalog; dense features
N(0, 1) per (query, candidate). The functions are those ``user_bags``
documents.
"""
import numpy as np

import reference
import reference_rank
import tables
import traffic as traffic_mod
import work_rank

# salts of the run's seed words beyond traffic.py's (1, 2) and run.py's (3)
WEIGHTS_SALT, CATALOG_SALT, PICKS_SALT, DENSE_SALT = 4, 5, 6, 7
PICK_DRAWS = 256         # Zipf draws per query to find its distinct picks


def item_tables(seed: int, cfg: dict) -> dict:
    """``{table: [rows_t, dim] float32}`` of the item tables, made on the
    default device by one jitted call; their global rows follow the user
    tables' (``reference_rank.item_offsets``)."""
    import jax
    import jax.numpy as jnp

    offs = [int(o) for o in reference_rank.item_offsets(cfg)]
    sizes = [int(n) for n in cfg["item_tables"]["rows"]]
    dim = cfg["dim"]

    @jax.jit
    def make(keys):
        return [tables.row_values(jnp, keys, jnp.arange(n, dtype=jnp.uint32)
                                  + jnp.uint32(o), dim)
                for o, n in zip(offs, sizes)]

    return dict(enumerate(make(jnp.asarray(tables.seed_keys(seed)))))


def build(cfg: dict, seed: int):
    from repro.core.io_sim import DEVICES
    from repro.models import dlrm
    from repro.runtime.engine import (DENSE_PRECISION, DeviceServingEngine,
                                      EngineConfig)
    if cfg["dense_precision"] != DENSE_PRECISION:
        raise ValueError(f"the engine's dense half runs at {DENSE_PRECISION} "
                         f"precision, configuration {cfg['dense_precision']}")
    users = tables.device_tables(seed, cfg["tables"]["rows"], cfg["dim"])
    items = item_tables(seed, cfg)
    weights = dlrm.init_dense_half(
        traffic_mod.seed_words(seed) + [WEIGHTS_SALT], cfg["num_dense"],
        cfg["bottom_mlp"], cfg["top_mlp"],
        cfg["num_user_tables"] + cfg["num_item_tables"])
    engine = DeviceServingEngine(
        users, DEVICES[cfg["sm_device"]],
        EngineConfig(hbm_cache_bytes=cfg["hbm_cache_bytes"],
                     ways=cfg["cache_ways"]),
        item_tables=items, dense=weights)
    del users, items
    geo = engine.cache.geo
    if (geo.num_sets, geo.ways) != (cfg["cache_sets"], cfg["cache_ways"]):
        raise ValueError(f"engine cache {geo.num_sets} sets x {geo.ways} "
                         f"ways, configuration {cfg['cache_sets']} x "
                         f"{cfg['cache_ways']}")
    return engine


def distinct_picks(rng, n_items: int, alpha: float, n: int, k: int):
    """``[n, k]`` catalog items, each row ``k`` distinct ones: the first
    ``k`` distinct values of ``PICK_DRAWS`` Zipf draws, in draw order; a
    row with fewer draws again."""
    out = np.empty((n, k), np.int64)
    todo = np.arange(n)
    while len(todo):
        d = traffic_mod.zipf_rows(
            rng, n_items, alpha, np.zeros(len(todo) * PICK_DRAWS, np.int64),
            0.0).reshape(len(todo), PICK_DRAWS)
        order = np.argsort(d, axis=1, kind="stable")
        s = np.take_along_axis(d, order, axis=1)
        head = np.ones(s.shape, bool)
        head[:, 1:] = s[:, 1:] != s[:, :-1]
        first = np.empty_like(head)
        np.put_along_axis(first, order, head, axis=1)
        rank = np.cumsum(first, axis=1)
        ok = rank[:, -1] >= k
        out[todo[ok]] = d[ok][(first & (rank <= k))[ok]].reshape(-1, k)
        todo = todo[~ok]
    return out


def candidates(cfg: dict, seed: int, n_queries: int):
    """The run's candidate model: ``(catalog [catalog_items, lookups]
    int32`` (each item's bags, item tables in order), ``picks [n_queries,
    item_batch]`` int64, ``dense [n_queries x item_batch, num_dense]``
    float32)."""
    words = traffic_mod.seed_words(seed)
    it = cfg["item_tables"]
    n_items = cfg["catalog_items"]
    rng = np.random.default_rng(words + [CATALOG_SALT])
    catalog = np.concatenate([
        traffic_mod.zipf_rows(rng, int(rows), float(alpha),
                              np.zeros(n_items * pool, np.int64), 0.0)
        .reshape(n_items, pool).astype(np.int32)
        for rows, pool, alpha in zip(it["rows"], it["pooling"],
                                     it["zipf_alpha"])], axis=1)
    picks = distinct_picks(np.random.default_rng(words + [PICKS_SALT]),
                           n_items, cfg["candidate_zipf_alpha"], n_queries,
                           cfg["item_batch"])
    dense = np.random.default_rng(words + [DENSE_SALT]).standard_normal(
        (n_queries * cfg["item_batch"], cfg["num_dense"]), np.float32)
    return catalog, picks, dense


def inputs(cfg: dict, tr, seed: int):
    """Per chunk ``(user chunk, item chunk, dense features)``: the user
    chunk is the traffic's, as ``user_bags`` serves it; the item chunk has
    one query per candidate over the item tables, its values int32."""
    from repro.core.columnar import ColumnarQueries
    cq = ColumnarQueries(tr.values, tr.seg_offsets, tr.seg_table,
                         tr.query_seg)
    catalog, picks, dense = candidates(cfg, seed, tr.n_queries)
    B, Bi = tr.chunk, cfg["item_batch"]
    pools = np.asarray(cfg["item_tables"]["pooling"], np.int64)
    N, Ti = B * Bi, len(pools)
    # every item chunk has the same CSR layout; only its values differ
    seg_offsets = np.concatenate([[0], np.cumsum(np.tile(pools, N))])
    seg_table = np.tile(np.arange(Ti, dtype=np.int64), N)
    query_seg = np.arange(0, N * Ti + 1, Ti, dtype=np.int64)
    return [(cq.chunk(s, s + B, B),
             ColumnarQueries(catalog[picks[s:s + B]].reshape(-1),
                             seg_offsets, seg_table, query_seg).whole(),
             dense[s * Bi:(s + B) * Bi])
            for s in range(0, tr.n_queries, B)]


def shape(tr, k: int) -> int:
    """The padded pooling ``P`` of chunk ``k``'s user block. Its item
    block's ``P`` is the same in every chunk (every candidate has a full
    bag of each item table's pooling), so it adds no compile shape."""
    B = tr.chunk
    return traffic_mod.padded_pooling(tr, k * B, k * B + B)


def serve(engine, x):
    scores, logits, _, io = engine.rank_columnar(*x)
    return (scores, logits), io


def counters(engine) -> dict:
    """The row cache's hits and misses so far (the user side's)."""
    return {"hits": int(engine.state["hits"]),
            "misses": int(engine.state["misses"])}


def attach(engine, telemetry) -> None:
    engine.telemetry = telemetry


def reference_logits(cfg, tr, seed, ks, precisions=("highest",)):
    """For each window chunk of ``ks``, the reference's logits ``[B x
    item_batch]`` at each of ``precisions`` (``reference_rank.forward``)."""
    catalog, picks, dense = candidates(cfg, seed, tr.n_queries)
    weights = reference_rank.dense_weights(
        traffic_mod.seed_words(seed) + [WEIGHTS_SALT], cfg)
    B, Bi = tr.chunk, cfg["item_batch"]
    first = tr.warmup // B
    for k in ks:
        q0 = (first + k) * B
        items, inv = np.unique(picks[q0:q0 + B], return_inverse=True)
        item = reference_rank.item_bags(cfg, seed, catalog, items)
        user = reference_rank.user_bags(cfg, seed, tr, q0, q0 + B)
        yield [reference_rank.forward(
            weights, dense[q0 * Bi:(q0 + B) * Bi],
            np.repeat(user, Bi, axis=0), item[inv.ravel()], p)
            for p in precisions]


def check(cfg, tr, seed, served, prog_reads, sample):
    """Compare what was served with the reference: each query's SCM reads
    (exact), and the logits and scores of the sampled chunks' candidates.
    Returns the checks and the per-serving counts of useful work."""
    want, counts = reference.expected_reads(cfg, tr, served)
    mismatch = sum(int(np.sum(np.asarray(p) != w))
                   for p, w in zip(prog_reads, want))
    Bi, Ti = cfg["item_batch"], cfg["num_item_tables"]
    lookups = int(np.sum(cfg["item_tables"]["pooling"]))
    for (q0, q1), c in zip(served, counts):
        n = (q1 - q0) * Bi
        c.update(items=n, item_bags=n * Ti, item_lookups=n * lookups)
    gap = 0.0
    for (_, (scores, logits)), (ref,) in zip(
            sample, reference_logits(cfg, tr, seed, [k for k, _ in sample])):
        # a score lies within a quarter of its logit's gap of the
        # reference's sigmoid, so this catches a wrong sigmoid alone
        want = 1.0 / (1.0 + np.exp(-ref.astype(np.float64)))
        gap = max(gap, float(np.abs(np.asarray(logits).ravel() - ref).max()),
                  float(np.abs(np.asarray(scores).ravel() - want).max()))
    return ({"logit_gap": (gap, reference_rank.LOGIT_GAP_LIMIT),
             "sm_ios_mismatch": (mismatch, reference.SM_IOS_MISMATCH_LIMIT)},
            counts)


step_work = work_rank.step
