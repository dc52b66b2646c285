"""The ranking cell: its configuration resolves to the ``rank`` served
path, the configuration's item tables are the inventory draw's, a tiny
ranking cell runs traced on the CPU with its readers, the readers of the
device scopes read a hand-made trace, and a wrong logit fails ``correct``."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from chip_bench_testlib import CHIP, DATA, ROOT, on_cpu
import enginetrace
from repro.obs import make_telemetry
from repro.obs import tracing as names

TINY_RANK = os.path.join(DATA, "configs", "tiny_rank.json")
SEED = 2 ** 33 + 11
RANK_METRICS = ["item_ms_per_chunk.rank", "dense_ms_per_chunk.rank",
                "item_gather_roofline.rank", "dense_mfu.rank",
                "item_pad_share.rank", "item_pack_ms_per_chunk.rank"]


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_rank_cell_resolves(bench):
    import run
    c = run.resolve(bench, "m1rank.backlog")
    assert c.cfg["served"] == "rank"
    assert c.served.__file__ == os.path.join(CHIP, "served", "rank.py")
    assert {m["name"] for m in c.e2e} == {"queries_per_s", "setup_s"}
    assert [m["name"] for m in c.layer] == RANK_METRICS
    assert all(callable(run.reader(m)) for m in RANK_METRICS)
    assert c.mix["arrival"]["process"] == "backlog"


def test_rank_configuration_is_table6_m1_whole(bench):
    """The published counts hold; the user side is dlrm-m1's; the item
    tables are the 30 item metas of the draw the user tables come from."""
    from repro.core.locality import sample_table_metas
    import run
    rank = run.resolve(bench, "m1rank.backlog").cfg
    m1 = run.resolve(bench, "m1.steady").cfg
    for k in ("tables", "dim", "hbm_cache_bytes", "cache_sets",
              "cache_ways", "chunk_queries", "sm_device"):
        assert rank[k] == m1[k], k
    assert (rank["num_item_tables"], rank["item_batch"],
            rank["num_mlp_layers"]) == (30, 50, 31)
    assert (len(rank["bottom_mlp"]) + len(rank["top_mlp"])
            == rank["num_mlp_layers"])
    metas = sample_table_metas(
        np.random.default_rng(np.random.SeedSequence([0, 7, 0])),
        num_user=61, num_item=30, user_dim_bytes=(90, 172),
        item_dim_bytes=(90, 172), user_pool=42, item_pool=9,
        total_bytes=1.5e9)
    item = [m for m in metas if m.kind == "item"]
    it = rank["item_tables"]
    assert it["rows"] == [m.num_rows for m in item]
    assert it["pooling"] == [m.pooling_factor for m in item]
    assert it["zipf_alpha"] == [round(m.zipf_alpha, 6) for m in item]
    assert sum(it["rows"]) == 3_719_186


def test_dense_weights_copy_the_program_formula():
    """The reference's weights are drawn by its own copy of
    ``init_dense_half``'s formula, and equal the program's bit for bit."""
    import reference_rank
    from repro.models import dlrm
    with open(TINY_RANK) as f:
        cfg = json.load(f)
    ref = reference_rank.dense_weights([5, 0, 4], cfg)
    prog = dlrm.init_dense_half([5, 0, 4], cfg["num_dense"],
                                cfg["bottom_mlp"], cfg["top_mlp"], 5)
    for part in ("bottom", "top"):
        assert len(ref[part]) == len(prog[part])
        for a, b in zip(ref[part], prog[part]):
            np.testing.assert_array_equal(a["w"], b["w"])
            np.testing.assert_array_equal(a["b"], b["b"])


def test_candidates_are_distinct_and_seeded():
    import run
    rank = run.served_module("rank")
    with open(TINY_RANK) as f:
        cfg = json.load(f)
    catalog, picks, dense = rank.candidates(cfg, SEED, 12)
    assert catalog.dtype == np.int32 and catalog.shape == (64, 5)
    assert (catalog[:, :3] < 400).all() and (catalog[:, 3:] < 200).all()
    assert picks.shape == (12, 3) and dense.shape == (36, 13)
    assert all(len(set(row)) == 3 for row in picks.tolist())
    again = rank.candidates(cfg, SEED, 12)
    for a, b in zip((catalog, picks, dense), again):
        np.testing.assert_array_equal(a, b)


def tiny_rank_bench(config_file=TINY_RANK):
    return {
        "configs": [{"name": "tiny_rank", "file": config_file}],
        "workloads": [{"name": "tiny_rank.backlog", "config": "tiny_rank",
                       "traffic": "backlog", "chips": 1}],
        "end_to_end": [{"name": "queries_per_s", "unit": "queries/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": n, "unit": "x", "moves": "queries_per_s",
                       "workloads": ["tiny_rank.backlog"]}
                      for n in RANK_METRICS],
    }


def test_tiny_rank_cell_runs_traced(monkeypatch):
    """A traced run of a tiny ranking cell on the CPU: correct, and its
    line carries the host-span and counter readers, the item padded share
    equal to the reference's from the configuration exactly. The CPU's
    trace has no device plane, so the scope readers read nothing."""
    run = on_cpu(monkeypatch)
    c = run.resolve(tiny_rank_bench(), "tiny_rank.backlog",
                    traffic_dir=os.path.join(DATA, "traffic"))
    m = run.measure(c, SEED, 0.5, True)
    out = run.report(c, m)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["logit_gap"]["value"] * 10 <= out["checks"][
        "logit_gap"]["limit"]
    assert out["checks"]["sm_ios_mismatch"]["value"] == 0
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(got) == {"item_pad_share.rank", "item_pack_ms_per_chunk.rank"}
    pools = c.cfg["item_tables"]["pooling"]
    P = 1 << (max(pools) - 1).bit_length()
    assert got["item_pad_share.rank"] == 100.0 * (
        1.0 - sum(pools) / (len(pools) * P))
    assert got["item_pack_ms_per_chunk.rank"] > 0
    counters = m.telemetry.registry.counters
    assert counters["engine.items_scored"] == (
        len(m.served_k) * c.cfg["chunk_queries"] * c.cfg["item_batch"])


@pytest.mark.parametrize("which", [0, 1], ids=["score", "logit"])
def test_wrong_logit_is_not_correct(monkeypatch, which):
    """A logit, or a score alone (a wrong sigmoid), off by 2e-3."""
    run = on_cpu(monkeypatch)
    c = run.resolve(tiny_rank_bench(), "tiny_rank.backlog",
                    traffic_dir=os.path.join(DATA, "traffic"))
    serve = c.served.serve

    def off(engine, x):
        out, io = serve(engine, x)
        out = list(out)
        out[which] = out[which] + 2e-3
        return tuple(out), io
    monkeypatch.setattr(c.served, "serve", off)
    out = run.report(c, run.measure(c, SEED, 0.5, False))
    chk = out["checks"]["logit_gap"]
    assert out["correct"] is False and chk["value"] > chk["limit"]


DEV = "/device:TPU:0"
MS = 1_000_000      # ns


def test_rank_readers_on_hand_made_trace():
    """Two chunks whose item and dense ops take known device time; the
    roofline and peak shares follow from the work functions."""
    import work_rank
    ops = [[DEV, "gather_pool.2", 0, 4 * MS], [DEV, "fusion.7", 4 * MS, MS],
           [DEV, "dot.3", 5 * MS, MS], [DEV, "fusion.9", 6 * MS, MS],
           [DEV, "gather_pool.1", 7 * MS, MS]]
    spans = [["bench.window", 0, 20 * MS], ["bench.serve_chunk", 0, 10 * MS],
             ["bench.serve_chunk", 10 * MS, 10 * MS],
             [names.SPAN_SERVE, 0, 10 * MS], [names.SPAN_SERVE, 10 * MS,
                                                 10 * MS],
             [names.SPAN_ITEM_PACK, MS, 3 * MS],
             [names.SPAN_ITEM_PACK, 11 * MS, MS]]
    ex = {"ops": ops, "modules": [[DEV, "jit_rank_step", 0, 8 * MS]],
          "spans": spans, "labels": {},
          "scopes": {"gather_pool.2": names.SCOPE_ITEM,
                     "fusion.7": names.SCOPE_ITEM,
                     "dot.3": names.SCOPE_DENSE,
                     "fusion.9": names.SCOPE_DENSE,
                     "gather_pool.1": names.SCOPE_GATHER}}
    with open(TINY_RANK) as f:
        cfg = json.load(f)
    counts = [dict(items=12, item_bags=24, item_lookups=60)] * 2
    tel = make_telemetry(True)
    tel.registry.inc("engine.item_positions", 96)
    tel.registry.inc("engine.item_valid_positions", 60)
    peak = {"hbm_bytes_per_s": 1e9, "flops_per_s": 1e12}
    run = SimpleNamespace(trace=enginetrace.EngineSummary(ex), telemetry=tel,
                          cfg=cfg, counts=counts, peak=peak)
    import run as bench_run
    got = {m: bench_run.reader(m)(run) for m in RANK_METRICS}
    item_bytes = 2 * work_rank.item_gather(cfg, counts[0])[0]
    dense_flops = 2 * work_rank.dense_half(cfg, counts[0])[1]
    assert got == pytest.approx({
        "item_ms_per_chunk.rank": 2.5, "dense_ms_per_chunk.rank": 1.0,
        "item_gather_roofline.rank": 100 * item_bytes / 1e9 / 5e-3,
        "dense_mfu.rank": 100 * dense_flops / 1e12 / 2e-3,
        "item_pad_share.rank": 100 * (1 - 60 / 96),
        "item_pack_ms_per_chunk.rank": 2.0})
    silent = SimpleNamespace(trace=None, telemetry=None, cfg=cfg,
                             counts=counts, peak=peak)
    plain = SimpleNamespace(trace=enginetrace.EngineSummary(
        dict(ex, scopes={}, spans=[sp for sp in spans
                                   if sp[0].startswith("bench.")])),
        telemetry=None, cfg=cfg,
        counts=counts, peak=peak)
    for m in RANK_METRICS:
        assert bench_run.reader(m)(silent) is None, m
        assert bench_run.reader(m)(plain) is None, m


def test_dense_flops_at_table6_widths(bench):
    """The dense half of one candidate at Table 6 M1's widths: both MLPs
    and the 4,186 pairs of 92 features over 128 elements."""
    import run
    import work_rank
    cfg = run.resolve(bench, "m1rank.backlog").cfg
    mlp = (13 * 300 + 300 * 300 + 300 * 128 + 4314 * 300
           + 26 * 300 * 300 + 300)
    assert work_rank.dense_flops_per_item(cfg) == 2 * (mlp + 4186 * 128)
