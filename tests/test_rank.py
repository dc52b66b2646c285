"""Ranking on the device engine (``rank_columnar``, paper §2.2 and Eq. 2):
the logits against a plain numpy forward written here, the user side
served exactly as ``serve_columnar`` serves it, the item counters, and the
limit that a one-pass bf16 dense half fails."""
import numpy as np
import pytest

from repro.core.columnar import ColumnarQueries
from repro.core.io_sim import DEVICES
from repro.models import dlrm
from repro.runtime.engine import DeviceServingEngine, EngineConfig

DIM = 128
USER_ROWS, ITEM_ROWS = (40, 64, 24), (30, 50)
USER_POOL, ITEM_POOL = (5, 3, 7), (2, 4)
B, ITEM_BATCH = 3, 4
NUM_DENSE = 13
# Table 6 M1's dense half (dlrm-m1-rank.json): 13 -> 300 -> 300 -> 128,
# then 27 layers of 300 and the logit
BOTTOM, TOP = (300, 300, 128), (300,) * 27 + (1,)
# the logit gap the benchmark's reference allows (reference_rank.py)
LOGIT_GAP_LIMIT = 1e-3


def grid_tables(rng, rows):
    """Tables on the 8-bit grid of each row (one q = 0 and one q = 255 per
    row), so any row-wise 8-bit quantization holds them exactly."""
    out = []
    for n in rows:
        q = rng.integers(0, 256, (n, DIM)).astype(np.float32)
        q[:, 0], q[:, 1] = 0, 255
        s = rng.uniform(0.5, 1.5, (n, 1)).astype(np.float32) * np.float32(
            0.02 * np.sqrt(12) / 255)
        out.append((-127.5 * s + q * s).astype(np.float32))
    return out


def bags(rng, n, rows, pools):
    return [{t: rng.integers(0, rows[t], pools[t]) for t in range(len(rows))}
            for _ in range(n)]


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(16)
    user = dict(enumerate(grid_tables(rng, USER_ROWS)))
    item = dict(enumerate(grid_tables(rng, ITEM_ROWS)))
    weights = dlrm.init_dense_half(
        [16, 1], NUM_DENSE, BOTTOM, TOP, len(USER_ROWS) + len(ITEM_ROWS))
    ureqs = bags(rng, B, USER_ROWS, USER_POOL)
    ireqs = bags(rng, B * ITEM_BATCH, ITEM_ROWS, ITEM_POOL)
    dense = rng.standard_normal((B * ITEM_BATCH, NUM_DENSE)).astype(
        np.float32)
    return user, item, weights, ureqs, ireqs, dense


def make_engine(setup, rank=True):
    user, item, weights, *_ = setup
    extra = dict(item_tables=item, dense=weights) if rank else {}
    return DeviceServingEngine(user, DEVICES["nand_flash"],
                               EngineConfig(hbm_cache_bytes=1 << 16), **extra)


def chunks(setup):
    *_, ureqs, ireqs, dense = setup
    return (ColumnarQueries.from_requests(ureqs).whole(),
            ColumnarQueries.from_requests(ireqs).whole(), dense)


def numpy_logits(setup, bf16=False):
    """The forward in plain numpy, float64; with ``bf16`` every matmul's
    operands are rounded to bfloat16 first (one-pass bf16, as a TPU runs
    a float32 matmul at default precision)."""
    user, item, weights, ureqs, ireqs, dense = setup

    def rnd(x):
        if not bf16:
            return x
        b = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
        b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000     # to nearest even
        return b.astype(np.uint32).view(np.float32).astype(np.float64)

    def mm(a, w):
        return rnd(a) @ rnd(w)

    def pool(tabs, reqs):
        return np.stack([np.stack([tabs[t][v].astype(np.float64).sum(0)
                                   for t, v in sorted(r.items())])
                         for r in reqs])

    u = np.repeat(pool(user, ureqs), ITEM_BATCH, axis=0)
    emb = np.concatenate([u, pool(item, ireqs)], axis=1)
    x = dense.astype(np.float64)
    for layer in weights["bottom"]:
        x = np.maximum(mm(x, layer["w"]) + layer["b"], 0)
    feats = np.concatenate([x[:, None], emb], axis=1)
    gram = np.einsum("nfe,nge->nfg", rnd(feats), rnd(feats))
    iu, ju = np.triu_indices(feats.shape[1], k=1)
    x = np.concatenate([x, gram[:, iu, ju]], axis=1)
    top = weights["top"]
    for i, layer in enumerate(top):
        x = mm(x, layer["w"]) + layer["b"]
        if i < len(top) - 1:
            x = np.maximum(x, 0)
    return x[:, 0].reshape(B, ITEM_BATCH)


@pytest.fixture(scope="module")
def ranked(setup):
    eng = make_engine(setup)
    return eng, eng.rank_columnar(*chunks(setup))


def test_logits_match_numpy_forward(setup, ranked):
    _, (scores, logits, _, _) = ranked
    want = numpy_logits(setup)
    assert logits.shape == scores.shape == (B, ITEM_BATCH)
    gap = np.abs(logits - want).max()
    assert gap * 10 <= LOGIT_GAP_LIMIT, gap
    np.testing.assert_allclose(scores, 1 / (1 + np.exp(-want)), atol=1e-6)
    # the logits are not all alike: the check has something to compare
    assert np.ptp(want) > 0.1


def test_one_pass_bf16_dense_half_fails_the_limit(setup):
    gap = np.abs(numpy_logits(setup, bf16=True) - numpy_logits(setup)).max()
    assert gap > LOGIT_GAP_LIMIT, gap


def test_user_side_served_once_as_serve_columnar(setup, ranked):
    """Eq. 2: the user side is served once per query, and the item side
    adds no SM read: reads, their latency and the row cache's state equal
    ``serve_columnar``'s on the same user chunk."""
    eng, (_, _, sm_time, sm_ios) = ranked
    plain = make_engine(setup, rank=False)
    uchunk, _, _ = chunks(setup)
    pooled, want_time, want_ios = plain.serve_columnar(uchunk)
    np.testing.assert_array_equal(sm_ios, want_ios)
    np.testing.assert_array_equal(sm_time, want_time)
    assert sm_ios.sum() > 0
    for k in plain.state:
        np.testing.assert_array_equal(eng.state[k], plain.state[k])
    assert eng.stats.sm_ios == plain.stats.sm_ios
    assert eng.io.total_ios == plain.io.total_ios


def test_item_counters_count_the_item_block(setup):
    from repro.obs import make_telemetry
    from repro.runtime.engine import dense_from_chunk
    eng = make_engine(setup)
    eng.telemetry = make_telemetry(True)
    uchunk, ichunk, dense = chunks(setup)
    for _ in range(2):
        eng.rank_columnar(uchunk, ichunk, dense)
    iidx, ivalid = dense_from_chunk(ichunk, eng.item_slot, len(ITEM_ROWS))
    idx, valid = dense_from_chunk(uchunk, eng.table_slot, len(USER_ROWS))
    c = eng.telemetry.registry.counters
    assert c["engine.item_positions"] == 2 * ivalid.size
    assert c["engine.item_valid_positions"] == 2 * B * ITEM_BATCH * sum(
        ITEM_POOL) == 2 * ivalid.sum()
    assert c["engine.items_scored"] == 2 * B * ITEM_BATCH
    assert c["engine.positions"] == 2 * valid.size
    assert c["engine.valid_positions"] == 2 * valid.sum()


def test_rank_step_is_one_program_with_its_regions(setup):
    """One jitted program holds the user step's regions, the item
    gather-pool and the dense half, each under its scope."""
    from repro.obs import tracing as names
    from repro.runtime.engine import dense_from_chunk
    eng = make_engine(setup)
    uchunk, ichunk, dense = chunks(setup)
    idx, valid = dense_from_chunk(uchunk, eng.table_slot, len(USER_ROWS))
    iidx, ivalid = dense_from_chunk(ichunk, eng.item_slot, len(ITEM_ROWS))
    text = eng._rank_step.lower(
        *eng._rank_args(idx, valid, iidx, ivalid, dense)).as_text(
        debug_info=True)
    for scope in names.ENGINE_SCOPES + names.CACHE_SCOPES:
        assert f"/{scope}/" in text, scope
    assert f'"jit(rank_step)/{names.SCOPE_ITEM}/' in text
    assert f'"jit(rank_step)/{names.SCOPE_DENSE}/' in text
    assert eng._rank_step.__wrapped__.__name__ == "rank_step"


def test_user_only_engine_builds_no_item_side(setup):
    user, item, weights, *_ = setup
    eng = make_engine(setup, rank=False)
    assert eng._rank_step is None
    with pytest.raises(ValueError):
        eng.rank_columnar(*chunks(setup))
    with pytest.raises(ValueError):
        DeviceServingEngine(user, DEVICES["nand_flash"], item_tables=item)


def test_rank_rejects_a_wrong_candidate_block(setup):
    eng = make_engine(setup)
    uchunk, ichunk, dense = chunks(setup)
    with pytest.raises(ValueError):
        eng.rank_columnar(uchunk, ichunk, dense[:-1])
    short = ColumnarQueries.from_requests(setup[4][:-1]).whole()
    with pytest.raises(ValueError):
        eng.rank_columnar(uchunk, short, dense[:-1])
    with pytest.raises(ValueError):             # no query to rank
        eng.rank_columnar(ColumnarQueries.from_requests([]).whole(),
                          ichunk, dense)


def test_score_items_uses_the_dense_half():
    """``score_items`` (the trainable model's serving form) and the
    engine's dense half are one implementation."""
    import jax
    import jax.numpy as jnp
    arch = dlrm.DLRMArch(user_tables=(20,) * 2, item_tables=(20,) * 2,
                         embed_dim=8, bottom_mlp=(16, 8), top_mlp=(16, 1))
    params = dlrm.init_params(arch, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    user_emb = jnp.asarray(rng.standard_normal((2, 8)), jnp.float32)
    item_idx = jnp.asarray(rng.integers(0, 20, (2, 3, 4)))
    dense = jnp.asarray(rng.standard_normal((3, 13)), jnp.float32)
    got = dlrm.score_items(params, user_emb, item_idx, dense, arch)
    item_emb = dlrm.embed_bags(params["tables"][2:], item_idx)
    emb = jnp.concatenate([jnp.broadcast_to(user_emb, (3, 2, 8)), item_emb],
                          axis=1)
    np.testing.assert_allclose(
        got, jax.nn.sigmoid(dlrm.dense_logits(params, dense, emb)),
        rtol=1e-6)
