"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis,
interpret=True on CPU (TPU is the compile target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hyp_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.cache_probe import cache_probe
from repro.kernels.flash_decode import flash_decode
from repro.kernels.gather_pool import gather_pool

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# gather_pool
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("R,D,N,P", [
    (16, 8, 1, 1), (64, 128, 8, 5), (128, 96, 4, 20), (1000, 64, 16, 3),
])
@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.int8])
def test_gather_pool_shapes(R, D, N, P, dtype):
    lo, hi = (0, 255) if dtype == jnp.uint8 else (-127, 127)
    payload = jnp.asarray(RNG.integers(lo, hi, (R, D)), dtype)
    scale = jnp.asarray(RNG.random(R), jnp.float32) * 0.1
    bias = jnp.asarray(RNG.standard_normal(R), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, R, (N, P)), jnp.int32)
    out = gather_pool(payload, scale, bias, idx, interpret=True)
    expect = ref.gather_pool_ref(payload, scale, bias, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-4)


def test_gather_pool_duplicate_indices():
    payload = jnp.asarray(RNG.integers(0, 255, (8, 16)), jnp.uint8)
    scale = jnp.ones(8, jnp.float32)
    bias = jnp.zeros(8, jnp.float32)
    idx = jnp.asarray([[3, 3, 3, 3]], jnp.int32)
    out = gather_pool(payload, scale, bias, idx, interpret=True)
    np.testing.assert_allclose(np.asarray(out)[0],
                               4.0 * np.asarray(payload)[3], rtol=1e-6)


@given(st.integers(1, 6), st.integers(1, 12))
@settings(max_examples=20, deadline=None)
def test_gather_pool_property(n, p):
    payload = jnp.asarray(RNG.integers(0, 255, (32, 24)), jnp.uint8)
    scale = jnp.asarray(RNG.random(32), jnp.float32)
    bias = jnp.asarray(RNG.standard_normal(32), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, 32, (n, p)), jnp.int32)
    out = gather_pool(payload, scale, bias, idx, interpret=True)
    expect = ref.gather_pool_ref(payload, scale, bias, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-4)


def test_ops_wrapper_pads_lanes():
    # D=96 not a multiple of 128: wrapper pads payload and unpads output
    payload = jnp.asarray(RNG.integers(0, 255, (32, 96)), jnp.uint8)
    scale = jnp.asarray(RNG.random(32), jnp.float32)
    bias = jnp.zeros(32, jnp.float32)
    idx = jnp.asarray(RNG.integers(0, 32, (4, 6)), jnp.int32)
    out = ops.embedding_gather_pool(payload, scale, bias, idx)
    assert out.shape == (4, 96)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.gather_pool_ref(payload, scale, bias, idx)),
        rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# cache_probe
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,W,D,N", [(4, 2, 8, 4), (16, 4, 64, 16), (64, 8, 128, 9)])
def test_cache_probe_shapes(S, W, D, N):
    tt = jnp.asarray(RNG.integers(0, 4, (S, W)), jnp.int32)
    tr = jnp.asarray(RNG.integers(0, 64, (S, W)), jnp.int32)
    data = jnp.asarray(RNG.standard_normal((S, W, D)), jnp.float32)
    qt = jnp.asarray(RNG.integers(0, 4, (N,)), jnp.int32)
    qr = jnp.asarray(RNG.integers(0, 64, (N,)), jnp.int32)
    sets = jnp.asarray(RNG.integers(0, S, (N,)), jnp.int32)
    v, h = cache_probe(tt, tr, data, qt, qr, sets, interpret=True)
    ve, he = ref.cache_probe_ref(tt, tr, data, qt, qr, sets)
    np.testing.assert_allclose(np.asarray(v), np.asarray(ve), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(he))


def test_cache_probe_guaranteed_hit_and_miss():
    tt = jnp.full((2, 2), -1, jnp.int32).at[1, 0].set(7)
    tr = jnp.full((2, 2), -1, jnp.int32).at[1, 0].set(42)
    data = jnp.arange(2 * 2 * 4, dtype=jnp.float32).reshape(2, 2, 4)
    v, h = cache_probe(tt, tr, data,
                       jnp.array([7, 7], jnp.int32),
                       jnp.array([42, 43], jnp.int32),
                       jnp.array([1, 1], jnp.int32), interpret=True)
    assert int(h[0]) == 1 and int(h[1]) == 0
    np.testing.assert_allclose(np.asarray(v[0]), np.asarray(data[1, 0]))
    np.testing.assert_allclose(np.asarray(v[1]), 0.0)


# ---------------------------------------------------------------------------
# flash_decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,K,hd,S,blk", [
    (1, 4, 4, 32, 512, 128),   # MHA
    (2, 8, 2, 64, 1024, 256),  # GQA 4:1
    (2, 16, 1, 128, 512, 256),  # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_shapes(B, H, K, hd, S, blk, dtype):
    q = jnp.asarray(RNG.standard_normal((B, H, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, S, K, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, S, K, hd)), dtype)
    kl = jnp.asarray(RNG.integers(1, S + 1, (B,)), jnp.int32)
    out = flash_decode(q, k, v, kl, block_s=blk, interpret=True)
    expect = ref.flash_decode_ref(q, k, v, kl)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


def test_flash_decode_respects_kv_len():
    B, H, K, hd, S = 1, 2, 2, 16, 256
    q = jnp.asarray(RNG.standard_normal((B, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, K, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, K, hd)), jnp.float32)
    out_10 = flash_decode(q, k, v, jnp.array([10], jnp.int32),
                          block_s=64, interpret=True)
    # zeroing the masked tail must not change the result
    k2 = k.at[:, 10:].set(99.0)
    v2 = v.at[:, 10:].set(-99.0)
    out_10b = flash_decode(q, k2, v2, jnp.array([10], jnp.int32),
                           block_s=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out_10), np.asarray(out_10b), rtol=1e-5)


def test_decode_attention_matches_model_attention():
    """flash_decode == the model's attention_core for a single query token."""
    from repro.models.layers import attention_core
    B, H, K, hd, S = 2, 8, 4, 32, 512
    q = jnp.asarray(RNG.standard_normal((B, 1, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, K, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, K, hd)), jnp.float32)
    kv_len = 300
    qpos = jnp.full((B, 1), kv_len - 1, jnp.int32)
    kpos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    valid = (kpos < kv_len)
    model_out = attention_core(q, k, v, qpos, kpos, causal=True, kv_valid=valid)
    kern_out = flash_decode(q[:, 0], k, v,
                            jnp.full((B,), kv_len, jnp.int32),
                            block_s=128, interpret=True)
    np.testing.assert_allclose(np.asarray(model_out[:, 0]), np.asarray(kern_out),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# backend guard
# ---------------------------------------------------------------------------


def test_ops_refuse_backends_other_than_cpu_and_tpu(monkeypatch):
    # interpret mode is for the CPU only: on any other backend the wrappers
    # raise instead of quietly interpreting the kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    payload = jnp.zeros((8, 128), jnp.uint8)
    ones = jnp.ones(8, jnp.float32)
    idx = jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(RuntimeError, match="gpu"):
        ops.embedding_gather_pool(payload, ones, ones, idx)
    tags = jnp.zeros((2, 2), jnp.int32)
    q = jnp.zeros((1,), jnp.int32)
    with pytest.raises(RuntimeError, match="gpu"):
        ops.row_cache_probe(tags, tags, jnp.zeros((2, 2, 128)), q, q, q)
    assert ops.embedding_gather_pool(payload, ones, ones, idx,
                                     use_kernel=False).shape == (1, 128)
