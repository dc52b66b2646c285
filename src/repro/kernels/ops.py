"""Jit'd public wrappers for the Pallas kernels.

Handle TPU lane alignment (row widths padded to multiples of 128; stores
are allocated with :func:`aligned_rows` rows), run the kernels compiled on a
TPU and in interpret mode on the CPU (the tests), refuse any other backend,
and expose numerically-identical jnp fallbacks (ref.py) for XLA-only paths
like the multi-pod dry-run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.cache_probe import cache_probe as _cache_probe_kernel
from repro.kernels.flash_decode import flash_decode as _flash_decode_kernel
from repro.kernels.gather_pool import ROW_GROUP
from repro.kernels.gather_pool import gather_pool as _gather_pool_kernel

LANE = 128


def _interpret() -> bool:
    """Interpret the kernels on the CPU, compile them on a TPU; no other
    backend runs them, so nothing falls back to the interpreter unseen."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas TPU kernels run compiled on 'tpu' or interpreted on "
            f"'cpu', not on {backend!r}")
    return backend == "cpu"


def aligned_rows(n: int) -> int:
    """Rows to allocate for a store of ``n`` rows: the gather kernel reads
    rows in aligned groups of ``ROW_GROUP``."""
    return -(-n // ROW_GROUP) * ROW_GROUP


def _pad_lanes(x: jax.Array, axis: int = -1):
    d = x.shape[axis]
    pad = (-d) % LANE
    if pad == 0:
        return x, d
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), d


def embedding_gather_pool(payload: jax.Array, scale: jax.Array, bias: jax.Array,
                          indices: jax.Array, *, use_kernel: bool = True) -> jax.Array:
    """Fused lookup+dequant+pool. payload [R, D] int8/uint8 with R a
    multiple of ``ROW_GROUP`` (see :func:`aligned_rows`); indices [N, P]."""
    if not use_kernel:
        return ref.gather_pool_ref(payload, scale, bias, indices)
    padded, D = _pad_lanes(payload)
    out = _gather_pool_kernel(padded, scale, bias, indices,
                              interpret=_interpret())
    return out[:, :D]


def row_cache_probe(tag_table, tag_row, data, q_table, q_row, sets, *,
                    use_kernel: bool = True):
    """Set-associative cache probe: (values [N, D], hit [N])."""
    if not use_kernel:
        return ref.cache_probe_ref(tag_table, tag_row, data, q_table, q_row, sets)
    padded, D = _pad_lanes(data)
    vals, hit = _cache_probe_kernel(tag_table, tag_row, padded, q_table, q_row,
                                    sets, interpret=_interpret())
    return vals[:, :D], hit


def decode_attention(q, k, v, kv_len, *, block_s: int = 512,
                     use_kernel: bool = True):
    """Flash decode attention: q [B,H,hd] vs cache k/v [B,S,K,hd]."""
    if not use_kernel or k.shape[1] % block_s != 0:
        return ref.flash_decode_ref(q, k, v, kv_len)
    return _flash_decode_kernel(q, k, v, kv_len, block_s=block_s,
                                interpret=_interpret())
