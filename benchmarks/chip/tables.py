"""Embedding tables of the chip benchmark, made from the run's seed.

Every element comes from a counter-based hash of ``(seed, global row,
column)``, so the same function gives the tables on the device (one jitted
call, for the program) and any single row on the host (for the reference),
with no table ever copied between them.

The values lie on the 8-bit grid of their row, as the rows of a model
trained for 8-bit serving do: row ``r`` holds ``lo_r + q * s_r`` with ``q``
a whole number in ``[0, 255]``, and every row has one ``q = 0`` (column 0)
and one ``q = 255`` (column 1). Row-wise 8-bit quantization of such a row
recovers each ``q`` exactly, however its arithmetic rounds, so no ``q``
sits on a rounding tie between two implementations. ``s_r`` is drawn in
``[0.5, 1.5) * SCALE``, so an element's standard deviation is about 0.02,
the scale of ``repro.models.layers.embed_init`` that ``chip_smoke.py`` uses.
"""
from __future__ import annotations

import numpy as np

LEVELS = 255
SCALE = 2.7e-4          # 0.02 * sqrt(12) / 255: std 0.02 for uniform q


def _fmix(xp, h):
    """murmur3's 32-bit finalizer on uint32 arrays of numpy or jax.numpy."""
    h = h ^ (h >> 16)
    h = h * xp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * xp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def seed_keys(seed: int) -> np.ndarray:
    """The run seed (any whole number) as two uint32 keys."""
    s = int(seed) % (1 << 64)
    return np.array([s & 0xFFFFFFFF, s >> 32], np.uint32)


def row_values(xp, keys, grow, dim: int):
    """Float32 rows ``[len(grow), dim]`` for global row ids ``grow``, in
    numpy (``xp=np``) or jax.numpy (``xp=jnp``); ``keys`` from
    :func:`seed_keys`."""
    r = _fmix(xp, grow.astype(xp.uint32) ^ keys[0])
    r = _fmix(xp, r + keys[1])                              # [n]
    col = xp.arange(dim, dtype=xp.uint32)[None, :]
    h = _fmix(xp, r[:, None] ^ (col * xp.uint32(0x9E3779B9)))
    q = (h >> 24).astype(xp.float32)
    q = xp.where(col == 0, xp.float32(0), q)
    q = xp.where(col == 1, xp.float32(LEVELS), q)
    u = (_fmix(xp, r ^ xp.uint32(0x68E31DA4)) >> 8).astype(xp.float32)
    s = xp.float32(SCALE) * (xp.float32(0.5) + u * xp.float32(2.0 ** -24))
    lo = xp.float32(-0.5 * LEVELS) * s
    return lo[:, None] + q * s[:, None]


def row_offsets(rows) -> np.ndarray:
    """Global id of each table's first row (tables in configuration order)."""
    return np.concatenate([[0], np.cumsum(rows)[:-1]]).astype(np.int64)


def device_tables(seed: int, rows, dim: int) -> dict:
    """``{table: [rows_t, dim] float32}`` made on the default device by one
    jitted call; the dict's order is the configuration's table order."""
    import jax
    import jax.numpy as jnp

    offs = [int(o) for o in row_offsets(rows)]
    sizes = [int(n) for n in rows]

    @jax.jit
    def make(keys):     # the seed is an argument: one program for all seeds
        return [row_values(jnp, keys, jnp.arange(n, dtype=jnp.uint32)
                           + jnp.uint32(o), dim)
                for o, n in zip(offs, sizes)]

    return dict(enumerate(make(jnp.asarray(seed_keys(seed)))))
