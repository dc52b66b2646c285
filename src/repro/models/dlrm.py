"""DLRM (paper Fig. 2): bottom MLP -> embeddings -> interaction -> top MLP.

Trainable JAX implementation used by the end-to-end example and tests. The
serving path swaps the plain-JAX embedding gather for the SDM store (user
tables on SM with the FM cache; item tables in FM) and the fused Pallas
``gather_pool`` kernel for dequant+pool.

Inference batching matches §2.2: user embeddings are looked up once per query
(B_U = 1) and broadcast across the item batch for the Top MLP (Eq. 2).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init, logical_constraint


@dataclasses.dataclass(frozen=True)
class DLRMArch:
    """Concrete trainable geometry (the paper's Table 6 entries are serving
    descriptions; this is the train/e2e-example form)."""
    num_dense: int = 13
    embed_dim: int = 64
    user_tables: Sequence[int] = (100_000,) * 8   # rows per user table
    item_tables: Sequence[int] = (100_000,) * 4   # rows per item table
    pooling: int = 8                               # indices per bag (fixed)
    bottom_mlp: Sequence[int] = (256, 128, 64)
    top_mlp: Sequence[int] = (256, 128, 1)

    @property
    def num_tables(self) -> int:
        return len(self.user_tables) + len(self.item_tables)

    @property
    def all_tables(self):
        return tuple(self.user_tables) + tuple(self.item_tables)

    def param_count(self) -> int:
        n = sum(r * self.embed_dim for r in self.all_tables)
        dims = [self.num_dense] + list(self.bottom_mlp)
        n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        f = self.num_tables + 1
        top_in = self.bottom_mlp[-1] + f * (f - 1) // 2
        dims = [top_in] + list(self.top_mlp)
        n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        return n


def _init_mlp(key, dims, dtype):
    ks = jax.random.split(key, len(dims) - 1)
    return [{"w": dense_init(ks[i], (dims[i], dims[i + 1]), dtype=dtype),
             "b": jnp.zeros((dims[i + 1],), dtype)} for i in range(len(dims) - 1)]


def _mlp(layers, x, final_act=False, precision=None):
    for i, l in enumerate(layers):
        x = jnp.matmul(x, l["w"], precision=precision) + l["b"]
        if i < len(layers) - 1 or final_act:
            x = jax.nn.relu(x)
    return x


def init_params(arch: DLRMArch, key, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3 + arch.num_tables)
    tables = [(jax.random.normal(ks[3 + i], (rows, arch.embed_dim)) /
               jnp.sqrt(arch.embed_dim)).astype(dtype)
              for i, rows in enumerate(arch.all_tables)]
    dims_b = [arch.num_dense] + list(arch.bottom_mlp)
    f = arch.num_tables + 1
    top_in = arch.bottom_mlp[-1] + f * (f - 1) // 2
    dims_t = [top_in] + list(arch.top_mlp)
    return {
        "bottom": _init_mlp(ks[0], dims_b, dtype),
        "top": _init_mlp(ks[1], dims_t, dtype),
        "tables": tables,
    }


def embed_bags(tables, indices: jax.Array) -> jax.Array:
    """indices: [T, B, P] -> pooled [B, T, E] (sum pooling, as SparseLengthsSum)."""
    pooled = []
    for t, table in enumerate(tables):
        rows = jnp.take(table, indices[t], axis=0)   # [B, P, E]
        pooled.append(jnp.sum(rows, axis=1))
    return jnp.stack(pooled, axis=1)                  # [B, T, E]


def interact(z0: jax.Array, emb: jax.Array, precision=None) -> jax.Array:
    """Dot-product interaction: z0 [B, E], emb [B, T, E] -> [B, E + T(T+1)/2]."""
    feats = jnp.concatenate([z0[:, None, :], emb], axis=1)   # [B, F, E]
    gram = jnp.einsum("bfe,bge->bfg", feats, feats, precision=precision)
    F = feats.shape[1]
    iu, ju = jnp.triu_indices(F, k=1)
    pairs = gram[:, iu, ju]                                   # [B, F(F-1)/2]
    return jnp.concatenate([z0, pairs], axis=1)


def dense_half_dims(num_dense: int, bottom: Sequence[int],
                    top: Sequence[int], num_bags: int):
    """Layer widths ``(bottom dims, top dims)`` of the dense half, inputs
    first: the bottom MLP maps ``num_dense`` features to the embedding
    width ``bottom[-1]``; the top MLP takes ``z0`` and the upper triangle of
    the Gram matrix of ``[z0, bags]`` (``interact``)."""
    f = num_bags + 1
    return ([num_dense, *bottom], [bottom[-1] + f * (f - 1) // 2, *top])


def init_dense_half(seed, num_dense: int, bottom: Sequence[int],
                    top: Sequence[int], num_bags: int) -> dict:
    """Float32 weights of the dense half, drawn on the host from ``seed``
    (anything ``np.random.default_rng`` takes): layer by layer, bottom
    first, ``w ~ N(0, 2 / fan_in)`` (He, for the ReLU layers) then
    ``b ~ N(0, 0.01^2)``."""
    rng = np.random.default_rng(seed)
    out = {}
    for part, dims in zip(("bottom", "top"), dense_half_dims(
            num_dense, bottom, top, num_bags)):
        out[part] = []
        for fi, fo in zip(dims[:-1], dims[1:]):
            w = rng.standard_normal((fi, fo), np.float32)
            w *= np.float32(math.sqrt(2.0 / fi))
            b = rng.standard_normal(fo, np.float32) * np.float32(0.01)
            out[part].append({"w": w, "b": b})
    return out


def dense_logits(params: dict, dense: jax.Array, emb: jax.Array,
                 precision=None) -> jax.Array:
    """The dense half: dense [N, num_dense] and pooled bags emb [N, T, E]
    -> logits [N]. ``precision`` goes to every matmul (``lax.Precision``
    or its name)."""
    z0 = _mlp(params["bottom"], dense, final_act=True, precision=precision)
    x = interact(z0, emb, precision=precision)
    return _mlp(params["top"], x, precision=precision)[:, 0]


def forward(params: dict, batch: dict, arch: DLRMArch) -> jax.Array:
    """batch: dense [B, num_dense], indices [T, B, P] -> CTR logit [B]."""
    z0 = _mlp(params["bottom"], batch["dense"], final_act=True)
    z0 = logical_constraint(z0, "batch", None)
    emb = embed_bags(params["tables"], batch["indices"])
    x = interact(z0, emb)
    return _mlp(params["top"], x)[:, 0]


def loss_fn(params: dict, batch: dict, arch: DLRMArch) -> jax.Array:
    logit = forward(params, batch, arch)
    y = batch["labels"].astype(jnp.float32)
    # numerically-stable BCE-with-logits
    return jnp.mean(jnp.maximum(logit, 0) - logit * y +
                    jnp.log1p(jnp.exp(-jnp.abs(logit))))


def serve_query(params: dict, user_idx: jax.Array, item_idx: jax.Array,
                dense: jax.Array, arch: DLRMArch) -> jax.Array:
    """Inference per §2.2: user bags once (B_U=1), broadcast over item batch.

    user_idx: [Tu, P]; item_idx: [Ti, Bi, P]; dense: [Bi, num_dense].
    Returns CTR scores [Bi].
    """
    n_user = len(arch.user_tables)
    user_emb = embed_bags(params["tables"][:n_user], user_idx[:, None, :])
    return score_items(params, user_emb[0], item_idx, dense, arch)


def score_items(params: dict, user_emb: jax.Array, item_idx: jax.Array,
                dense: jax.Array, arch: DLRMArch) -> jax.Array:
    """Rank one query's item batch (Eq. 2) from its pooled user bags.

    user_emb: [Tu, E], from ``embed_bags`` or a serving engine's pooled
    output; item_idx: [Ti, Bi, P]; dense: [Bi, num_dense].
    Returns CTR scores [Bi].
    """
    n_user = len(arch.user_tables)
    Bi = dense.shape[0]
    user_emb = jnp.broadcast_to(user_emb[None], (Bi,) + user_emb.shape)
    item_emb = embed_bags(params["tables"][n_user:], item_idx)              # [Bi, Ti, E]
    emb = jnp.concatenate([user_emb, item_emb], axis=1)
    return jax.nn.sigmoid(dense_logits(params, dense, emb))
