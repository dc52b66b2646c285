"""Plain reference of the served path ``rank``: the logits of a DLRM query's
candidates (DLRM, arXiv 1906.00091, as Fig. 2 of arXiv 2110.11489 follows
it), and the limit of the comparison that decides ``correct``.

It imports nothing of the program and takes nothing the program made. From
the run's seed and the configuration it recomputes:

* the pooled bags of every user and item lookup bag with
  :func:`reference.pool` (rows made again from the seed, quantized to 8
  bits, dequantized, summed in float64). The item tables' rows follow the
  user tables' in one global numbering (:func:`item_offsets`);
* the dense half's weights, by the formula of
  ``repro.models.dlrm.init_dense_half`` copied here (layer by layer,
  bottom first, ``w ~ N(0, 2 / fan_in)`` then ``b ~ N(0, 0.01^2)``, float32
  from ``np.random.default_rng``), so that a change to the program cannot
  move the yardstick;
* the forward in ``jax.numpy`` float32 at ``highest`` matmul precision:
  ``z0 = relu-MLP_bottom(dense)``; ``feats = [z0, the query's pooled user
  bags, the candidate's pooled item bags]``; ``x = [z0, upper triangle of
  feats feats^T]``; ``logit = MLP_top(x)``, ReLU on every layer but the
  last. No kernel, cache or batching: each candidate's row of features
  carries its query's user bags.

``forward(..., precision="default")`` is the control: on a TPU the same
forward with one-pass bf16 matmuls, the next precision below the
configuration's.
"""
from __future__ import annotations

import math

import numpy as np

import reference
import tables as tables_mod

# Limit of the widest gap of a candidate's logit from the reference's, and
# of its score from the reference's sigmoid (readings in PERF.md, section
# 6). Logits are compared before the sigmoid, which would flatten a gap at
# a saturated score. On a TPU v5e the program reads gaps up to 2e-5
# (float32 sums in another order), a one-pass bf16 dense half 0.3: the
# limit sits between them, 50 times above the first.
LOGIT_GAP_LIMIT = 1e-3


def item_offsets(cfg: dict) -> np.ndarray:
    """Global id of each item table's first row: after every user row."""
    base = int(np.sum(cfg["tables"]["rows"]))
    return base + tables_mod.row_offsets(cfg["item_tables"]["rows"])


def dense_dims(cfg: dict):
    """``(bottom dims, top dims)``, inputs first."""
    bottom, top = list(cfg["bottom_mlp"]), list(cfg["top_mlp"])
    f = cfg["num_user_tables"] + cfg["num_item_tables"] + 1
    return ([cfg["num_dense"]] + bottom,
            [bottom[-1] + f * (f - 1) // 2] + top)


def dense_weights(seed_words: list, cfg: dict) -> dict:
    """The dense half's float32 weights drawn from ``seed_words``."""
    rng = np.random.default_rng(seed_words)
    out = {}
    for part, dims in zip(("bottom", "top"), dense_dims(cfg)):
        out[part] = []
        for fi, fo in zip(dims[:-1], dims[1:]):
            w = rng.standard_normal((fi, fo), np.float32)
            w *= np.float32(math.sqrt(2.0 / fi))
            b = rng.standard_normal(fo, np.float32) * np.float32(0.01)
            out[part].append({"w": w, "b": b})
    return out


def forward(weights: dict, dense, user, item, precision: str = "highest"):
    """Logits ``[N]`` of ``N`` candidates: ``dense [N, num_dense]``, their
    queries' pooled user bags ``user [N, Tu, D]`` and their pooled item
    bags ``item [N, Ti, D]``, float32."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision(precision):
        x = jnp.asarray(dense, jnp.float32)
        for layer in weights["bottom"]:
            x = jnp.maximum(x @ layer["w"] + layer["b"], 0)
        feats = jnp.concatenate(
            [x[:, None], jnp.asarray(user, jnp.float32),
             jnp.asarray(item, jnp.float32)], axis=1)
        gram = jnp.einsum("nfe,nge->nfg", feats, feats)
        iu, ju = np.triu_indices(feats.shape[1], k=1)
        x = jnp.concatenate([x, gram[:, iu, ju]], axis=1)
        top = weights["top"]
        for i, layer in enumerate(top):
            x = x @ layer["w"] + layer["b"]
            if i < len(top) - 1:
                x = jnp.maximum(x, 0)
        return np.asarray(x[:, 0])


def item_bags(cfg: dict, seed: int, catalog: np.ndarray, items: np.ndarray):
    """Pooled item bags ``[len(items), Ti, D]`` (float64) of catalog items
    ``items``; ``catalog`` holds each item's lookups, tables in order."""
    pools = np.asarray(cfg["item_tables"]["pooling"])
    Ti, D = len(pools), cfg["dim"]
    n = len(items)
    table = np.tile(np.repeat(np.arange(Ti), pools), n)
    starts = np.arange(n * Ti) // Ti * pools.sum() + np.tile(
        np.cumsum(pools) - pools, n)
    pooled = reference.pool(seed, item_offsets(cfg), table,
                            catalog[items].ravel().astype(np.int64), starts,
                            n * Ti, D)
    return pooled.reshape(n, Ti, D)


def user_bags(cfg: dict, seed: int, traffic, first: int, last: int):
    """Pooled user bags ``[last - first, Tu, D]`` (float64) of queries
    ``[first, last)``."""
    T, D = traffic.lens.shape[1], cfg["dim"]
    _, t, r, starts = reference.chunk_lookups(traffic, first, last)
    pooled = reference.pool(seed, tables_mod.row_offsets(cfg["tables"]["rows"]),
                            t, r, starts, (last - first) * T, D)
    return pooled.reshape(last - first, T, D)
