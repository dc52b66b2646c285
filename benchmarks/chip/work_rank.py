"""Useful work of one ranking chunk, beside ``work``'s user side, counted
from what the algorithm needs: valid item lookups only (no padding, no
row-group amplification) at their stored size, the pooled item bags, and
the dense half's matmul operations at the configuration's widths.

A chunk's counts (``served/rank.py``'s ``check`` adds them to the user
side's) add ``items`` candidates, ``item_bags`` (candidate, item table)
bags and ``item_lookups`` valid item lookups.
"""
from __future__ import annotations

import work
from reference_rank import dense_dims


def item_gather(cfg: dict, c: dict):
    """(bytes, flops) of pooling every item lookup from the item store."""
    d = cfg["dim"]
    return (c["item_lookups"] * work.row_bytes(cfg)
            + c["item_bags"] * d * work.OUT_BYTES,
            c["item_lookups"] * d * work.FLOPS_PER_ELEM)


def dense_flops_per_item(cfg: dict) -> int:
    """Multiply-adds x 2 of one candidate's dense half: both MLPs and the
    upper triangle of the interaction's Gram matrix."""
    bottom, top = dense_dims(cfg)
    mlp = sum(a * b for dims in (bottom, top)
              for a, b in zip(dims[:-1], dims[1:]))
    pairs = top[0] - bottom[-1]
    return 2 * (mlp + pairs * cfg["dim"])


def dense_half(cfg: dict, c: dict):
    """(bytes, flops) of the dense half: its operations alone (its weights
    and activations are small next to them)."""
    return (0, c["items"] * dense_flops_per_item(cfg))


def step(cfg: dict, c: dict):
    """(bytes, flops) of the whole ranking step: the user step's, the item
    gather-pool's and the dense half's."""
    parts = [work.step(cfg, c), item_gather(cfg, c), dense_half(cfg, c)]
    return (sum(p[0] for p in parts), sum(p[1] for p in parts))
