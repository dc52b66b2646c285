"""Padded share of the item blocks' positions over the window, in percent,
from the engine's counters: 100 x (1 - ``engine.item_valid_positions`` /
``engine.item_positions``)."""


def read(run):
    tel = getattr(run, "telemetry", None)
    if tel is None:
        return None
    c = tel.registry.counters
    pos = c.get("engine.item_positions", 0)
    return (100.0 * (1.0 - c.get("engine.item_valid_positions", 0) / pos)
            if pos else None)
