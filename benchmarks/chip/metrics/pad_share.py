"""Padded share of the dense index blocks' positions over the window, in
percent, from the engine's counters: 100 x (1 - ``engine.valid_positions``
/ ``engine.positions``)."""
import enginetrace


def read(run):
    return enginetrace.READERS["pad_share"](run)
