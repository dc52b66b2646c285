"""Deduped SCM row reads per query over the window, from the per-query
read counts the engine returns (checked exactly against the reference)."""


def read(run):
    if not run.window_queries:
        return None
    return run.window_reads / run.window_queries
