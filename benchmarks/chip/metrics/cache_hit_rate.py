"""Row-cache hit rate over the window's lookups, in percent, from the
engine's own hit and miss counters."""


def read(run):
    n = run.hits + run.misses
    return 100.0 * run.hits / n if n else None
