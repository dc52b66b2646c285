"""Host self time of the engine's ``engine.account`` span (per-query IO and
latency accounting and the result arrays) per served chunk, in ms."""
import enginetrace


def read(run):
    return enginetrace.READERS["account_ms_per_chunk"](run)
