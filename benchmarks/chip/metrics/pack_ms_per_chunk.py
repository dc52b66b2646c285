"""Host self time of the engine's ``engine.pack`` span (the chunk's dense
index block built on the host) per served chunk, in ms: the span's time
not covered by a span inside it."""
import enginetrace


def read(run):
    return enginetrace.READERS["pack_ms_per_chunk"](run)
