"""Device time of the operations inside the engine's ``engine.probe`` scope
(the row-cache probe, with the cache's rematch and stamp parts) per
served chunk, in ms."""
import enginetrace


def read(run):
    return enginetrace.READERS["probe_ms_per_chunk"](run)
