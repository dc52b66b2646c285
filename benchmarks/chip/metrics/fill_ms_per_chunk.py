"""Device time of the operations inside the engine's ``engine.fill`` scope
(the cache fill: rank, LRU way pick and scatters) per served chunk, in
ms."""
import enginetrace


def read(run):
    return enginetrace.READERS["fill_ms_per_chunk"](run)
