"""Device time of the operations inside the engine's ``engine.item`` scope
(the FM-direct item store's gather-pool with its scale and bias gathers)
per served chunk, in ms."""
import enginetrace
from repro.obs import tracing as names

read = enginetrace._scope_ms_per_chunk(names.SCOPE_ITEM)
