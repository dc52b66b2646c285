"""Device time of the operations inside the engine's ``engine.dense`` scope
(the Eq. 2 broadcast, bottom MLP, dot interaction, top MLP and sigmoid)
per served chunk, in ms."""
import enginetrace
from repro.obs import tracing as names

read = enginetrace._scope_ms_per_chunk(names.SCOPE_DENSE)
