"""Host self time of the engine's ``engine.item_pack`` span (the item
block ``[B x item_batch, item tables, P]`` packed on the host) per served
chunk, in ms."""
import enginetrace
from repro.obs import tracing as names

read = enginetrace._span_ms_per_chunk(names.SPAN_ITEM_PACK)
