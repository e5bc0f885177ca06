"""Device ms per chunk step in the ``sweep.op_terms`` scope of the jitted
step (op-term passes, latency and stall contractions), busiest chip, from
each operation's op-name metadata."""
from harness.spans import scope_ms_per_chunk


def read(rec):
    return scope_ms_per_chunk(rec, "sweep.op_terms")
