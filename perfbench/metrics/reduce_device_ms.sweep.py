"""Device ms per chunk step in the ``sweep.reduce`` scope of the jitted
step (superiority count, running top-k, stall top-k, local filter and
dominance test), busiest chip, from each operation's op-name metadata."""
from harness.spans import scope_ms_per_chunk


def read(rec):
    return scope_ms_per_chunk(rec, "sweep.reduce")
