"""Device ms per chunk step in the ``sweep.decode`` scope of the jitted
step (unrank, decode and hardware derivation), busiest chip, from each
operation's op-name metadata."""
from harness.spans import scope_ms_per_chunk


def read(rec):
    return scope_ms_per_chunk(rec, "sweep.decode")
