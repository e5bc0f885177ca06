"""Rows per chunk that survived the device's dominance filter and were
inserted into the host archives (the ``rows`` of ``sweep.insert``, summed
over groups), over the window's chunks."""
from harness.spans import program_spans, stat


def read(rec):
    sp = program_spans(rec, "sweep", "sweep.chunk")
    if sp is None or not rec["window"]["chunks"]:
        return None
    return stat(sp, "sweep.insert", "rows") / rec["window"]["chunks"]
