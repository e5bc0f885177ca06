"""Host ms per chunk in the program's ``sweep.fetch`` span (copy the
chunk's objective block and ids back, only when a row survived), over the
window's chunks."""
from harness.spans import program_spans, seconds


def read(rec):
    sp = program_spans(rec, "sweep", "sweep.chunk")
    if sp is None or not rec["window"]["chunks"]:
        return None
    return seconds(sp, "sweep.fetch") / rec["window"]["chunks"] * 1e3
