"""Host ms per chunk in the program's ``sweep.filter`` span (build the
dominance filter from the archives and upload it), over the window's
chunks."""
from harness.spans import program_spans, seconds


def read(rec):
    sp = program_spans(rec, "sweep", "sweep.chunk")
    if sp is None or not rec["window"]["chunks"]:
        return None
    return seconds(sp, "sweep.filter") / rec["window"]["chunks"] * 1e3
