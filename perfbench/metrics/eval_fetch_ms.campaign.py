"""Host ms per evaluator call in the ``eval.fetch`` span (one blocking
copy back per output leaf)."""
from harness.spans import program_spans, seconds


def read(rec):
    sp = program_spans(rec, "campaign", "eval.call")
    if sp is None:
        return None
    return seconds(sp, "eval.fetch") / sp["eval.call"]["count"] * 1e3
