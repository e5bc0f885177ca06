"""Host ms per evaluator call in the ``eval.launch`` span (the jitted call
until it returns)."""
from harness.spans import program_spans, seconds


def read(rec):
    sp = program_spans(rec, "campaign", "eval.call")
    if sp is None:
        return None
    return seconds(sp, "eval.launch") / sp["eval.call"]["count"] * 1e3
