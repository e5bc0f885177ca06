"""Host ms per evaluator call in the ``eval.upload`` span (pad to the
batch bucket and upload the index batch)."""
from harness.spans import program_spans, seconds


def read(rec):
    sp = program_spans(rec, "campaign", "eval.call")
    if sp is None:
        return None
    return seconds(sp, "eval.upload") / sp["eval.call"]["count"] * 1e3
