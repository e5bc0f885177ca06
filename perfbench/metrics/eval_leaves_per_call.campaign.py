"""Output leaves copied back per evaluator call (the ``leaves`` of
``eval.fetch``)."""
from harness.spans import program_spans, stat


def read(rec):
    sp = program_spans(rec, "campaign", "eval.call")
    if sp is None:
        return None
    return stat(sp, "eval.fetch", "leaves") / sp["eval.call"]["count"]
