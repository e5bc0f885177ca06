"""Host ms per evaluator call, from the program's own ``eval.call`` span
(``ModelEvaluator.evaluate`` whole), both tiers."""
from harness.spans import program_spans


def read(rec):
    sp = program_spans(rec, "campaign", "eval.call")
    if sp is None:
        return None
    c = sp["eval.call"]
    return c["s"] / c["count"] * 1e3
