"""Device-to-host copies per evaluator call (the ``copies`` of
``eval.fetch`` over the ``eval.call`` count): 1 where each report comes
back in one transfer."""
from harness.spans import program_spans, stat


def read(rec):
    sp = program_spans(rec, "campaign", "eval.call")
    if sp is None:
        return None
    return stat(sp, "eval.fetch", "copies") / sp["eval.call"]["count"]
