"""Evaluator calls in the window per campaign step (``eval.call`` count
over ``dse.step`` count; campaign start-up calls count too)."""
from harness.spans import program_spans


def read(rec):
    sp = program_spans(rec, "campaign", "dse.step")
    if sp is None or "eval.call" not in sp:
        return None
    return sp["eval.call"]["count"] / sp["dse.step"]["count"]
