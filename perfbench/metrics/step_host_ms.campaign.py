"""Campaign host logic per step, in ms: the self time of ``dse.step``,
``dse.propose`` and ``dse.observe`` (time in none of the spans nested in
them, evaluator calls included), over the ``dse.step`` count."""
from harness.spans import program_spans, seconds


def read(rec):
    sp = program_spans(rec, "campaign", "dse.step")
    if sp is None:
        return None
    host = sum(seconds(sp, n, "self_s")
               for n in ("dse.step", "dse.propose", "dse.observe"))
    return host / sp["dse.step"]["count"] * 1e3
