"""Mean per campaign step of the step's wall time minus the evaluator
calls inside it, in ms: propose, observe and refinement on the host."""


def read(rec):
    w = rec["window"]
    if w["kind"] != "campaign" or not w["step_s"]:
        return None
    host = [s - e for s, e in zip(w["step_s"], w["step_eval_s"])]
    return sum(host) / len(host) * 1e3
