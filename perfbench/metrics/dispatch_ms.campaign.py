"""Mean host wall time per dispatching evaluator call in the window, in
ms: ``evaluate`` and ``objectives`` on both tiers, timed by the harness's
wrapper around each call (pad, upload, run, copy the report back)."""


def read(rec):
    w = rec["window"]
    if w["kind"] != "campaign" or not w["dispatch_calls"]:
        return None
    return w["dispatch_s"] / w["dispatch_calls"] * 1e3
