"""Campaign steps completed in the window, over the window (host clock).
Each campaign's start-up and first step count as window time."""


def read(rec):
    w = rec["window"]
    if w["kind"] != "campaign":
        return None
    return w["units"] / w["elapsed_s"]
