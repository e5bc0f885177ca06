"""Share of the traced sweep window in which no operation ran on the
device, in %: 1 - union of busy intervals / window, mean over chips."""


def read(rec):
    t = rec.get("trace")
    if rec["window"]["kind"] != "sweep" or not t:
        return None
    busy = sum(t["busy_s"].values()) / len(t["busy_s"])
    return (1.0 - busy / t["window_s"]) * 100.0
