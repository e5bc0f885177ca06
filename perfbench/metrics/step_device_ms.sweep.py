"""Device busy time of the traced sweep window per chunk step, in ms.

Nothing but the sweep's chunk steps runs on the device in a sweep window,
so busy time needs no operation names.  On several chips, the busiest."""


def read(rec):
    w, t = rec["window"], rec.get("trace")
    if w["kind"] != "sweep" or not t or not w["chunks"]:
        return None
    return max(t["busy_s"].values()) / w["chunks"] * 1e3
