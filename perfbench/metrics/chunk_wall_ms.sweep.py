"""Mean host wall time per chunk in the window, in ms, from the engine's
own ``sweep_chunk_s`` histogram (filter build and upload, step, survivor
copy back and archive insert)."""


def read(rec):
    w = rec["window"]
    if w["kind"] != "sweep" or not w["chunk_s_count"]:
        return None
    return w["chunk_s_sum"] / w["chunk_s_count"] * 1e3
