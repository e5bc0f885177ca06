"""Device ns in the chunk step's ``sweep.op_terms`` scope per design scored
and per op row (``op_rows.sweep``): what one row of one design's op terms
costs, so tables of different lengths compare.  The scope's time is the
busiest chip's times the chips that ran (a sharded sweep gives each chip
the same share of a chunk), over the designs the window scored.  None
where the run was not traced, the trace names no ``sweep.*`` scope, or
the program reports no ``op_rows``."""
from harness import spec


def read(rec):
    w, t = rec["window"], rec.get("trace") or {}
    sc = t.get("scopes") or {}
    if (w["kind"] != "sweep" or not w["work"]
            or not any(k.startswith("sweep.") for k in sc)):
        return None
    rows = spec.metric_reader("op_rows.sweep").read(rec)
    if not rows:
        return None
    chips = sum(1 for s in t["busy_s"].values() if s > 0)
    return sc.get("sweep.op_terms", 0.0) * chips / (w["work"] * rows) * 1e9
