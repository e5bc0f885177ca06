"""Per-op term rows the sweep's chunk step evaluates for one design: the
``op_rows`` of the program's ``sweep.run`` span, over the window's sweeps.
The pair step counts both workloads' tables; the portfolio step counts the
deduped union plus the stall columns' second pass.  None where the run was
not traced, or the program's span carries no ``op_rows``."""


def read(rec):
    t = rec.get("trace") or {}
    run = (t.get("spans") or {}).get("sweep.run")
    if rec["window"]["kind"] != "sweep" or not run or not run["count"]:
        return None
    rows = run["stats"].get("op_rows")
    return None if rows is None else rows / run["count"]
