"""Set-up: process start to the first timed unit of work (host clock).

It covers importing JAX, reaching the device, building the cell's
evaluator, loading or compiling its programs and the warm-up unit."""


def read(rec):
    return rec["setup_s"]
