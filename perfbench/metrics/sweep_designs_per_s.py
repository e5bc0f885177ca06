"""Designs scored by whole sweeps run back to back, over the elapsed time
of the window (host clock).  The window ends when the first sweep that
crosses the window length completes, so every sweep counted is whole."""


def read(rec):
    w = rec["window"]
    if w["kind"] != "sweep":
        return None
    return w["work"] / w["elapsed_s"]
