"""95th percentile of every campaign step of the window, in ms (host
clock).  A step is propose + evaluate + observe; it ends once ``observe``
returns.  The percentile interpolates linearly between order statistics."""
import numpy as np


def read(rec):
    w = rec["window"]
    if w["kind"] != "campaign" or not w["step_s"]:
        return None
    return float(np.percentile(np.asarray(w["step_s"]), 95)) * 1e3
