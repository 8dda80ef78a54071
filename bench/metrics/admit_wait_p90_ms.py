"""90th percentile, over the requests due in the window, of the wait from
when a request was due to the end of its prefill (the engine's
`Request.admit_time`); the wait so far for one not admitted by the close."""
import numpy as np


def read(run):
    waits = []
    for r in run.window_recs():
        t = getattr(r.req, "admit_time", 0.0) or 0.0
        waits.append((t if 0 < t < run.t_close else run.t_close) - r.due)
    return float(np.percentile(waits, 90)) * 1e3 if waits else None
