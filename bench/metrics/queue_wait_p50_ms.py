"""Median, over the requests due in the window, of the wait from when a
request was due to when it left the engine's queue (`Request.start_time`:
its slot claimed for a one-shot prefill, or its chunked prefill begun); the
wait so far for one still queued at the close. None where the program does
not stamp `start_time`."""
import numpy as np


def read(run):
    recs = run.window_recs()
    if not any(hasattr(r.req, "start_time") for r in recs
               if r.req is not None):
        return None
    waits = []
    for r in recs:
        t = getattr(r.req, "start_time", 0.0) or 0.0
        waits.append((t if 0 < t < run.t_close else run.t_close) - r.due)
    return float(np.percentile(waits, 50)) * 1e3
