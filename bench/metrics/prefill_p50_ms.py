"""Median, over the requests due in the window, of the time from when a
request left the engine's queue (`Request.start_time`) to its first token
(`Request.admit_time`, the end of its prefill, chunked or not): the prefill
so far for one started but not admitted by the close, 0 for one not yet
started. None where the program does not stamp `start_time`."""
import numpy as np


def read(run):
    recs = run.window_recs()
    if not any(hasattr(r.req, "start_time") for r in recs
               if r.req is not None):
        return None
    spans = []
    for r in recs:
        start = getattr(r.req, "start_time", 0.0) or 0.0
        if not 0 < start < run.t_close:
            spans.append(0.0)
            continue
        end = getattr(r.req, "admit_time", 0.0) or 0.0
        spans.append((end if 0 < end < run.t_close else run.t_close) - start)
    return float(np.percentile(spans, 50)) * 1e3
