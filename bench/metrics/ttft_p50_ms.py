"""Median, over every request due in the window, of the time from when it
was due to its first token (the wait so far for one that has none at the
close)."""
import numpy as np


def read(run):
    v = run.ttfts()
    return float(np.percentile(v, 50)) * 1e3 if v else None
