"""95th percentile of the gaps between consecutive tokens of all requests,
for gaps that end in the window (see serve.Run.itl_gaps)."""
import numpy as np


def read(run):
    v = run.itl_gaps()
    return float(np.percentile(v, 95)) * 1e3 if v else None
