"""Median time from a request's due time to its dispatch, read from FIFO
order around each ``pump()`` of the window."""
import numpy as np


def read(run):
    w = run.window
    waits = [r.t_dispatch - r.due for r in w.recs
             if r.t_dispatch is not None and r.due <= w.t0 + w.seconds]
    if not waits:
        return None
    return float(np.median(waits)) * 1e3
