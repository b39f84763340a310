"""Answered requests completed inside the window, per second of window."""
import measures


def read(run):
    w = run.window
    return measures.rate_per_s(w.recs, w.t0, w.seconds)
