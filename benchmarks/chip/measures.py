"""Arithmetic of the end-to-end metrics over one window's requests.

Every request carries the time it was due (for a closed backlog: when
it was put in the queue).  A rate counts the answers that landed inside
the window over the whole window; a latency runs from the due time to
the logits on the host, over every request due in the window.  A
request that was shed or never answered counts as failed, and in the
percentiles as having waited until the run gave up on it.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def answered_in_window(recs, t0: float, seconds: float) -> int:
    return sum(1 for r in recs
               if r.t_done is not None and t0 <= r.t_done <= t0 + seconds)


def rate_per_s(recs, t0: float, seconds: float) -> float:
    """Answers completed inside the window per second of window."""
    return answered_in_window(recs, t0, seconds) / seconds


def failed(recs) -> int:
    return sum(1 for r in recs if r.shed or r.t_done is None)


def latencies_ms(recs, gave_up: Optional[float] = None) -> np.ndarray:
    """Due-to-answer latency of every request; failed ones wait until
    ``gave_up`` (the end of the run)."""
    out: List[float] = []
    for r in recs:
        if r.t_done is not None:
            out.append(r.t_done - r.due)
        elif gave_up is not None:
            out.append(gave_up - r.due)
    return np.asarray(out, np.float64) * 1e3


def percentile(values: np.ndarray, q: float) -> Optional[float]:
    """The ``q``-th percentile (linear between order statistics), or
    None without values."""
    if values.size == 0:
        return None
    return float(np.percentile(values, q))


def lateness_ms(recs) -> np.ndarray:
    """How late the generator sent each request after it was due."""
    return np.asarray([r.t_submit - r.due for r in recs
                       if r.t_submit is not None], np.float64) * 1e3
