"""Arrival schedules: the one generator every traffic file is read by.

A traffic file (``traffic/<name>.json``) holds only parameters; its
``kind`` picks one of the schedules below.  Every seed gets the same
multiset of sizes and gaps in another order, so two seeds differ in
arrangement and not in the amount of work.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Arrival:
    """One request of an open-loop schedule."""
    due: float            # seconds after the window opens
    stream: str           # tenant name, or "" for a single-model cell
    cloud: int            # index into that stream's pool of clouds


def exp_gaps(n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` exponential inter-arrival gaps of mean ``1/rate``, taken at
    the distribution's midpoint quantiles and shuffled by ``rng``."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    return rng.permutation(gaps)


def poisson(seconds: float, rates: dict, pool: int,
            rng: np.random.Generator) -> List[Arrival]:
    """Open-loop Poisson arrivals for each named stream at its rate.

    Each stream sends ``round(rate * seconds)`` requests whose gaps are
    scaled to span the window exactly, so the offered rate is the same
    for every seed.  Cloud ``i`` of a stream is ``i % pool``.
    """
    out: List[Arrival] = []
    for name in sorted(rates):
        n = max(1, int(round(rates[name] * seconds)))
        gaps = exp_gaps(n, rates[name], rng)
        due = np.cumsum(gaps) * (seconds / gaps.sum())
        out += [Arrival(float(t), name, i % pool) for i, t in enumerate(due)]
    out.sort(key=lambda a: a.due)
    return out


def sensor_frames(seconds: float, sessions: int, hz: float,
                  rng: np.random.Generator) -> List[Arrival]:
    """Frames of ``sessions`` sensors at ``hz``, phases spread evenly
    over one period in a seeded order.  ``stream`` is the session index
    and ``cloud`` the frame index within it."""
    period = 1.0 / hz
    frames = int(seconds * hz)
    phase = (rng.permutation(sessions) + 0.5) * period / sessions
    out = [Arrival(float(phase[s] + f * period), str(s), f)
           for s in range(sessions) for f in range(frames)]
    out.sort(key=lambda a: a.due)
    return out
