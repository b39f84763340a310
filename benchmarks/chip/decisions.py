"""The mapping decisions of one cloud (FPS samples, kNN neighbour sets),
recomputed in float64 on the host, with every decision that float32
rounding leaves open.

FPS picks, at each step, the point farthest from those already picked;
kNN keeps, for each centre, the k points nearest to it.  Where two
candidates lie closer than float32 rounding can resolve, a sound
float32 implementation may take either: which one it takes depends on
the order of its sums, and so on its shapes and fusions, not on the
configuration.  The answer then differs from the reference's by as much
as a different neighbour or sample moves the logits, which can be far
more than rounding.  So the reference accepts each such open decision
either way: :func:`paths` lists the decision path in exact arithmetic,
and the paths that flip one or two of its open decisions (each later
decision recomputed on the flipped path).

A decision is open when its margin is under ``EPS`` of the scale of the
numbers it was computed from: for FPS the farthest distance (distances
are sums of squared differences, exact to a few float32 ulps of
themselves); for kNN the squared norms of the centre and the points
(the distance is |s|^2 - 2 s.p + |p|^2, exact to a few ulps of those).
``EPS`` is 2^-20, eight times float32's unit roundoff and more than
either form's error.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

EPS = 2.0 ** -20
#: Open decisions of one cloud that the candidates flip (smallest
#: margins first); a cloud rarely has more than two.
MAX_OPEN = 6

Path = Tuple[Tuple[np.ndarray, ...], Tuple[np.ndarray, ...]]


@dataclasses.dataclass(frozen=True)
class Open:
    """One open decision: at ``stage``, FPS step ``at`` could pick
    ``alt`` (the runner-up) or kNN centre row ``at`` could swap its k-th
    neighbour for ``alt`` (the (k+1)-th); ``ratio`` is margin over
    tolerance, under 1."""
    stage: int
    kind: str
    at: int
    alt: int
    ratio: float


def stage_samples(c) -> List[int]:
    return [c["n_points"] // 2 ** (i + 1) for i in range(4)]


def _fps(pts: np.ndarray, m: int, stage: int, flip: Optional[Open],
         found: List[Open]) -> Optional[np.ndarray]:
    """FPS from index 0 (ties to the lowest index); ``flip`` takes the
    runner-up at its step, and None comes back where that step is no
    longer open with that runner-up on this path."""
    n = len(pts)
    idx = np.zeros(m, np.int64)
    dist = np.full(n, np.inf)
    for i in range(1, m):
        dist = np.minimum(dist, np.sum((pts - pts[idx[i - 1]]) ** 2, -1))
        best = int(np.argmax(dist))
        top = dist[best]
        dist[best] = -np.inf
        runner = int(np.argmax(dist))
        dist[best] = top
        ratio = (top - dist[runner]) / (EPS * top) if top > 0 else np.inf
        if ratio < 1:
            found.append(Open(stage, "fps", i, runner, float(ratio)))
        idx[i] = best
        if flip is not None and flip.at == i:
            if not (ratio < 1 and runner == flip.alt):
                return None
            idx[i] = runner
    return idx


def _knn(cen: np.ndarray, pts: np.ndarray, k: int, stage: int,
         rnd: Callable, flips: Sequence[Open], found: List[Open]
         ) -> Optional[np.ndarray]:
    """The k nearest points of each centre (ties to the lowest index)
    and the open k-th/(k+1)-th boundaries; ``flips`` swap them."""
    s2 = np.sum(cen * cen, -1)
    p2 = np.sum(pts * pts, -1)
    d = s2[:, None] - 2.0 * (rnd(cen) @ rnd(pts).T) + p2[None, :]
    part = np.argpartition(d, k, axis=1)[:, :k + 1]
    dp = np.take_along_axis(d, part, 1)
    order = np.lexsort((part, dp), axis=1)
    part = np.take_along_axis(part, order, 1)
    nbr = part[:, :k].copy()
    kth, nxt = part[:, k - 1], part[:, k]
    rows = np.arange(len(cen))
    tol = EPS * (s2.max() + p2.max())
    ratio = (d[rows, nxt] - d[rows, kth]) / tol
    apart = np.any(pts[kth] != pts[nxt], axis=-1)
    for j in np.nonzero((ratio < 1) & apart)[0]:
        found.append(Open(stage, "knn", int(j), int(nxt[j]), float(ratio[j])))
    for f in flips:
        if not (ratio[f.at] < 1 and nxt[f.at] == f.alt):
            return None
        nbr[f.at, k - 1] = f.alt
    return nbr


def _path(c, cloud: np.ndarray, urs_idx, rnd: Callable,
          flips: Sequence[Open]) -> Tuple[Optional[Path], List[Open]]:
    """The decision path of ``cloud`` with ``flips`` applied (None where
    one of them is not open on it), and the open decisions met."""
    cur = cloud.astype(np.float64)
    idx_t, nbr_t, found = [], [], []
    for s, m in enumerate(stage_samples(c)):
        if urs_idx is not None:
            idx = np.asarray(urs_idx[s], np.int64)
        else:
            fl = [f for f in flips if f.stage == s and f.kind == "fps"]
            if len(fl) > 1:
                return None, found
            idx = _fps(cur, m, s, fl[0] if fl else None, found)
            if idx is None:
                return None, found
        cen = cur[idx]
        nbr = _knn(cen, cur, c["k_neighbors"], s, rnd,
                   [f for f in flips if f.stage == s and f.kind == "knn"],
                   found)
        if nbr is None:
            return None, found
        idx_t.append(idx.astype(np.int32))
        nbr_t.append(nbr.astype(np.int32))
        cur = cen
    return (tuple(idx_t), tuple(nbr_t)), found


def paths(c, cloud: np.ndarray, urs_idx=None,
          rnd: Callable = lambda a: a) -> List[Path]:
    """The exact decision path of ``cloud`` under configuration ``c``,
    then every path that flips one or two of its ``MAX_OPEN`` most open
    decisions.  ``urs_idx`` are the shared URS indices (FPS where None);
    ``rnd`` rounds the operands of the kNN cross term as the
    configuration's matmul arithmetic does."""
    first, found = _path(c, cloud, urs_idx, rnd, ())
    opened = sorted(found, key=lambda o: o.ratio)[:MAX_OPEN]
    out = [first]
    for r in (1, 2):
        for flips in itertools.combinations(opened, r):
            p, _ = _path(c, cloud, urs_idx, rnd, flips)
            if p is not None:
                out.append(p)
    return out
