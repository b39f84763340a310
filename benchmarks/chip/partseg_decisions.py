"""The mapping decisions of one PointMLP part-segmentation cloud (FPS
samples, kNN neighbour sets, the decoder's 3-NN sources), recomputed in
float64 on the host, with every decision that float32 rounding leaves
open.

The encoder's decisions are those of ``decisions.py`` (its ``_fps`` and
``_knn``, with their tolerance), taken at this model's stage sizes: each
stage keeps ``N / stage_reduction`` of the points before it.  The
decoder adds one decision per point of each finer level: which 3 points
of the coarser level are its nearest.  It is open, like a kNN boundary,
where the 3rd and 4th nearest lie closer than the selection's float32
distances (``|f|^2 - 2 f.c + |c|^2``) can resolve; a flip takes the 4th
in place of the 3rd.  Decoder levels count as stages 4 to 7 of an
:class:`decisions.Open`.

A 3-NN choice decides nothing after it, and a kNN set decides nothing
but its own stage's grouping, so a path that flips only those reuses the
exact path and swaps the flipped entries; a flipped FPS step recomputes
every decision after it.
"""
from __future__ import annotations

import itertools
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

import decisions
from decisions import MAX_OPEN, Open

N_STAGES = 4
#: Coarse points each fine point interpolates from.
NN = 3

Path = Tuple[Tuple[np.ndarray, ...], Tuple[np.ndarray, ...],
             Tuple[np.ndarray, ...]]


def stage_samples(c) -> List[int]:
    r = c["stage_reduction"]
    return [c["n_points"] // r ** (i + 1) for i in range(N_STAGES)]


def _select(cen: np.ndarray, pts: np.ndarray, k: int, stage: int,
            rnd: Callable, flips: Sequence[Open], found: List[Open]
            ) -> Optional[np.ndarray]:
    """The ``k`` nearest of ``pts`` for each of ``cen`` (``decisions._knn``);
    where ``k`` takes every point there is nothing to decide."""
    if k >= len(pts):
        d = np.sum((cen[:, None, :] - pts[None, :, :]) ** 2, -1)
        return None if flips else np.argsort(d, axis=1, kind="stable")[:, :k]
    return decisions._knn(cen, pts, k, stage, rnd, flips, found)


def _swap(nbr: np.ndarray, flips: Sequence[Open]) -> np.ndarray:
    """The exact path's choices with ``flips`` (found open on it) taken."""
    nbr = nbr.copy()
    for f in flips:
        nbr[f.at, -1] = f.alt
    return nbr


def _path(c, xyz: np.ndarray, rnd: Callable, flips: Sequence[Open],
          found: List[Open], exact: Optional[Path] = None
          ) -> Optional[Path]:
    """The decision path of the cloud's ``xyz`` with ``flips`` applied
    (None where one of them is not open on it); ``exact`` is the path
    without flips, whose decisions before the first flipped FPS step
    are reused."""
    fps_at = [f.stage for f in flips if f.kind == "fps"]
    first = min(fps_at, default=None)
    cur, levels = xyz, [xyz]
    idx_t, nbr_t, up_t = [], [], []
    for s, m in enumerate(stage_samples(c)):
        here = [f for f in flips if f.stage == s]
        knn_flips = [f for f in here if f.kind == "knn"]
        if exact is not None and (first is None or s < first):
            idx, nbr = exact[0][s], _swap(exact[1][s], knn_flips)
        else:
            fl = [f for f in here if f.kind == "fps"]
            if len(fl) > 1:
                return None
            idx = decisions._fps(cur, m, s, fl[0] if fl else None, found)
            if idx is None:
                return None
            nbr = _select(cur[idx], cur, c["k_neighbors"], s, rnd,
                          knn_flips, found)
            if nbr is None:
                return None
        idx_t.append(np.asarray(idx, np.int32))
        nbr_t.append(np.asarray(nbr, np.int32))
        cur = cur[idx]
        levels.append(cur)
    for j in range(N_STAGES):
        s = N_STAGES + j
        here = [f for f in flips if f.stage == s]
        if exact is not None and first is None:
            up = _swap(exact[2][j], here)
        else:
            up = _select(levels[-2 - j], levels[-1 - j], NN, s, rnd, here,
                         found)
            if up is None:
                return None
        up_t.append(np.asarray(up, np.int32))
    return tuple(idx_t), tuple(nbr_t), tuple(up_t)


def paths(c, xyz: np.ndarray, rnd: Callable = lambda a: a) -> List[Path]:
    """The exact decision path of one cloud's ``xyz`` [N, 3] under
    configuration ``c``, then every path that flips one or two of its
    ``MAX_OPEN`` most open decisions (encoder and decoder together).
    ``rnd`` rounds the operands of the distance cross terms as the
    configuration's matmul arithmetic does."""
    found: List[Open] = []
    first = _path(c, np.asarray(xyz, np.float64), rnd, (), found)
    opened = sorted(found, key=lambda o: o.ratio)[:MAX_OPEN]
    out = [first]
    for r in (1, 2):
        for flips in itertools.combinations(opened, r):
            p = _path(c, np.asarray(xyz, np.float64), rnd, flips, [],
                      exact=first)
            if p is not None:
                out.append(p)
    return out
