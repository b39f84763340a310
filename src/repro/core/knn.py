"""KNN grouping + geometric affine (HLS4PC §2.1, Fig. 2; PointMLP grouper).

The paper's KNN engine: parallel *distance PEs* compute the distance from
each sample to every input point into a *distance buffer*; a
selection-sort-style module then extracts the k nearest by repeatedly
taking the argmin and overwriting the selected entry with the numeric
maximum of the fixed-point representation.

TPU adaptation (see DESIGN.md §2): distances come from an MXU-friendly
expansion ‖s−p‖² = ‖s‖² − 2 s·p + ‖p‖², and the selection trick is kept
verbatim (branch-free, vectorized over all samples).  The tiled Pallas
version lives in ``repro.kernels.knn``; this module is the composable
reference used by models and oracles.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def pairwise_sqdist(samples: jnp.ndarray, points: jnp.ndarray) -> jnp.ndarray:
    """[S, C], [N, C] -> [S, N] squared euclidean distances (MXU form)."""
    s2 = jnp.sum(samples * samples, axis=-1, keepdims=True)        # [S, 1]
    p2 = jnp.sum(points * points, axis=-1)[None, :]                # [1, N]
    cross = samples @ points.T                                     # [S, N] (MXU)
    return s2 - 2.0 * cross + p2


def knn_select(dist: jnp.ndarray, k: int) -> jnp.ndarray:
    """Paper-faithful k-min extraction: k × (argmin, overwrite with +max).

    dist: [S, N] -> indices [S, k] in ascending-distance order.
    """
    big = jnp.asarray(jnp.finfo(dist.dtype).max, dist.dtype)

    def body(d, _):
        j = jnp.argmin(d, axis=-1)                                  # [S]
        d = d.at[jnp.arange(d.shape[0]), j].set(big)
        return d, j.astype(jnp.int32)

    _, idx = jax.lax.scan(body, dist, None, length=k)               # [k, S]
    return idx.T


@functools.partial(jax.jit, static_argnames=("k",))
def knn(samples: jnp.ndarray, points: jnp.ndarray, k: int) -> jnp.ndarray:
    """[S, C], [N, C] -> [S, k] nearest-neighbor indices."""
    return knn_select(pairwise_sqdist(samples, points), k)


def knn_batched(samples: jnp.ndarray, points: jnp.ndarray, k: int
                ) -> jnp.ndarray:
    """[B, S, C], [B, N, C] -> [B, S, k]."""
    return jax.vmap(lambda s, p: knn(s, p, k))(samples, points)


def ball_query(samples: jnp.ndarray, points: jnp.ndarray, k: int,
               radius: float) -> jnp.ndarray:
    """Ball query: neighbors within ``radius``, capped at the k nearest.

    Reuses the KNN distance core: the k nearest are extracted with the
    paper's selection trick, then any selected neighbor outside the
    ball is replaced by the nearest one (PointNet++ fill semantics —
    the nearest neighbor of a sampled centroid is itself, distance 0,
    so the fill index is always in-ball).  ``radius=inf`` degenerates
    to plain KNN bit-for-bit.

    [S, C], [N, C] -> [S, k] int32.
    """
    d = pairwise_sqdist(samples, points)
    idx = knn_select(d, k)                                   # ascending
    sel = jnp.take_along_axis(d, idx, axis=1)                # [S, k]
    in_ball = sel <= jnp.asarray(radius, d.dtype) ** 2
    return jnp.where(in_ball, idx, idx[:, :1])


def ball_query_batched(samples: jnp.ndarray, points: jnp.ndarray, k: int,
                       radius: float) -> jnp.ndarray:
    """[B, S, C], [B, N, C] -> [B, S, k]."""
    return jax.vmap(lambda s, p: ball_query(s, p, k, radius))(samples,
                                                              points)


def gather_neighbors(feats: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """feats [B, N, C], idx [B, S, k] -> [B, S, k, C]."""
    b, s, k = idx.shape
    flat = idx.reshape(b, s * k)
    out = jnp.take_along_axis(feats, flat[..., None], axis=1)
    return out.reshape(b, s, k, feats.shape[-1])


# ------------------------------------------------ geometric affine -------

def geometric_affine_init(channels: int) -> dict:
    """PointMLP's learnable affine (alpha, beta) over grouped features."""
    return {
        "alpha": jnp.ones((channels,), jnp.float32),
        "beta": jnp.zeros((channels,), jnp.float32),
    }


def normalize_group(grouped: jnp.ndarray, centers: jnp.ndarray,
                    params: Optional[dict], mode: str = "affine",
                    eps: float = 1e-5,
                    per_sample: bool = False) -> jnp.ndarray:
    """Normalize grouped neighborhoods to a stable local representation.

    grouped: [B, S, k, C] neighbor features, centers: [B, S, C].

    Modes (the compression ladder of Table 1):
      * ``affine``  — PointMLP-Elite: (g - c) / sigma * alpha + beta with
        learnable per-channel alpha/beta (sigma is the std over the whole
        batch of local offsets, as in PointMLP).
      * ``norm``    — alpha/beta *pruned* (M-1..M-4 / PointMLP-Lite):
        (g - c) / sigma.
      * ``center``  — plain centering (g - c).

    ``per_sample`` computes sigma per cloud instead of over the batch —
    the streaming-deployment semantics (the FPGA pipeline sees one frame
    at a time), which decouples co-batched serving requests.
    """
    off = grouped - centers[:, :, None, :]
    if mode == "center":
        return off
    red = (1, 2, 3) if per_sample else None
    sigma = jnp.sqrt(jnp.mean(off * off, axis=red, keepdims=per_sample)
                     + eps)
    out = off / (sigma + eps)
    if mode == "norm":
        return out
    if mode == "affine":
        assert params is not None, "affine mode needs alpha/beta params"
        return out * params["alpha"] + params["beta"]
    raise ValueError(f"unknown normalize mode: {mode}")


def neighbor_index(new_xyz: jnp.ndarray, xyz: jnp.ndarray, k: int,
                   radius: Optional[float] = None) -> jnp.ndarray:
    """The mapping half of the grouper: [B, S, 3], [B, N, 3] -> [B, S, k].

    ``radius=None`` selects plain KNN; a float switches to ball query.
    This is the expensive, geometry-only piece the streaming cache
    (``repro.serve.streaming``) reuses across coherent LiDAR frames.
    """
    with jax.named_scope("knn"):
        if radius is None:
            return knn_batched(new_xyz, xyz, k)
        return ball_query_batched(new_xyz, xyz, k, radius)


def group_with_idx(xyz: jnp.ndarray, feats: jnp.ndarray,
                   sample_idx: jnp.ndarray, nbr_idx: jnp.ndarray,
                   affine_params: Optional[dict], mode: str,
                   per_sample_norm: bool = False
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The arithmetic half: gather -> normalize -> concat, indices given.

    Same contract as :func:`group_points` but with the neighbor list
    ``nbr_idx`` [B, S, k] supplied (freshly computed or replayed from a
    stream cache) instead of derived from coordinates.
    """
    new_xyz = jnp.take_along_axis(xyz, sample_idx[..., None], axis=1)
    center_f = jnp.take_along_axis(feats, sample_idx[..., None], axis=1)
    grouped = gather_neighbors(feats, nbr_idx)                # [B, S, k, C]
    grouped = normalize_group(grouped, center_f, affine_params, mode,
                              per_sample=per_sample_norm)
    center_b = jnp.broadcast_to(center_f[:, :, None, :], grouped.shape)
    return new_xyz, center_f, jnp.concatenate([grouped, center_b], axis=-1)


def group_points(xyz: jnp.ndarray, feats: jnp.ndarray,
                 sample_idx: jnp.ndarray, k: int,
                 affine_params: Optional[dict], mode: str,
                 per_sample_norm: bool = False,
                 radius: Optional[float] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full local-grouper: sample -> KNN -> gather -> normalize -> concat.

    Args:
      xyz:   [B, N, 3] coordinates.
      feats: [B, N, C] features.
      sample_idx: [B, S] centroid indices (from FPS or URS).
      radius: None selects plain KNN; a float switches neighbor
        selection to ball query (radius + k cap; the ``ball`` grouper
        registry entry).

    Returns:
      new_xyz  [B, S, 3], centers' features [B, S, C],
      grouped  [B, S, k, 2C] (normalized neighbors ++ broadcast center),
      matching PointMLP's grouper output layout.
    """
    new_xyz = jnp.take_along_axis(xyz, sample_idx[..., None], axis=1)
    nbr_idx = neighbor_index(new_xyz, xyz, k, radius)         # [B, S, k]
    return group_with_idx(xyz, feats, sample_idx, nbr_idx, affine_params,
                          mode, per_sample_norm)
