"""A toy part-segmentation reference, for the harness's tests only.

It has the shape of what a segmentation configuration brings, at a size
the CPU runs at once: each request is two arrays, points ``[N, 6]``
(xyz and normals) and an object category, and each answer is per point,
``[N, parts]``.  Its one decision is each point's nearest centre (the
first ``n_centres`` points) by xyz, so a cache is that index per point.
It provides the contract ``harness.CONTRACT`` names, and nothing more.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from work import Layer


def _features(c: Dict) -> int:
    return 6 + 6 + c["n_categories"]


def make_pool(key, c: Dict, size: int):
    kp, kc = jax.random.split(key)
    points = jax.random.normal(kp, (size, c["n_points"], 6), jnp.float32)
    category = jax.random.randint(kc, (size,), 0, c["n_categories"],
                                  jnp.int32)
    return points, category


def deploy_params(key, c: Dict, bits=None) -> Dict:
    f = _features(c)
    return {"w": jax.random.normal(key, (f, c["n_parts"])) / np.sqrt(f),
            "b": jnp.zeros((c["n_parts"],), jnp.float32)}


def _nearest(xyz: np.ndarray, n_centres: int) -> np.ndarray:
    d = np.sum((xyz[:, None, :] - xyz[None, :n_centres, :]) ** 2, -1)
    return np.argmin(d, axis=1).astype(np.int32)


def forward(params: Dict, c: Dict, inputs, *, lfsr_seed: int, mode: str,
            bits=None, cache=None):
    """Per point: its features, its centre's and the category's one-hot,
    through one linear layer."""
    points, category = (np.asarray(a) for a in inputs)
    if cache is None:
        cache = np.stack([_nearest(p[:, :3].astype(np.float32),
                                   c["n_centres"]) for p in points])
    centres = np.take_along_axis(points, cache[..., None], axis=1)
    onehot = np.broadcast_to(
        np.eye(c["n_categories"], dtype=np.float32)[category][:, None, :],
        points.shape[:2] + (c["n_categories"],))
    feats = jnp.asarray(np.concatenate([points, centres, onehot], -1))
    precision = (jax.lax.Precision.HIGHEST if mode == "highest"
                 else jax.lax.Precision.DEFAULT)
    answers = jnp.matmul(feats, params["w"], precision=precision)
    return np.asarray(answers + params["b"]), cache


def decision_paths(c: Dict, deciders, lfsr_seed: int, rnd
                   ) -> List[List[np.ndarray]]:
    """One path per request: the nearest centres in float64."""
    points, _ = deciders
    return [[_nearest(p[:, :3].astype(np.float64), c["n_centres"])]
            for p in points]


def cbr_layers(c: Dict) -> List[Layer]:
    return [Layer("point", c["n_points"], _features(c), c["n_parts"])]


def mapping_flops(c: Dict) -> int:
    return 2 * c["n_points"] * c["n_centres"] * 3
