"""Quantization: QAT fake-quant (STE), calibration, int8 export (HLS4PC §2.2, Fig. 4).

The paper uses Brevitas-style quantization-aware training at W/A
precisions swept over {4..32} bits, finding 8/8 Pareto-optimal, then
exports fused fixed-point parameters for the FPGA.  TPU adaptation: the
MXU natively multiplies int8 operands into int32 accumulators, so the
same compression gives ~2x compute and ~4x weight-byte savings.  The
export path produces int8 weight trees + per-channel scales consumed by
``repro.kernels.int8_matmul``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Compile-time quantization parametrization (the HLS4PC analogue of
    per-layer precision parameters)."""
    w_bits: int = 8
    a_bits: int = 8
    per_channel: bool = True        # per-out-channel weight scales
    symmetric: bool = True
    # matmul implementation: fake (QAT), int8_ref (jnp int8), int8_pallas
    backend: str = "fake"
    # int8_pallas only: interpret-mode flag, bound at lowering time from
    # the stage backend (None = platform-resolved).
    interpret: Optional[bool] = None

    @property
    def enabled(self) -> bool:
        return self.w_bits < 32 or self.a_bits < 32


def qrange(bits: int) -> Tuple[int, int]:
    return -(2 ** (bits - 1)), 2 ** (bits - 1) - 1


def compute_scale(x: jnp.ndarray, bits: int, axis: Optional[int] = None
                  ) -> jnp.ndarray:
    """Symmetric absmax scale. ``axis`` keeps that axis (per-channel)."""
    qmax = 2 ** (bits - 1) - 1
    if axis is None:
        amax = jnp.max(jnp.abs(x))
    else:
        red = tuple(i for i in range(x.ndim) if i != axis)
        amax = jnp.max(jnp.abs(x), axis=red, keepdims=True)
    return jnp.maximum(amax, 1e-8) / qmax


def quantize(x: jnp.ndarray, scale: jnp.ndarray, bits: int) -> jnp.ndarray:
    qmin, qmax = qrange(bits)
    return jnp.clip(jnp.round(x / scale), qmin, qmax)


def fake_quant(x: jnp.ndarray, bits: int, axis: Optional[int] = None
               ) -> jnp.ndarray:
    """Quantize-dequantize with a straight-through estimator.

    Forward: round-to-scale; backward: identity (STE), the standard QAT
    trick the paper uses via Brevitas.
    """
    if bits >= 32:
        return x
    scale = jax.lax.stop_gradient(compute_scale(x, bits, axis))
    q = quantize(x, scale, bits) * scale
    return x + jax.lax.stop_gradient(q - x)


def weight_scale(w: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Per-out-channel scale for a (possibly layer-stacked) matmul weight
    [..., d_in, d_out]: reduce ONLY the contraction dim, keeping stack
    dims (each layer gets its own scales — required for scan-over-layers
    and strictly better quantization)."""
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    return jnp.maximum(amax, 1e-8) / qmax


def fake_quant_weight(w: jnp.ndarray, cfg: QuantConfig) -> jnp.ndarray:
    """Weights are [..., d_in, d_out]; per-channel over the out axis."""
    if cfg.w_bits >= 32:
        return w
    if not cfg.per_channel:
        return fake_quant(w, cfg.w_bits, None)
    scale = jax.lax.stop_gradient(weight_scale(w, cfg.w_bits))
    q = quantize(w, scale, cfg.w_bits) * scale
    return w + jax.lax.stop_gradient(q - w)


def fake_quant_act(x: jnp.ndarray, cfg: QuantConfig) -> jnp.ndarray:
    return fake_quant(x, cfg.a_bits, axis=None)


# ------------------------------------------------------------ export ----

def quantize_weight_int8(w: jnp.ndarray, cfg: QuantConfig
                         ) -> Dict[str, jnp.ndarray]:
    """Export one weight to {q: int8[...], scale: f32[..., 1, d_out]}.
    Stack dims (scan-over-layers) keep their own scales."""
    assert cfg.w_bits <= 8, "int8 export path requires w_bits <= 8"
    if cfg.per_channel and w.ndim >= 2:
        scale = weight_scale(w, cfg.w_bits)
    else:
        scale = compute_scale(w, cfg.w_bits, None)
    q = quantize(w, scale, cfg.w_bits).astype(jnp.int8)
    return {"q": q, "scale": scale.astype(jnp.float32)}


def is_quantizable_leaf_path(path: tuple) -> bool:
    """Heuristic over param-tree key paths: quantize matmul weights only
    (named 'w' / 'kernel' / '*_w'), never norms, biases or embeddings."""
    last = str(path[-1].key) if hasattr(path[-1], "key") else str(path[-1])
    return last == "w" or last == "kernel" or last.endswith("_w")


def quantize_tree(params: Any, cfg: QuantConfig,
                  predicate: Callable[[tuple, jnp.ndarray], bool] = None
                  ) -> Any:
    """Walk a param pytree; replace each quantizable weight leaf with the
    int8 export dict.  Everything else passes through unchanged."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    out = []
    for path, leaf in flat:
        take = (predicate(path, leaf) if predicate
                else (is_quantizable_leaf_path(path) and leaf.ndim >= 2))
        out.append(quantize_weight_int8(leaf, cfg) if take else leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def dequantize_tree(qparams: Any) -> Any:
    """Inverse of :func:`quantize_tree` (for testing round-trip error)."""
    def fix(node):
        if isinstance(node, dict) and set(node) == {"q", "scale"}:
            return node["q"].astype(jnp.float32) * node["scale"]
        return node
    return _map_dicts(qparams, fix)


def _map_dicts(tree, fn):
    tree = fn(tree)
    if isinstance(tree, dict):
        return {k: _map_dicts(v, fn) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_map_dicts(v, fn) for v in tree)
    return tree


def tree_size_bytes(params: Any) -> int:
    """Model size in bytes (the x-axis of the paper's Fig. 4)."""
    leaves = jax.tree_util.tree_leaves(params)
    return int(sum(x.size * x.dtype.itemsize for x in leaves))


# --------------------------------------------- stochastic rounding -------

def stochastic_round_int8(x: jnp.ndarray, scale: jnp.ndarray,
                          rand_bits: jnp.ndarray) -> jnp.ndarray:
    """LFSR-driven stochastic rounding to int8 (used by gradient
    compression — the paper's fixed-point + LFSR insights combined).

    rand_bits: uint32 uniform bits, same shape as x."""
    y = x / scale
    frac = y - jnp.floor(y)
    u = (rand_bits.astype(jnp.float32) + 0.5) / 4294967296.0
    q = jnp.floor(y) + (u < frac).astype(y.dtype)
    return jnp.clip(q, -128, 127).astype(jnp.int8)
