"""Shared NN layers (pure JAX, pytree params).

Conventions:
  * every layer is an ``init(key, ...) -> params`` + ``apply(params, x)``
    pair; params are plain dicts so the fusion/quantization tree
    transforms in ``repro.core`` apply uniformly.
  * matmul weights are stored ``[d_in, d_out]`` under key ``"w"`` —
    the key the quantizer recognizes.
  * a ``dense_apply`` weight may have been replaced by an int8 export
    dict ``{"q", "scale"}``; the apply functions dispatch on that.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.quant import (QuantConfig, fake_quant_act, fake_quant_weight)
from repro.core.fusion import batchnorm_apply, batchnorm_init


# ------------------------------------------------------------ dense -----

def dense_init(key, d_in: int, d_out: int, bias: bool = True,
               dtype=jnp.float32, scale: Optional[float] = None) -> Dict:
    std = scale if scale is not None else (1.0 / math.sqrt(d_in))
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def _matmul(x: jnp.ndarray, w, quant: Optional[QuantConfig]) -> jnp.ndarray:
    """Dispatch: fp matmul, QAT fake-quant matmul, or int8 export matmul."""
    if isinstance(w, dict):  # int8 export {"q","scale"}
        backend = quant.backend if quant is not None else "int8_ref"
        if backend == "int8_pallas":
            from repro.kernels import ops as kops
            return kops.int8_matmul(x, w["q"], w["scale"],
                                    a_bits=quant.a_bits,
                                    interpret=quant.interpret)
        # W8 reference path: dequantized weight matmul (W8A16/W8A32).
        wd = (w["q"].astype(x.dtype) * w["scale"].astype(x.dtype))
        return x @ wd
    if quant is not None and quant.enabled:
        w = fake_quant_weight(w, quant)
        x = fake_quant_act(x, quant)
    return x @ w.astype(x.dtype)


def dense_apply(p: Dict, x: jnp.ndarray,
                quant: Optional[QuantConfig] = None) -> jnp.ndarray:
    y = _matmul(x, p["w"], quant)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ------------------------------------------- conv1d (pointwise + k>1) ---

def conv1d_init(key, c_in: int, c_out: int, ksize: int = 1,
                bias: bool = True, bn: bool = False,
                dtype=jnp.float32) -> Dict:
    """PointMLP's layers are 1x1 conv1d == pointwise linear; whisper's
    frontend uses k=3.  Weight layout [ksize, c_in, c_out] (k=1 squeezed to
    [c_in, c_out] so the fusion/quant transforms see a matmul weight)."""
    std = 1.0 / math.sqrt(c_in * ksize)
    shape = (c_in, c_out) if ksize == 1 else (ksize, c_in, c_out)
    p = {"w": (jax.random.normal(key, shape) * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((c_out,), dtype)
    if bn:
        p["bn"] = batchnorm_init(c_out)
    return p


def conv1d_apply(p: Dict, x: jnp.ndarray, stride: int = 1,
                 quant: Optional[QuantConfig] = None,
                 bn_eps: float = 1e-5) -> jnp.ndarray:
    """x: [..., T, C_in] -> [..., T', C_out]. BN (if present and unfused)
    is applied inference-mode after the conv."""
    w = p["w"]
    if isinstance(w, dict) or w.ndim == 2:   # pointwise (possibly int8)
        y = _matmul(x, w, quant)
        if stride > 1:
            y = y[..., ::stride, :]
    else:
        lhs = x[None] if x.ndim == 2 else x
        y = jax.lax.conv_general_dilated(
            lhs.astype(w.dtype), w, window_strides=(stride,),
            padding="SAME", dimension_numbers=("NWC", "WIO", "NWC"))
        if x.ndim == 2:
            y = y[0]
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    if "bn" in p:
        y = batchnorm_apply(y, p["bn"], bn_eps).astype(y.dtype)
    return y


# ------------------------------------------------------------- norms ----

def rmsnorm_init(d: int, dtype=jnp.float32) -> Dict:
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm_apply(p: Dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * p["g"].astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.float32) -> Dict:
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layernorm_apply(p: Dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * p["g"].astype(x.dtype) + p["b"].astype(x.dtype)


# ------------------------------------------------------------- embed ----

def embedding_init(key, vocab: int, d: int, dtype=jnp.float32) -> Dict:
    return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def embedding_apply(p: Dict, ids: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["table"], ids, axis=0)


def unembed_apply(p: Dict, x: jnp.ndarray) -> jnp.ndarray:
    """Tied unembedding: logits = x @ table.T (f32 for stability)."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      p["table"].astype(jnp.float32))


# -------------------------------------------------------------- rope ----

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """x: [..., T, D]; positions broadcastable to [..., T] (right-aligned,
    e.g. positions [T] against x [B, H, T, D])."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, D/2]
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# -------------------------------------------------------- activations ---

def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu_init(key, d: int, d_ff: int, dtype=jnp.float32) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense_init(k1, d, d_ff, bias=False, dtype=dtype),
        "up": dense_init(k2, d, d_ff, bias=False, dtype=dtype),
        "down": dense_init(k3, d_ff, d, bias=False, dtype=dtype),
    }


def swiglu_apply(p: Dict, x: jnp.ndarray,
                 quant: Optional[QuantConfig] = None) -> jnp.ndarray:
    g = dense_apply(p["gate"], x, quant)
    u = dense_apply(p["up"], x, quant)
    return dense_apply(p["down"], silu(g) * u, quant)


def scan_blocks(f, init, xs, cfg, length=None):
    """Layer-stack scan; fully unrolled when ``cfg.unroll_layers`` (dry-run
    cost-analysis fidelity — see ModelConfig.unroll_layers)."""
    return jax.lax.scan(f, init, xs, length=length,
                        unroll=True if cfg.unroll_layers else 1)


def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray
                          ) -> jnp.ndarray:
    """logits [..., V], labels [...] int32 -> scalar mean loss.

    Label logit extraction uses an iota-compare + masked reduce instead of
    ``take_along_axis`` so a vocab-sharded logits tensor never gets
    all-gathered (a ~16x activation-memory blowup at 32k seq)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    logits.ndim - 1)
    ll = jnp.sum(jnp.where(iota == labels[..., None], logits, 0.0), axis=-1)
    return jnp.mean(lse - ll)
