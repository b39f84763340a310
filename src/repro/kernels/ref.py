"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``*_ref`` is the mathematical definition the kernel must match
(``tests/test_kernels.py`` sweeps shapes/dtypes and asserts allclose in
interpret mode).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.knn import knn_select, pairwise_sqdist


def knn_ref(samples: jnp.ndarray, points: jnp.ndarray, k: int) -> jnp.ndarray:
    """[S, C], [N, C] -> [S, k] ascending-distance neighbor indices."""
    return knn_select(pairwise_sqdist(samples, points), k)


def int8_matmul_ref(x_q: jnp.ndarray, w_q: jnp.ndarray,
                    scale: jnp.ndarray, out_dtype=jnp.float32) -> jnp.ndarray:
    """int8[M,K] @ int8[K,N] -> int32 accum, dequantized by scale [1,N] or
    scalar (combined activation*weight scale)."""
    acc = jax.lax.dot(x_q.astype(jnp.int32), w_q.astype(jnp.int32),
                      preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * scale.astype(jnp.float32)).astype(out_dtype)


def w8_matmul_ref(x: jnp.ndarray, w_q: jnp.ndarray, w_scale: jnp.ndarray
                  ) -> jnp.ndarray:
    """Weight-only int8 (W8A16): dequantize-then-matmul oracle."""
    w = w_q.astype(x.dtype) * w_scale.astype(x.dtype)
    return x @ w


def fused_linear_ref(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                     activation: str = "relu") -> jnp.ndarray:
    """Fused (post-BN-fold) linear + bias + activation."""
    y = x @ w + b
    if activation == "relu":
        return jax.nn.relu(y)
    if activation == "gelu":
        return jax.nn.gelu(y)
    if activation == "none":
        return y
    raise ValueError(activation)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True,
                  sliding_window: int = 0) -> jnp.ndarray:
    """[B,H,Tq,D], [B,Hkv,Tk,D] GQA attention oracle (f32 softmax)."""
    b, h, tq, d = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(d).astype(jnp.float32)
    tk = k.shape[2]
    qpos = jnp.arange(tq)[:, None] + (tk - tq)
    kpos = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= kpos <= qpos
    if sliding_window > 0:
        mask &= kpos > qpos - sliding_window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)
