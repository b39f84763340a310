"""Public jit'd wrappers for the Pallas kernels.

Every kernel resolves ``interpret=None`` from the platform
(``repro.kernels.tuning.resolve_interpret``): compiled to Mosaic on a
TPU, interpreted (and validated against ``ref.py``) elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quant import compute_scale, quantize
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_linear import fused_linear_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas, w8_matmul_pallas
from repro.kernels.knn import knn_pallas


def knn(samples: jnp.ndarray, points: jnp.ndarray, k: int) -> jnp.ndarray:
    return knn_pallas(samples, points, k)


def knn_batched(samples: jnp.ndarray, points: jnp.ndarray, k: int
                ) -> jnp.ndarray:
    return jax.vmap(lambda s, p: knn(s, p, k))(samples, points)


def int8_matmul(x: jnp.ndarray, w_q: jnp.ndarray, w_scale: jnp.ndarray,
                a_bits: int = 8, interpret=None) -> jnp.ndarray:
    """Quantize activations on the fly (A8) and run the int8 kernel.
    Combined dequant scale = act_scale * weight_scale.  ``interpret``
    defaults to the platform resolution."""
    a_scale = compute_scale(x, a_bits)
    x_q = quantize(x, a_scale, a_bits).astype(jnp.int8)
    scale = (a_scale * w_scale.reshape(1, -1)).astype(jnp.float32)
    lead = x.shape[:-1]
    y = int8_matmul_pallas(x_q.reshape(-1, x.shape[-1]), w_q, scale,
                           out_dtype=jnp.float32, interpret=interpret)
    return y.reshape(*lead, w_q.shape[1]).astype(x.dtype)


def w8_matmul(x: jnp.ndarray, w_q: jnp.ndarray, w_scale: jnp.ndarray
              ) -> jnp.ndarray:
    lead = x.shape[:-1]
    y = w8_matmul_pallas(x.reshape(-1, x.shape[-1]), w_q,
                         w_scale.reshape(1, -1))
    return y.reshape(*lead, w_q.shape[1])


def fused_linear(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 activation: str = "relu") -> jnp.ndarray:
    lead = x.shape[:-1]
    y = fused_linear_pallas(x.reshape(-1, x.shape[-1]), w, b,
                            activation=activation)
    return y.reshape(*lead, w.shape[1])


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    tq: int = 128, tk: int = 128) -> jnp.ndarray:
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  tq=tq, tk=tk)
