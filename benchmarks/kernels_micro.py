"""Kernel micro-benchmarks: measured CPU wall-time (interpret-mode Pallas
vs jnp oracle) + derived TPU roofline time per call.

The CPU µs numbers are NOT TPU performance (interpret mode runs the
kernel body op-by-op); they are regression anchors.  The derived column
is the v5e roofline bound for the same call (what §Roofline predicts).
"""
from __future__ import annotations

import time
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp

from repro import roofline as RL
from repro.core import sampling
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _time(fn: Callable, *args, iters: int = 5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6      # us


def rows() -> List[Tuple[str, float, str]]:
    out = []
    k1, k2, k3 = jax.random.split(KEY, 3)

    # KNN: 512 samples x 1024 points (PointMLP-Lite stage-1 shape)
    s = jax.random.normal(k1, (256, 3))
    p = jax.random.normal(k2, (512, 3))
    us_pal = _time(lambda: ops.knn(s, p, 16))
    us_ref = _time(lambda: ref.knn_ref(s, p, 16))
    flops = 2 * 256 * 512 * 3
    t_tpu = flops / RL.PEAK_FLOPS + (256 * 512 * 4) / RL.HBM_BW
    out.append(("knn_pallas_256x512_k16", us_pal,
                f"ref={us_ref:.0f}us tpu_roofline={t_tpu*1e6:.1f}us"))

    # int8 matmul 512x512x512
    xq = jax.random.randint(k1, (512, 512), -128, 128, jnp.int8)
    wq = jax.random.randint(k2, (512, 512), -128, 128, jnp.int8)
    sc = jnp.ones((1, 512), jnp.float32) * 0.01
    from repro.kernels.int8_matmul import int8_matmul_pallas
    us_pal = _time(lambda: int8_matmul_pallas(xq, wq, sc))
    us_ref = _time(lambda: ref.int8_matmul_ref(xq, wq, sc))
    flops = 2 * 512 ** 3
    t_tpu = flops / RL.PEAK_INT8_OPS
    out.append(("int8_matmul_512^3", us_pal,
                f"ref={us_ref:.0f}us tpu_roofline={t_tpu*1e6:.1f}us"))

    # fused linear 1024x512x512 relu
    x = jax.random.normal(k1, (1024, 512))
    w = jax.random.normal(k2, (512, 512)) * 0.05
    b = jnp.zeros((512,))
    us_pal = _time(lambda: ops.fused_linear(x, w, b, "relu"))
    us_ref = _time(lambda: ref.fused_linear_ref(x, w, b, "relu"))
    out.append(("fused_linear_1024x512x512", us_pal, f"ref={us_ref:.0f}us"))

    # flash attention 4x8 heads x 512 x 64
    q = jax.random.normal(k1, (1, 8, 512, 64))
    kk = jax.random.normal(k2, (1, 2, 512, 64))
    v = jax.random.normal(k3, (1, 2, 512, 64))
    us_pal = _time(lambda: ops.flash_attention(q, kk, v), iters=2)
    us_ref = _time(lambda: ref.attention_ref(q, kk, v), iters=2)
    flops = 4 * 1 * 8 * 512 * 512 * 64
    t_tpu = flops / RL.PEAK_FLOPS
    out.append(("flash_attn_8h_512_64", us_pal,
                f"ref={us_ref:.0f}us tpu_roofline={t_tpu*1e6:.1f}us"))

    # LFSR URS vs FPS (the paper's core swap) at PointMLP-Lite scale
    pts = jax.random.normal(k1, (512, 3))
    st = sampling.seed_streams(0, 64)
    us_urs = _time(lambda: sampling.urs_indices(st, 512, 256)[1])
    us_fps = _time(lambda: sampling.fps(pts, 256))
    out.append(("urs_lfsr_512->256", us_urs, f"fps={us_fps:.0f}us "
                f"speedup={us_fps/max(us_urs,1e-9):.0f}x"))
    return out
