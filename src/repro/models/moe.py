"""Mixture-of-Experts FFN: sort-based capacity dispatch, expert parallel.

Design (DESIGN.md §5.3): no ``[T, E, C]`` one-hot dispatch tensors (they
OOM at 32k sequence). Instead:

  router top-k  ->  flatten (token, slot) entries  ->  stable argsort by
  expert id  ->  rank-within-expert via running offsets  ->  scatter into
  a ``[E, C, d]`` buffer  ->  batched expert SwiGLU (einsum over E)  ->
  gather back, weighted combine.  Entries beyond expert capacity are
  dropped (standard capacity-factor semantics; the residual path carries
  the token).

The ``[E, ...]`` buffers shard over the ``model`` mesh axis (expert
parallelism); XLA lowers the scatter/gather to all-to-alls, which is why
the MoE train cells are the collective-bound rows of the roofline table.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def moe_init(key, cfg: ModelConfig) -> Dict:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    kr, kg, ku, kd = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    std = 1.0 / math.sqrt(d)
    return {
        "router": {"w": (jax.random.normal(kr, (d, e)) * std
                         ).astype(jnp.float32)},
        "gate_w": (jax.random.normal(kg, (e, d, f)) * std).astype(dt),
        "up_w": (jax.random.normal(ku, (e, d, f)) * std).astype(dt),
        "down_w": (jax.random.normal(kd, (e, f, d)) /
                   math.sqrt(f)).astype(dt),
    }


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(math.ceil(n_tokens * cfg.experts_per_token *
                      cfg.capacity_factor / cfg.n_experts))
    return max(8, -(-c // 8) * 8)   # pad to a multiple of 8


def moe_apply(p: Dict, cfg: ModelConfig, x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if cfg.sharding_profile.startswith("moe_local"):
        from repro.sharding.context import current_mesh
        mesh = current_mesh()
        if mesh is not None and "model" in mesh.axis_names:
            return moe_apply_local(p, cfg, x, mesh)
    return moe_apply_global(p, cfg, x)


def moe_apply_global(p: Dict, cfg: ModelConfig, x: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [B, T, d] -> (out [B, T, d], aux_loss scalar).

    aux_loss is the standard load-balancing loss (mean fraction-routed x
    mean router-prob per expert, scaled by E)."""
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    n = b * t
    c = capacity(cfg, n)
    xf = x.reshape(n, d)

    logits = (xf.astype(jnp.float32) @ p["router"]["w"])        # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                      # [N, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)      # renorm

    # ---- load-balance auxiliary loss (Switch-style) ----
    frac_routed = jnp.mean(
        jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_routed * mean_prob)

    # ---- sort-based dispatch ----
    flat_e = top_e.reshape(n * k)                               # entry -> expert
    flat_w = top_p.reshape(n * k).astype(x.dtype)
    order = jnp.argsort(flat_e, stable=True)                    # entries by expert
    sorted_e = flat_e[order]
    counts = jax.ops.segment_sum(jnp.ones_like(flat_e), flat_e,
                                 num_segments=e)                # [E]
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(n * k) - starts[sorted_e]                 # pos within expert
    keep = rank < c
    dest = jnp.where(keep, sorted_e * c + rank, e * c)          # drop slot at end
    src_tok = order // k                                        # entry -> token

    buf = jnp.zeros((e * c + 1, d), x.dtype)
    buf = buf.at[dest].set(xf[src_tok] * keep[:, None].astype(x.dtype))
    hb = buf[:-1].reshape(e, c, d)

    # ---- batched expert SwiGLU (E sharded over `model`) ----
    g = jnp.einsum("ecd,edf->ecf", hb, p["gate_w"])
    u = jnp.einsum("ecd,edf->ecf", hb, p["up_w"])
    yb = jnp.einsum("ecf,efd->ecd", L.silu(g) * u, p["down_w"])

    # ---- combine ----
    y_flat = yb.reshape(e * c, d)
    y_entries = jnp.where(keep[:, None], y_flat[jnp.clip(dest, 0, e * c - 1)],
                          0.0)
    out = jnp.zeros((n, d), x.dtype).at[src_tok].add(
        y_entries * flat_w[order][:, None])
    return out.reshape(b, t, d), aux


# ------------------------------------------------ shard_map local MoE ----
#
# §Perf iteration (EXPERIMENTS.md): the GSPMD lowering of the global
# sort-based dispatch scatters into an [E·C, d] buffer, which the
# partitioner realizes as a full-buffer masked all-reduce — 17.4 TB/device
# of wire per moonshot train step.  The manual form below keeps *all*
# routing local to each data shard: tokens never move; only (a) the
# expert-parallel buffer blocks implicitly laid out by the out_specs and
# (b) ONE per-layer activation psum over `model` touch the interconnect.

def _dispatch_local(xf, top_e, top_p, *, e_local: int, cap: int, dtype):
    """Per-device dispatch. xf [T_loc, d]; returns (buf [E_loc, cap, d],
    src [E_loc, cap] token idx or -1, wgt [E_loc, cap])."""
    m = jax.lax.axis_index("model")
    t_loc, d = xf.shape
    k = top_e.shape[-1]
    e_lo = m.astype(jnp.int32) * e_local
    flat_e = top_e.reshape(t_loc * k)
    flat_w = top_p.reshape(t_loc * k)
    mine = (flat_e >= e_lo) & (flat_e < e_lo + e_local)
    e_loc = jnp.where(mine, flat_e - e_lo, e_local)      # e_local = drop
    order = jnp.argsort(e_loc, stable=True)
    sorted_e = e_loc[order]
    counts = jax.ops.segment_sum(jnp.ones_like(e_loc), e_loc,
                                 num_segments=e_local + 1)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(t_loc * k) - starts[sorted_e]
    keep = (sorted_e < e_local) & (rank < cap)
    dest = jnp.where(keep, sorted_e * cap + rank, e_local * cap)
    src_tok = order // k
    buf = jnp.zeros((e_local * cap + 1, d), dtype)
    buf = buf.at[dest].set(xf[src_tok] * keep[:, None].astype(dtype))
    src = jnp.full((e_local * cap + 1,), -1, jnp.int32)
    src = src.at[dest].set(jnp.where(keep, src_tok, -1))
    wgt = jnp.zeros((e_local * cap + 1,), jnp.float32)
    wgt = wgt.at[dest].set(flat_w[order] * keep)
    return (buf[:-1].reshape(e_local, cap, d),
            src[:-1].reshape(e_local, cap),
            wgt[:-1].reshape(e_local, cap))


def _combine_local(y_buf, src, wgt, *, t_loc: int, dtype):
    """Inverse: scatter-add my expert outputs back to my tokens, then
    psum partial token outputs over the expert-parallel axis."""
    e_local, cap, d = y_buf.shape
    fy = y_buf.reshape(e_local * cap, d).astype(jnp.float32)
    fs = src.reshape(-1)
    fw = wgt.reshape(-1)
    valid = (fs >= 0).astype(jnp.float32)
    y = jnp.zeros((t_loc, d), jnp.float32)
    y = y.at[jnp.clip(fs, 0, t_loc - 1)].add(fy * (fw * valid)[:, None])
    return jax.lax.psum(y, "model").astype(dtype)


def moe_apply_local(p: Dict, cfg: ModelConfig, x: jnp.ndarray, mesh
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE with data-local routing (see note above)."""
    from jax.sharding import PartitionSpec as P
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    model_size = mesh.shape.get("model", 1) if hasattr(mesh.shape, "get") \
        else mesh.shape["model"]
    assert e % model_size == 0, "experts must divide the model axis"
    e_local = e // model_size
    n_tok = b * t
    t_loc = n_tok // n_dp
    cap = max(8, -(-int(t_loc * k / e * cfg.capacity_factor) // 8) * 8)

    xf = x.reshape(n_tok, d)
    logits = (xf.astype(jnp.float32) @ p["router"]["w"])       # local op
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = (top_p / jnp.sum(top_p, axis=-1, keepdims=True))
    frac = jnp.mean(jax.nn.one_hot(top_e, e, dtype=jnp.float32),
                    axis=(0, 1))
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))

    dispatch = jax.shard_map(
        functools.partial(_dispatch_local, e_local=e_local, cap=cap,
                          dtype=x.dtype),
        mesh=mesh,
        in_specs=(P(dp, None), P(dp, None), P(dp, None)),
        out_specs=(P("model", dp, None), P("model", dp), P("model", dp)),
        check_vma=False)
    buf, src, wgt = dispatch(xf, top_e.astype(jnp.int32),
                             top_p.astype(jnp.float32))
    # buf global: [E, n_dp*cap, d] sharded (model, dp, -): expert matmuls
    # are fully local under GSPMD (E and C both sharded, d contraction)
    g = jnp.einsum("ecd,edf->ecf", buf, p["gate_w"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["up_w"])
    yb = jnp.einsum("ecf,efd->ecd", L.silu(g) * u, p["down_w"])

    combine = jax.shard_map(
        functools.partial(_combine_local, t_loc=t_loc, dtype=x.dtype),
        mesh=mesh,
        in_specs=(P("model", dp, None), P("model", dp), P("model", dp)),
        out_specs=P(dp, None), check_vma=False)
    out = combine(yb, src, wgt)
    return out.reshape(b, t, d), aux
