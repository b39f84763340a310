"""KNN Pallas kernel — HLS4PC Fig. 2 adapted to TPU (see DESIGN.md §2).

The FPGA engine's X parallel *distance PEs* become grid programs over
tiles of query samples; the *distance buffer* becomes a VMEM tile
``[TILE_S, N]``; distance evaluation uses the MXU-friendly expansion
``‖s−p‖² = ‖s‖² − 2 s·pᵀ + ‖p‖²`` (one ``lax.dot``); and the paper's
selection-sort-style extraction — argmin, then overwrite the selected
entry with the numeric maximum — is kept verbatim, vectorized over the
whole sample tile (branch-free, VPU-friendly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tuning import resolve_interpret


def _knn_kernel(s_ref, p_ref, idx_ref, *, k: int, n_valid: int):
    s = s_ref[:].astype(jnp.float32)                     # [TS, C]
    p = p_ref[:].astype(jnp.float32)                     # [N, C]
    s2 = jnp.sum(s * s, axis=-1, keepdims=True)          # [TS, 1]
    p2 = jnp.sum(p * p, axis=-1)[None, :]                # [1, N]
    cross = jax.lax.dot(s, p.T, preferred_element_type=jnp.float32)
    d = s2 - 2.0 * cross + p2                            # [TS, N] dist buffer
    big = jnp.finfo(jnp.float32).max
    col = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    # mask out padding points (wrapper pads N up to the lane multiple)
    d = jnp.where(col < n_valid, d, big)

    # Column j of the output is written with a select against this iota
    # (Mosaic has no dynamic_update_slice).
    kcol = jax.lax.broadcasted_iota(jnp.int32, (d.shape[0], k), 1)

    def body(j, carry):
        dist, idx = carry
        am = jnp.argmin(dist, axis=1).astype(jnp.int32)  # [TS]
        idx = jnp.where(kcol == j, am[:, None], idx)
        # the paper's trick: selected entry := numeric max of the format
        dist = jnp.where(col == am[:, None], big, dist)
        return dist, idx

    idx0 = jnp.zeros((d.shape[0], k), jnp.int32)
    _, idx = jax.lax.fori_loop(0, k, body, (d, idx0))
    idx_ref[:] = idx


@functools.partial(jax.jit,
                   static_argnames=("k", "tile_s", "interpret"))
def knn_pallas(samples: jnp.ndarray, points: jnp.ndarray, k: int,
               tile_s: int = 128, interpret=None) -> jnp.ndarray:
    """[S, C], [N, C] -> [S, k] int32 (ascending distance order).

    ``interpret=None`` resolves from the platform (compiled on TPU,
    interpreter elsewhere); the lowering layer passes an explicit bool.
    """
    interpret = resolve_interpret(interpret)
    s, c = samples.shape
    n = points.shape[0]
    s_pad = -s % tile_s
    n_pad = -n % 128                      # lane alignment for the MXU
    sp = jnp.pad(samples, ((0, s_pad), (0, 0)))
    pp = jnp.pad(points, ((0, n_pad), (0, 0)))
    grid = ((s + s_pad) // tile_s,)
    out = pl.pallas_call(
        functools.partial(_knn_kernel, k=k, n_valid=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_s, c), lambda i: (i, 0)),
            pl.BlockSpec((n + n_pad, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_s, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s + s_pad, k), jnp.int32),
        interpret=interpret,
    )(sp, pp)
    return out[:s]
