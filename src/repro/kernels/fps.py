"""FPS Pallas kernel (the baseline sampler HLS4PC replaces).

FPS is inherently sequential over samples, but each iteration's hot loop —
fold the distance-to-the-last-centroid into the running minimum over all N
points — is data-parallel.  :func:`fps_walk_pallas` runs a whole walk in
one kernel invocation per cloud: the coordinates and the running minimum
stay in VMEM for every step, so a step costs a few vector ops and
reductions, not a trip round a device loop.  The ``fps`` sampler uses it
on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tuning import resolve_interpret

_LANES = 128
_SUBLANES = 8


def _fps_walk_kernel(p_ref, o_ref, *, n_valid: int, n_samples: int):
    """One cloud's walk.  ``p_ref`` [C, R, 128]: each coordinate laid out
    lane-dense, point ``r * 128 + l`` at ``[r, l]``; ``o_ref`` [RS, 128]
    int32 receives sample ``i`` at flat position ``i``."""
    c, rows, lanes = p_ref.shape
    flat = (jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) * lanes
            + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))
    pos = flat.astype(jnp.float32)        # exact: N is far below 2**24
    out_rows = o_ref.shape[0]
    slot = (jax.lax.broadcasted_iota(jnp.int32, (out_rows, lanes), 0) * lanes
            + jax.lax.broadcasted_iota(jnp.int32, (out_rows, lanes), 1))
    coords = [p_ref[j] for j in range(c)]

    def body(i, carry):
        dists, last, picked = carry
        d = None
        for x, cx in zip(coords, last):       # ((dx*dx + dy*dy) + dz*dz)
            t = x - cx
            d = t * t if d is None else d + t * t
        dists = jnp.minimum(dists, d)
        # argmax with jnp.argmax's rule: the lowest index among the maxima
        m = jnp.max(dists)
        nxt = jnp.min(jnp.where(dists == m, pos, jnp.inf))
        # the new centroid's coordinates, by a masked reduction
        hit = pos == nxt
        last = tuple(jnp.sum(jnp.where(hit, x, 0.0)) for x in coords)
        picked = jnp.where(slot == i, nxt, picked)
        return dists, last, picked

    # Padded points start at -inf, so no step can pick one.
    dists0 = jnp.where(flat < n_valid, jnp.inf, -jnp.inf)
    last0 = tuple(x[0, 0] for x in coords)            # the walk starts at 0
    picked0 = jnp.zeros((out_rows, lanes), jnp.float32)
    _, _, picked = jax.lax.fori_loop(1, n_samples, body,
                                     (dists0, last0, picked0))
    o_ref[:] = picked.astype(jnp.int32)


def _tile_rows(n: int) -> int:
    """Rows of 128 lanes holding ``n`` values, in whole (8, 128) tiles."""
    return _SUBLANES * pl.cdiv(n, _SUBLANES * _LANES)


def fps_walk_pallas(points: jnp.ndarray, n_samples: int,
                    interpret=None) -> jnp.ndarray:
    """Whole FPS walks, one kernel program per cloud.  [B, N, C] ->
    [B, n_samples] int32, equal to ``sampling.fps`` per cloud (start at
    point 0, ties to the lowest index).  ``interpret=None`` resolves from
    the platform (compiled on TPU, interpreter elsewhere) on every call,
    before the jit cache is keyed on it."""
    return _fps_walk(points, n_samples, resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("n_samples", "interpret"))
def _fps_walk(points: jnp.ndarray, n_samples: int,
              interpret: bool) -> jnp.ndarray:
    b, n, c = points.shape
    rows, out_rows = _tile_rows(n), _tile_rows(n_samples)
    pt = jnp.swapaxes(points.astype(jnp.float32), 1, 2)       # [B, C, N]
    pt = jnp.pad(pt, ((0, 0), (0, 0), (0, rows * _LANES - n)))
    pt = pt.reshape(b, c, rows, _LANES)
    out = pl.pallas_call(
        functools.partial(_fps_walk_kernel, n_valid=n, n_samples=n_samples),
        grid=(b,),
        in_specs=[pl.BlockSpec((None, c, rows, _LANES),
                               lambda i: (i, 0, 0, 0))],
        out_specs=pl.BlockSpec((None, out_rows, _LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, out_rows, _LANES), jnp.int32),
        interpret=interpret,
        name="fps_walk",
    )(pt)
    return out.reshape(b, out_rows * _LANES)[:, :n_samples]
