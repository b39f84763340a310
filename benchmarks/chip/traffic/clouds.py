"""Synthetic point clouds for the benchmark's traffic.

A copy of the program's ``repro.data.pointclouds.make_batch`` /
``make_stream`` (8 parametric shape classes under random rigid
transforms, anisotropic scale and jitter), kept with the benchmark so
that the inputs cannot change when the program does.  Deterministic by
key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

N_CLASSES = 8


def _unit(key, n):
    return jax.random.uniform(key, (n,), minval=0.0, maxval=1.0)


def _shape_points(key, cls, n: int):
    k1, k2, k3 = jax.random.split(key, 3)
    u, v = _unit(k1, n), _unit(k2, n)
    two_pi = 2.0 * jnp.pi
    th, ph = two_pi * u, jnp.arccos(2.0 * v - 1.0)

    def sphere():
        return jnp.stack([jnp.sin(ph) * jnp.cos(th),
                          jnp.sin(ph) * jnp.sin(th), jnp.cos(ph)], -1)

    def cube():
        face = (jax.random.uniform(k3, (n,)) * 6).astype(jnp.int32)
        a, b = 2 * u - 1, 2 * v - 1
        one = jnp.ones_like(a)
        faces = jnp.stack([
            jnp.stack([one, a, b], -1), jnp.stack([-one, a, b], -1),
            jnp.stack([a, one, b], -1), jnp.stack([a, -one, b], -1),
            jnp.stack([a, b, one], -1), jnp.stack([a, b, -one], -1)], 0)
        return jnp.take_along_axis(faces, face[None, :, None], 0)[0]

    def cylinder():
        return jnp.stack([jnp.cos(th), jnp.sin(th), 2 * v - 1], -1)

    def cone():
        r = 1 - v
        return jnp.stack([r * jnp.cos(th), r * jnp.sin(th), 2 * v - 1], -1)

    def torus():
        r_min, ph2 = 0.35, two_pi * v
        return jnp.stack([(1 + r_min * jnp.cos(ph2)) * jnp.cos(th),
                          (1 + r_min * jnp.cos(ph2)) * jnp.sin(th),
                          r_min * jnp.sin(ph2)], -1)

    def pyramid():
        r = 1 - v
        sq_th = jnp.round(th / (jnp.pi / 2)) * (jnp.pi / 2)
        ang = 0.7 * sq_th + 0.3 * th
        return jnp.stack([r * jnp.cos(ang), r * jnp.sin(ang), 2 * v - 1], -1)

    def disk():
        r, ph2 = jnp.sqrt(u), two_pi * v
        return jnp.stack([r * jnp.cos(ph2), r * jnp.sin(ph2),
                          0.05 * (2 * u - 1)], -1)

    def helix():
        t = 4 * two_pi * u
        return jnp.stack([0.8 * jnp.cos(t), 0.8 * jnp.sin(t),
                          2 * u - 1 + 0.08 * jnp.sin(two_pi * v)], -1)

    return jax.lax.switch(cls, [sphere, cube, cylinder, cone, torus,
                                pyramid, disk, helix])


def _rotation_zyx(a):
    ca, sa = jnp.cos(a), jnp.sin(a)
    rz = jnp.array([[ca[0], -sa[0], 0], [sa[0], ca[0], 0], [0, 0, 1.0]])
    ry = jnp.array([[ca[1], 0, sa[1]], [0, 1.0, 0], [-sa[1], 0, ca[1]]])
    rx = jnp.array([[1.0, 0, 0], [0, ca[2], -sa[2]], [0, sa[2], ca[2]]])
    return rz @ ry @ rx


def _random_rotation(key):
    return _rotation_zyx(
        jax.random.uniform(key, (3,), minval=0, maxval=2 * jnp.pi))


@functools.partial(jax.jit, static_argnames=("n_points", "batch"))
def make_batch(key, n_points: int, batch: int):
    """[batch, n_points, 3] f32 clouds, each centred and scaled into the
    unit ball."""
    def one(k):
        kc, kp, kr, ks, kj = jax.random.split(k, 5)
        cls = jax.random.randint(kc, (), 0, N_CLASSES)
        pts = _shape_points(kp, cls, n_points)
        scale = jax.random.uniform(ks, (3,), minval=0.7, maxval=1.3)
        pts = (pts * scale) @ _random_rotation(kr).T
        pts = pts + 0.02 * jax.random.normal(kj, pts.shape)
        pts = pts - jnp.mean(pts, axis=0, keepdims=True)
        pts = pts / (jnp.max(jnp.linalg.norm(pts, axis=-1)) + 1e-6)
        return pts.astype(jnp.float32)

    return jax.vmap(one)(jax.random.split(key, batch))


@functools.partial(jax.jit, static_argnames=("n_points", "frames"))
def make_stream(key, n_points: int, frames: int, drift: float):
    """[frames, n_points, 3] f32: one rigid body over consecutive frames.

    Frame 0 is a normalized shape; each later frame applies a rigid
    motion with rotation angles and translation uniform in ``+-drift/2``
    plus ``0.1 * drift`` Gaussian jitter per point.
    """
    kc, kp, kr, ks, kmot = jax.random.split(key, 5)
    cls = jax.random.randint(kc, (), 0, N_CLASSES)
    pts = _shape_points(kp, cls, n_points)
    scale = jax.random.uniform(ks, (3,), minval=0.7, maxval=1.3)
    pts = (pts * scale) @ _random_rotation(kr).T
    pts = pts - jnp.mean(pts, axis=0, keepdims=True)
    pts = pts / (jnp.max(jnp.linalg.norm(pts, axis=-1)) + 1e-6)

    def step(cur, k):
        ka, kt, kj = jax.random.split(k, 3)
        ang = jax.random.uniform(ka, (3,), minval=-drift / 2,
                                 maxval=drift / 2)
        t = jax.random.uniform(kt, (3,), minval=-drift / 2, maxval=drift / 2)
        nxt = cur @ _rotation_zyx(ang).T + t
        nxt = nxt + 0.1 * drift * jax.random.normal(kj, cur.shape)
        return nxt, nxt

    _, rest = jax.lax.scan(step, pts, jax.random.split(kmot, frames - 1))
    return jnp.concatenate([pts[None], rest], axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_points", "frames",
                                             "sessions"))
def make_streams(key, n_points: int, frames: int, sessions: int,
                 drift: float):
    """[sessions, frames, n_points, 3]: one :func:`make_stream` per
    session, in one call."""
    return jax.vmap(lambda k: make_stream(k, n_points, frames, drift))(
        jax.random.split(key, sessions))
