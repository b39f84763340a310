"""Production mesh builders.

Single pod: (data=16, model=16) — 256 chips (one v5e pod's worth for the
assignment). Multi-pod: (pod=2, data=16, model=16) — 512 chips; the
``pod`` axis composes with ``data`` for batch sharding, so gradient
all-reduce crosses the inter-pod links (where the int8 gradient
compression of ``repro.train.grad_compress`` pays).

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS *before* any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: its default is Explicit axes,
    which ``with_sharding_constraint`` (``repro.sharding.rules``)
    refuses."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host has (tests / examples): 1-D data mesh."""
    n = len(jax.devices())
    return _auto_mesh((n,), ("data",))


def batch_axes(mesh) -> tuple:
    """Mesh axes a global batch dimension shards over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh):
    return "model" if "model" in mesh.axis_names else None
