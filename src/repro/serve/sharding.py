"""Data-parallel sharded dispatch: split a batch over a 1-D device mesh.

The software analogue of HLS4PC's multi-PE unrolling (and of PointAcc's
accelerator array): one fixed-shape dispatch of ``max_batch`` lanes is
physically split ``max_batch // data_shards`` lanes per device with a
``shard_map`` over a ``("data",)`` mesh, params replicated.  Because the
serving walk is lane-mapped (``repro.models.pointmlp``: under serving
semantics every lane runs a fixed-shape single-cloud executable), the
split is *bit-identical* to the single-device dispatch — sharding is
purely a throughput decision, invisible to results, so both serving
engines accept a sharded :class:`~repro.api.build.FrozenPipeline`
with zero scheduler changes.

LFSR placement follows the sampler semantics:

* ``shared_urs`` (serving specs): one index sequence serves every lane,
  so the state is *replicated* — each device reads stream 0, advances
  the full state identically, and the advanced state stays replicated.
* per-lane URS (``shared_urs=False``): lane ``b`` consumes stream
  ``b``, so the streams are *split* with the lanes — which requires
  exactly one stream per lane (state length == batch), checked at
  trace time.

``per_sample_norm`` is required either way: batch-statistic
normalization couples lanes across the dispatch, which a device split
would silently turn into shard-local statistics.

``repro.sharding.context.use_mesh`` is installed around the dispatch so
model code stays mesh-agnostic (anything consulting ``current_mesh()``
sees the serving mesh, and the previous mesh is restored even when the
dispatch raises).
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding import context

__all__ = ["make_mesh", "make_mesh2d", "replica_submesh", "shard_forward"]


def make_mesh(data_shards: int) -> Mesh:
    """A 1-D ``("data",)`` mesh over the first ``data_shards`` devices.

    Raises ``ValueError`` when the host has fewer devices, with the
    forced-host-device recipe for CPU testing in the message.
    """
    devices = jax.devices()
    if data_shards > len(devices):
        raise ValueError(
            f"data_shards={data_shards} needs {data_shards} JAX devices "
            f"but only {len(devices)} are available; on CPU, force host "
            f"devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{data_shards}")
    return Mesh(np.array(devices[:data_shards]), ("data",))


def make_mesh2d(n_replicas: int, data_shards: int) -> Mesh:
    """A 2-D ``("replica", "data")`` mesh over the first
    ``n_replicas * data_shards`` devices — the fleet generalization of
    :func:`make_mesh`.

    Row ``r`` is replica ``r``'s device set: each pool pipeline is
    built over its own row (:func:`replica_submesh`), so replicas never
    contend for a device and the data-parallel dispatch inside one
    replica stays exactly the 1-D ``("data",)`` split of PR 4.
    """
    need = n_replicas * data_shards
    devices = jax.devices()
    if need > len(devices):
        raise ValueError(
            f"a {n_replicas} x {data_shards} replica x data mesh needs "
            f"{need} JAX devices but only {len(devices)} are available; "
            f"on CPU, force host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    grid = np.array(devices[:need]).reshape(n_replicas, data_shards)
    return Mesh(grid, ("replica", "data"))


def replica_submesh(mesh: Mesh, replica: int) -> Mesh:
    """Row ``replica`` of a 2-D ``("replica", "data")`` mesh as the 1-D
    ``("data",)`` mesh that replica's pipeline dispatches over."""
    if tuple(mesh.axis_names) != ("replica", "data"):
        raise ValueError(
            f"replica_submesh takes a ('replica', 'data') mesh, got "
            f"axes {tuple(mesh.axis_names)}")
    n_replicas = mesh.devices.shape[0]
    if not 0 <= replica < n_replicas:
        raise ValueError(f"replica {replica} out of range for a "
                         f"{n_replicas}-replica mesh")
    return Mesh(mesh.devices[replica], ("data",))


def shard_forward(fwd: Callable, spec,
                  mesh: Mesh | None = None,
                  cache_in: bool = False,
                  cache_out: bool = False) -> Tuple[Callable, Mesh]:
    """Wrap a built ``fwd(params, pts, lfsr)`` in a data-parallel
    ``shard_map`` dispatch over ``spec.data_shards`` devices (``pts``
    one batch array, or a tuple of them, each scattered by lanes).

    Returns ``(dispatch, mesh)``; ``dispatch`` has the same signature
    and — given the lane-mapped serving walk — bit-identical results.
    Shape contracts are checked at trace time with ``ValueError``
    (``jax.jit`` surfaces them on the first call of a new shape):
    the batch must divide ``data_shards``, and per-lane URS needs one
    stream per lane.

    Args:
      mesh: a pre-built 1-D ``("data",)`` mesh to dispatch over —
        fleet placement passes a :func:`replica_submesh` row here so
        each pool replica owns its device set; None builds the default
        first-devices mesh.  Must match ``spec.data_shards``.
      cache_in: ``fwd`` takes a trailing stream-cache pytree argument
        (batch-leading leaves) — split with the lanes, ``P("data")``
        as a pytree prefix.
      cache_out: ``fwd`` returns a trailing collected-cache pytree —
        likewise lane-split on the way out.
    """
    # One enforcement path with validate()/build(): the placement-scope
    # analysis pass raises RPA020 ("data_shards > 1 requires per-sample
    # normalization ...") for a sharded spec without per_sample_norm.
    from repro.analysis.passes import enforce_spec
    enforce_spec(spec, scopes=("placement",))
    if mesh is None:
        mesh = make_mesh(spec.data_shards)
    elif (tuple(mesh.axis_names) != ("data",)
            or mesh.devices.shape != (spec.data_shards,)):
        raise ValueError(
            f"shard_forward needs a 1-D ('data',) mesh of exactly "
            f"data_shards={spec.data_shards} devices; got axes "
            f"{tuple(mesh.axis_names)} shape {mesh.devices.shape} "
            f"(build replica rows with replica_submesh(make_mesh2d(...)))")
    lfsr_spec = P() if spec.shared_urs else P("data")
    # A single P("data") acts as a pytree *prefix* for the whole cache
    # subtree — every leaf is batch-leading, so they all lane-split.
    in_specs = (P(), P("data"), lfsr_spec)
    if cache_in:
        in_specs = in_specs + (P("data"),)
    out_specs = (P("data"), lfsr_spec)
    if cache_out:
        out_specs = out_specs + (P("data"),)
    sharded = jax.shard_map(fwd, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)

    def dispatch(params, pts, lfsr, *extra):
        with context.use_mesh(mesh):
            # A multi-array request (points, category) splits array by
            # array: P("data") is a prefix of the whole payload.
            batch = jax.tree_util.tree_leaves(pts)[0].shape[0]
            if batch % spec.data_shards:
                raise ValueError(
                    f"data_shards={spec.data_shards} must divide the "
                    f"dispatch batch evenly: got batch {batch} (the "
                    f"engines pad to max_batch — pick a max_batch that "
                    f"is a multiple of data_shards)")
            if (lfsr is not None and not spec.shared_urs
                    and lfsr.shape[0] != batch):
                raise ValueError(
                    f"per-lane URS under data_shards={spec.data_shards} "
                    f"splits the LFSR streams with the lanes and needs "
                    f"exactly one stream per lane: got {lfsr.shape[0]} "
                    f"streams for batch {batch}")
            return sharded(params, pts, lfsr, *extra)

    return dispatch, mesh
