"""String-keyed component registries for the pipeline API (HLS4PC §2).

The paper's framework treats mapping operations (sample, group) and NN
layers as interchangeable units of one configurable pipeline.  We encode
that as three registries — samplers, groupers, backends — so a new
component (a real-TPU Pallas path, a sharded sampler, a ball-query
grouper) plugs in under a string key without touching the model walk:

    @register_sampler("my-sampler")
    def my_sampler(xyz, n_samples, lfsr_state, shared): ...

``PipelineSpec`` fields name entries by key; ``repro.api.build`` (and
the legacy ``pointmlp_infer`` wrapper) resolve keys to callables once,
and the walk in ``repro.models.pointmlp`` consumes only the resolved
callables.

Entry contracts
---------------
sampler(xyz [B,N,3], n_samples, lfsr_state, shared) ->
    (idx [B,S] int32, new_lfsr_state)
grouper(xyz, feats, idx, k, affine_params, mode, per_sample_norm
        [, use_xyz=True]) ->
    (new_xyz [B,S,3], center_feats [B,S,C], grouped [B,S,k,2C])
    — ``use_xyz=True`` is passed only for specs that set it, and then
    the grouped output is [B,S,k,2C+3].
backend(p, x, quant, act) -> y
    — one Conv(+folded BN)(+ReLU) inference layer; ``p`` is a layer
    param dict (``w`` may be an int8 export dict), ``quant`` a
    QuantConfig or None, ``act`` whether to apply ReLU.
fused-op(p, xyz, feats, idx, k, affine_params, mode, per_sample_norm,
         act) -> (new_xyz [B,S,3], center_feats [B,S,C],
                  out [B,S,k,C_out])
    — a whole mapping+NN group executed as one kernel (the stage-plan
    lowering of a ``GroupOp`` + transfer-``CBROp`` pair); ``p`` is the
    transfer layer's fused fp32 param dict.  Named by
    ``PipelineSpec.fused_group``.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict


class Registry:
    """A named string-key -> callable table with decorator registration.

    Re-registration of an existing key raises (plugins must pick fresh
    names); unknown-key lookup raises a ``KeyError`` that lists every
    registered name, so typos are self-diagnosing.
    """

    def __init__(self, kind: str):
        self.kind = kind
        self._entries: Dict[str, Callable] = {}

    def register(self, name: str) -> Callable[[Callable], Callable]:
        def deco(fn: Callable) -> Callable:
            if name in self._entries:
                raise ValueError(
                    f"{self.kind} {name!r} is already registered; "
                    f"unregister it first or pick a new name")
            self._entries[name] = fn
            return fn
        return deco

    def unregister(self, name: str) -> None:
        self._entries.pop(name, None)

    def get(self, name: str) -> Callable:
        try:
            return self._entries[name]
        except KeyError:
            raise KeyError(
                f"unknown {self.kind} {name!r}; registered {self.kind}s: "
                f"{', '.join(self.names())}") from None

    def names(self) -> tuple:
        return tuple(sorted(self._entries))

    def __contains__(self, name: str) -> bool:
        return name in self._entries


SAMPLERS = Registry("sampler")
GROUPERS = Registry("grouper")
BACKENDS = Registry("backend")
FUSED_OPS = Registry("fused-op")

register_sampler = SAMPLERS.register
register_grouper = GROUPERS.register
register_backend = BACKENDS.register
register_fused_op = FUSED_OPS.register


# ------------------------------------------------- builtin samplers -----
# Imports are deferred into the entry bodies: this module sits below
# ``repro.models.pointmlp`` in the import graph, and the lazy imports
# keep it free of heavyweight (or cyclic) module loads.

@register_sampler("fps")
def _fps_sampler(xyz, n_samples: int, lfsr_state, shared: bool):
    """Farthest Point Sampling — data-dependent, stateless.

    On a TPU each cloud's whole walk is one VMEM-resident Pallas kernel
    program (``fps_walk_pallas``); elsewhere the reference walk, an XLA
    loop of one argmax per step.  Both give ``sampling.fps``'s indices.
    """
    from repro.kernels.tuning import on_tpu
    if on_tpu():
        from repro.kernels.fps import fps_walk_pallas
        return fps_walk_pallas(xyz, n_samples), lfsr_state
    from repro.core import sampling
    return sampling.fps_batched(xyz, n_samples), lfsr_state


#: Stream-cache contract: a sampler that advances the LFSR state must
#: still *run* on the cached path (so the state walk stays exactly the
#: cold path's); only stateless samplers may have their indices
#: replayed from the cache.  See ``repro.serve.streaming``.
_fps_sampler.advances_state = False


@register_sampler("urs")
def _urs_sampler(xyz, n_samples: int, lfsr_state, shared: bool):
    """LFSR-driven Uniform Random Sampling (HLS4PC §2.1).

    ``shared`` serves the whole batch from one index sequence — the
    hardware has a single LFSR-driven URS unit in the pipeline, so a
    request's result is independent of its batch slot (the serving
    engine's queue-order-invariance contract).
    """
    import jax.numpy as jnp

    from repro.core import sampling
    assert lfsr_state is not None, "URS sampler needs an LFSR state"
    b, n = xyz.shape[0], xyz.shape[1]
    if shared:
        new_state, idx = sampling.urs_indices(lfsr_state, n, n_samples)
        return jnp.broadcast_to(idx[None, :], (b, n_samples)), new_state
    new_state, idx = sampling.urs_indices_batched(
        lfsr_state, n, n_samples, batch=b)
    return idx, new_state


_urs_sampler.advances_state = True


# ------------------------------------------------- builtin groupers -----

@register_grouper("knn")
def _knn_grouper(xyz, feats, idx, k: int, affine_params, mode: str,
                 per_sample_norm: bool, use_xyz: bool = False):
    """KNN group + geometric-affine normalize (HLS4PC §2.1, Fig. 2)."""
    from repro.core import knn as knn_core
    return knn_core.group_points(xyz, feats, idx, k, affine_params, mode,
                                 per_sample_norm=per_sample_norm,
                                 use_xyz=use_xyz)


def _knn_neighbor_index(new_xyz, xyz, k: int):
    from repro.core import knn as knn_core
    return knn_core.neighbor_index(new_xyz, xyz, k)


def _knn_group_with_idx(xyz, feats, idx, nbr_idx, affine_params,
                        mode: str, per_sample_norm: bool):
    from repro.core import knn as knn_core
    return knn_core.group_with_idx(xyz, feats, idx, nbr_idx, affine_params,
                                   mode, per_sample_norm=per_sample_norm)


#: Stream-cache contract: a grouper exposing these two attributes can
#: be split into its mapping half (``neighbor_index`` — cacheable) and
#: its arithmetic half (``group_with_idx`` — always recomputed), and
#: ``group_with_idx(.., neighbor_index(..), ..)`` must be bit-identical
#: to calling the grouper whole.  ``lower(stream=True)`` rejects
#: groupers without them.
_knn_grouper.neighbor_index = _knn_neighbor_index
_knn_grouper.group_with_idx = _knn_group_with_idx


#: Default ball-query radius for the builtin ``ball`` grouper entry.
#: The synthetic clouds (``repro.data.pointclouds``) live on unit-scale
#: surfaces, where 0.5 comfortably covers k<=16 neighbors in dense
#: regions while still clipping far-side strays; register a custom
#: radius with :func:`make_ball_grouper`.
DEFAULT_BALL_RADIUS = 0.5


def make_ball_grouper(radius: float):
    """A grouper-contract callable doing ball query (radius + k cap).

    Reuses the KNN distance core: the k nearest are extracted first,
    then any of them outside ``radius`` is replaced by the nearest
    in-ball neighbor (PointNet++ semantics — with ``radius=inf`` the
    result is bit-identical to the ``knn`` entry).  Register under a
    custom key for a non-default radius::

        register_grouper("ball-0.2")(make_ball_grouper(0.2))
    """
    if not radius > 0:        # also rejects NaN; a sign-error radius
        raise ValueError(     # must not masquerade as its absolute value
            f"ball-query radius must be positive, got {radius!r}")

    def ball_grouper(xyz, feats, idx, k: int, affine_params, mode: str,
                     per_sample_norm: bool, use_xyz: bool = False):
        from repro.core import knn as knn_core
        return knn_core.group_points(xyz, feats, idx, k, affine_params,
                                     mode, per_sample_norm=per_sample_norm,
                                     radius=radius, use_xyz=use_xyz)

    def ball_neighbor_index(new_xyz, xyz, k: int):
        from repro.core import knn as knn_core
        return knn_core.neighbor_index(new_xyz, xyz, k, radius=radius)

    def ball_group_with_idx(xyz, feats, idx, nbr_idx, affine_params,
                            mode: str, per_sample_norm: bool):
        from repro.core import knn as knn_core
        return knn_core.group_with_idx(xyz, feats, idx, nbr_idx,
                                       affine_params, mode,
                                       per_sample_norm=per_sample_norm)

    ball_grouper.radius = radius
    ball_grouper.neighbor_index = ball_neighbor_index
    ball_grouper.group_with_idx = ball_group_with_idx
    return ball_grouper


GROUPERS.register("ball")(make_ball_grouper(DEFAULT_BALL_RADIUS))


# ------------------------------------------------- builtin backends -----

def _cbr_ref(p, x, quant, act: bool):
    import jax

    from repro.models import layers as L
    y = L.conv1d_apply(p, x, quant=quant)
    return jax.nn.relu(y) if act else y


def _cbr_fused_pallas(p, x, quant, act: bool, interpret, tiles=None):
    """Fused fp32 layers through the single-pass ``fused_linear`` kernel.

    Only a *frozen* fp32 layer takes the fused kernel — plain 2-D matmul
    weight, BN already folded, no quantization.  An int8 export dict
    with ``quant.backend="int8_pallas"`` routes through the reference
    lowering *into the int8 Pallas matmul* (``layers._matmul``
    dispatches on the QuantConfig the plan bound to the op); anything
    else (unfused BN, fake-quant) falls back to the pure reference
    path, so one backend entry serves mixed trees.

    ``tiles`` is an optional (tm, tk, tn) override bound at lowering
    time from the spec's :class:`~repro.kernels.tuning.KernelTuning`.
    """
    import jax.numpy as jnp
    w = p["w"]
    if (not isinstance(w, dict) and getattr(w, "ndim", 0) == 2
            and "bn" not in p and quant is None):
        from repro.kernels.fused_linear import fused_linear_pallas
        b = p.get("b")
        if b is None:
            b = jnp.zeros((w.shape[1],), w.dtype)
        tm, tk, tn = tiles if tiles is not None else (128, 128, 128)
        y = fused_linear_pallas(x.reshape(-1, w.shape[0]), w, b,
                                activation="relu" if act else "none",
                                tm=tm, tk=tk, tn=tn, interpret=interpret)
        return y.reshape(*x.shape[:-1], w.shape[1])
    return _cbr_ref(p, x, quant, act)


BACKENDS.register("ref")(_cbr_ref)
BACKENDS.register("pallas_interpret")(
    functools.partial(_cbr_fused_pallas, interpret=True))
BACKENDS.register("pallas")(
    functools.partial(_cbr_fused_pallas, interpret=False))


# ------------------------------------------------- builtin fused ops ----

@register_fused_op("grouped_transfer")
def _grouped_transfer(p, xyz, feats, idx, k: int, affine_params,
                      mode: str, per_sample_norm: bool, act: bool = True,
                      tile_s: int = 64, interpret=None):
    """Fused gather + geometric-affine-normalize + matmul+bias+ReLU.

    The stage-plan lowering of a ``GroupOp`` + transfer-``CBROp`` pair:
    one Pallas kernel (``repro.kernels.grouped_transfer``, interpret
    mode on CPU) gathers KNN neighborhoods, normalizes them, and runs
    the transfer layer without the ``[B, S, k, 2C]`` grouped tensor
    ever round-tripping through HBM.  Requires a fused fp32 transfer
    layer (plan lowering enforces this).  ``tile_s``/``interpret`` are
    bound at lowering time from the spec's KernelTuning / stage
    backend.
    """
    from repro.kernels.grouped_transfer import fused_group_transfer
    return fused_group_transfer(xyz, feats, idx, k, affine_params, mode,
                                per_sample_norm, p, act=act, tile_s=tile_s,
                                interpret=interpret)


def resolve(sampler: str, grouper: str, backend: str
            ) -> tuple:
    """Resolve the three registry keys of a spec to callables at once."""
    return SAMPLERS.get(sampler), GROUPERS.get(grouper), BACKENDS.get(backend)
