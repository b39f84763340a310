"""``build(spec, params) -> FrozenPipeline`` — the one-shot pipeline compiler.

The deploy-side transform the FPGA flow performs after QAT, as a single
call: fold BN into (w, b) (``spec.fuse``), export int8 weights
(``spec.precision``), resolve the sampler/grouper/backend registry keys
to callables, and jit the fixed-topology walk once.  The result is a
:class:`FrozenPipeline` — an immutable, introspectable executable:

    pipe = build(lite_spec(n_classes).serving(), params)
    logits, state = pipe.infer(pts, state)
    pipe.flops(); print(pipe.describe())

``infer`` is stateless-functional: the URS LFSR state goes in and comes
out (the paper's "same starting states" deployment contract); callers
that hold state across calls (the serving engine) thread it themselves.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.api import registry
from repro.api.spec import PipelineSpec


def _freeze(spec: PipelineSpec, params: Dict) -> Tuple[Dict, Any, Any]:
    """The placement-independent half of :func:`build`: fuse BN, lower
    the stage plan, selectively export int8.  Returns
    ``(frozen_params, deploy_cfg, plan)`` — everything two replicas of
    the same spec + params can share without re-tracing
    (:func:`build_pool` dedupes on exactly this)."""
    from repro.api import plan as stage_plan
    from repro.core import fusion
    from repro.core.quant import QuantConfig, quantize_tree

    cfg = spec.to_model_config()
    frozen = params
    if spec.fuse:
        frozen, cfg = fusion.fuse_pointmlp(frozen, cfg)
    # Lower the stage plan once: per-stage precision/backend overrides
    # and the fused group->transfer path resolve here, and the plan's
    # predicate drives a *selective* int8 export (only regions whose
    # stage resolved to int8 are quantized — for a uniform-int8 spec
    # this is the exact pre-plan whole-tree export).
    plan = stage_plan.lower(spec, cfg)
    if plan.any_int8:
        qcfg = QuantConfig(w_bits=min(spec.w_bits, 8), a_bits=spec.a_bits,
                           per_channel=spec.per_channel,
                           symmetric=spec.symmetric, backend="int8_ref")
        frozen = quantize_tree(frozen, qcfg,
                               predicate=plan.quant_predicate())
        cfg = cfg.replace(quant=qcfg if spec.precision == "int8"
                          else QuantConfig(w_bits=32, a_bits=32))
    else:
        cfg = cfg.replace(quant=QuantConfig(w_bits=32, a_bits=32))
    return frozen, cfg, plan


def _place(spec: PipelineSpec, frozen: Dict, cfg, plan, *, jit: bool,
           donate_lfsr: bool, mesh) -> "FrozenPipeline":
    """The placement half of :func:`build`: resolve registry keys, wrap
    the walk, shard it over its mesh, jit."""
    from repro.models import pointmlp as PM

    sampler, grouper, backend = registry.resolve(
        spec.sampler, spec.grouper, spec.backend)

    def fwd(p, pts, lfsr):
        return PM.pointmlp_infer_with(
            p, cfg, pts, lfsr, sampler=sampler, grouper=grouper,
            backend=backend, shared_urs=spec.shared_urs,
            per_sample_norm=spec.per_sample_norm, plan=plan)

    fwd_collect = fwd_cached = None
    if getattr(plan, "stream", False):
        # Stream specs get two extra executables over the same plan:
        # the collect pass (cold path + cache pytree out) and the
        # cached pass (cache pytree in, mapping ops replayed).  The
        # plain ``fwd`` stays — non-stream requests on a streaming
        # pipeline serve through it unchanged.
        def fwd_collect(p, pts, lfsr):
            return PM.pointmlp_infer_with(
                p, cfg, pts, lfsr, sampler=sampler, grouper=grouper,
                backend=backend, shared_urs=spec.shared_urs,
                per_sample_norm=spec.per_sample_norm, plan=plan,
                collect_cache=True)

        def fwd_cached(p, pts, lfsr, cache):
            return PM.pointmlp_infer_with(
                p, cfg, pts, lfsr, sampler=sampler, grouper=grouper,
                backend=backend, shared_urs=spec.shared_urs,
                per_sample_norm=spec.per_sample_norm, plan=plan,
                mapping_cache=cache)

    out_mesh = None
    if spec.data_shards > 1:
        # Shard step: after fuse/quantize, before jit — the frozen
        # forward is split batch-wise over a 1-D device mesh.  Deferred
        # import: repro.serve sits above this package in the import
        # graph (mirrors the policy-registry deferral in spec.validate).
        from repro.serve.sharding import shard_forward
        fwd, out_mesh = shard_forward(fwd, spec, mesh=mesh)
        if fwd_collect is not None:
            fwd_collect, _ = shard_forward(fwd_collect, spec, mesh=out_mesh,
                                           cache_out=True)
            fwd_cached, _ = shard_forward(fwd_cached, spec, mesh=out_mesh,
                                          cache_in=True)
    elif mesh is not None:
        raise ValueError(
            "build() was given a placement mesh but spec.data_shards "
            "== 1 — an unsharded pipeline has no mesh to place on "
            "(set spec.data_shards to the mesh's data axis)")

    fn = jax.jit(fwd, donate_argnums=(2,) if donate_lfsr else ()) \
        if jit else fwd
    fn_collect = fn_cached = None
    if fwd_collect is not None:
        # No LFSR donation on the stream paths: a frame's dispatch
        # restarts from the session's seed state, which must survive.
        fn_collect = jax.jit(fwd_collect) if jit else fwd_collect
        fn_cached = jax.jit(fwd_cached) if jit else fwd_cached
    return FrozenPipeline(spec=spec, params=frozen, model_config=cfg,
                          _fn=fn, mesh=out_mesh, plan=plan,
                          _fn_collect=fn_collect, _fn_cached=fn_cached)


def build(spec: PipelineSpec, params: Dict, *, jit: bool = True,
          donate_lfsr: bool = False, mesh=None) -> "FrozenPipeline":
    """Compile a spec + trained params into a frozen executable pipeline.

    Args:
      spec: the variant description (registry keys are resolved here —
        a typo raises ``KeyError`` listing the registered names).
      params: trained parameter tree (BN running stats populated when
        ``spec.fuse``).
      jit: wrap the forward in ``jax.jit`` (one executable per
        ``(batch, n_points)`` shape).  ``jit=False`` gives the eager
        walk — bit-identical to the legacy un-jitted entry points.
      donate_lfsr: donate the LFSR argument buffer to each jitted call
        (serving engines that immediately replace their state with the
        returned one; invalid for callers that reuse the input buffer).
      mesh: a pre-built 1-D ``("data",)`` mesh of ``spec.data_shards``
        devices to dispatch over instead of the default first-devices
        mesh — fleet placement passes each replica's
        ``repro.serve.sharding.replica_submesh`` row.  Only valid for
        sharded specs.
    """
    # Fail placement misconfigurations (RPA020: sharded dispatch without
    # per-sample normalization) before the fuse/quantize work, not at
    # shard_forward time.  Deferred: repro.analysis sits above spec/plan
    # but below this module in the import graph.
    from repro.analysis.passes import enforce_spec
    enforce_spec(spec, scopes=("placement",))
    frozen, cfg, plan = _freeze(spec, params)
    return _place(spec, frozen, cfg, plan, jit=jit,
                  donate_lfsr=donate_lfsr, mesh=mesh)


def build_pool(specs: Sequence[PipelineSpec],
               params_by_name: Mapping[str, Dict], *, jit: bool = True,
               mesh=None) -> List["FrozenPipeline"]:
    """Build a fleet pool: one :class:`FrozenPipeline` per spec, with
    shared structure deduped instead of re-traced.

    Replicas of the same spec + params share one
    fuse/lower/int8-export pass (:func:`_freeze` runs once per distinct
    ``(spec_fingerprint, params)``), and *unsharded* identical replicas
    share the whole pipeline object — one jit cache, one compile, N
    pool slots.  Sharded replicas each get their own
    ``shard_map`` wrap over their row of the 2-D
    ``("replica", "data")`` mesh (built here when not passed), so two
    replicas never dispatch onto the same device.

    Args:
      specs: the flat pool, one spec per replica, in mesh-row order
        (``FleetSpec.pool_specs()``).  All must agree on
        ``data_shards``.
      params_by_name: parameter tree per ``spec.name`` — replicas of a
        pipeline share its entry.  A missing name raises ``KeyError``
        listing what was provided.
      mesh: a pre-built ``("replica", "data")`` mesh whose replica
        axis is ``len(specs)``; None builds one when the pool is
        sharded.
    """
    from repro.api import plan as stage_plan

    specs = list(specs)
    shards = {s.data_shards for s in specs}
    if len(shards) > 1:
        raise ValueError(f"pool specs must agree on data_shards (the "
                         f"replica x data mesh is rectangular), got "
                         f"{sorted(shards)}")
    data_shards = shards.pop() if specs else 1
    if data_shards > 1:
        from repro.serve.sharding import make_mesh2d, replica_submesh
        if mesh is None:
            mesh = make_mesh2d(len(specs), data_shards)
        if tuple(mesh.axis_names) != ("replica", "data") \
                or mesh.devices.shape[0] != len(specs):
            raise ValueError(
                f"build_pool needs a ('replica', 'data') mesh with one "
                f"row per pool spec ({len(specs)}); got axes "
                f"{tuple(mesh.axis_names)} shape {mesh.devices.shape}")
    elif mesh is not None:
        raise ValueError("build_pool was given a mesh but the pool is "
                         "unsharded (data_shards == 1)")

    frozen_cache: Dict[Tuple[str, int], Tuple] = {}
    shared_pipes: Dict[Tuple[PipelineSpec, int], FrozenPipeline] = {}
    pool: List[FrozenPipeline] = []
    for i, spec in enumerate(specs):
        try:
            params = params_by_name[spec.name]
        except KeyError:
            raise KeyError(
                f"build_pool: no params for pool pipeline {spec.name!r}; "
                f"params_by_name has "
                f"{', '.join(map(repr, params_by_name))}") from None
        fkey = (stage_plan.spec_fingerprint(spec), id(params))
        if fkey not in frozen_cache:
            frozen_cache[fkey] = _freeze(spec, params)
        frozen, cfg, plan = frozen_cache[fkey]
        if data_shards > 1:
            pool.append(_place(spec, frozen, cfg, plan, jit=jit,
                               donate_lfsr=False,
                               mesh=replica_submesh(mesh, i)))
            continue
        # Unsharded replicas of one (spec, params) are interchangeable
        # executables — share the FrozenPipeline so the pool compiles
        # each distinct variant exactly once.
        pkey = (spec, id(params))
        if pkey not in shared_pipes:
            shared_pipes[pkey] = _place(spec, frozen, cfg, plan, jit=jit,
                                        donate_lfsr=False, mesh=None)
        pool.append(shared_pipes[pkey])
    return pool


@dataclasses.dataclass(frozen=True)
class FrozenPipeline:
    """An immutable compiled pipeline: frozen params + jitted walk.

    Produced by :func:`build`; consumed directly or wrapped by
    :class:`repro.serve.pointcloud.PointCloudEngine` for batched
    queue-draining service.
    """
    spec: PipelineSpec
    params: Dict
    model_config: Any            # resolved deploy PointMLPConfig
    _fn: Any = dataclasses.field(repr=False)
    mesh: Any = None             # 1-D device mesh (data_shards > 1 only)
    plan: Any = None             # compiled repro.api.plan.StagePlan
    _fn_collect: Any = dataclasses.field(repr=False, default=None)
    _fn_cached: Any = dataclasses.field(repr=False, default=None)

    @property
    def streaming(self) -> bool:
        """Whether this pipeline was lowered with cache-aware mapping
        ops (``spec.stream=True``) — i.e. :meth:`infer_collect` /
        :meth:`infer_cached` are available."""
        return self._fn_collect is not None

    def infer(self, pts: jnp.ndarray,
              lfsr_state: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
        """Run the frozen pipeline.

        Args:
          pts: [B, N, 3] point clouds (N == spec.n_points); for
            ``head="partseg"`` the tuple ``(points [B, N, in_channels],
            category [B] int32)``.
          lfsr_state: uint32 [>=B] LFSR streams (URS specs only) —
            shorter states used to silently alias streams inside the
            sampler's index math; now rejected here.

        Returns: (logits [B, n_classes] — [B, N, n_classes] for the
        per-point heads —, advanced LFSR state).
        """
        points = pts[0] if isinstance(pts, tuple) else pts
        if (lfsr_state is not None and points.ndim >= 1
                and lfsr_state.shape[0] < points.shape[0]):
            raise ValueError(
                f"LFSR state has {lfsr_state.shape[0]} streams for a "
                f"batch of {points.shape[0]}; per-lane URS needs one "
                f"stream per lane — size the state from the dispatch "
                f"batch, e.g. pipeline.seed_state(seed, max_batch)")
        return self._fn(self.params, pts, lfsr_state)

    def _require_streaming(self, what: str) -> None:
        if self._fn_collect is None:
            raise ValueError(
                f"{what} needs a streaming pipeline — build one from a "
                f"spec with stream=True (e.g. "
                f"spec.replace(stream=True, stream_drift_threshold=...))")

    def infer_collect(self, pts: jnp.ndarray,
                      lfsr_state: Optional[jnp.ndarray] = None):
        """The cold streaming pass: exactly :meth:`infer` (bit-identical
        logits and state) plus the collected mapping cache pytree
        ``{"sample": (idx, ...), "nbr": (nbr, ...)[, "up": idx]}``
        (batch-leading leaves) for a stream session to key future
        frames off.

        Returns: (logits, advanced LFSR state, cache).
        """
        self._require_streaming("infer_collect")
        return self._fn_collect(self.params, pts, lfsr_state)

    def infer_cached(self, pts: jnp.ndarray,
                     lfsr_state: Optional[jnp.ndarray],
                     cache) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
        """The cached streaming pass: mapping ops replay ``cache``
        (from :meth:`infer_collect`, broadcast to this batch); the
        arithmetic ops recompute on the frame's actual points.

        Returns: (logits, advanced LFSR state).
        """
        self._require_streaming("infer_cached")
        return self._fn_cached(self.params, pts, lfsr_state, cache)

    def seed_state(self, seed: int, n_streams: int = 64) -> jnp.ndarray:
        """Fresh LFSR streams for this pipeline's URS sampler — the
        paper's "initialize the LFSRs with the same starting states".

        Args:
          n_streams: how many parallel streams — size this from the
            consumer's dispatch batch (the serving engines pass their
            ``max_batch``); the historical 64-stream default covers
            batches up to 64, and ``infer`` rejects shorter states.
        """
        from repro.core import sampling
        return sampling.seed_streams(seed, n_streams)

    def flops(self) -> int:
        """Analytic MAC*2 count per sample (Table 2/3 derivations)."""
        from repro.models import pointmlp as PM
        return PM.pointmlp_flops(self.model_config)

    def flops_breakdown(self) -> Dict[str, int]:
        """Per-stage-op MAC*2 counts (sums to :meth:`flops` exactly)."""
        from repro.models import pointmlp as PM
        return PM.pointmlp_flops_breakdown(self.model_config)

    def cost_breakdown(self):
        """Per-stage-op FLOPs / weight-bytes / activation-bytes rows,
        derived from the compiled plan (precision overrides shrink
        weight bytes; a fused group->transfer stage zeroes the grouped
        tensor's HBM round-trip)."""
        if self.plan is None:
            raise ValueError(
                "this FrozenPipeline carries no stage plan (constructed "
                "directly rather than by build()); use build(spec, "
                "params) or pointmlp_flops_breakdown(model_config)")
        return self.plan.cost_breakdown(self.model_config)

    def describe(self) -> str:
        """Human-readable rendering of the compiled variant."""
        from repro.core.quant import tree_size_bytes
        s = self.spec
        cfg = self.model_config
        from repro.api.plan import _PALLAS_BACKENDS
        mm = ("int8_pallas" if s.backend in _PALLAS_BACKENDS
              else "int8_ref")
        prec = (f"int8 (w{min(s.w_bits, 8)}/a{s.a_bits}, {mm} matmul)"
                if s.precision == "int8" else "fp32")
        lines = [
            f"FrozenPipeline({s.name})",
            f"  topology  : {s.n_points} pts -> stages "
            f"{cfg.stage_samples} x dims {cfg.stage_dims} -> "
            f"{s.n_classes} classes",
            f"  sampler   : {s.sampler}"
            + (" (shared across batch)" if s.shared_urs else ""),
            f"  grouper   : {s.grouper} (k={s.k_neighbors}, "
            f"{s.affine_mode}"
            + (", per-sample sigma)" if s.per_sample_norm else ")"),
            f"  precision : {prec}",
            f"  fusion    : {'BN folded into (w, b)' if s.fuse else 'off'}",
            f"  backend   : {s.backend}",
            f"  sharding  : "
            + (f"{s.data_shards}-way data-parallel over mesh axis "
               f"'data' ({next(iter(self.mesh.devices.flat)).platform} "
               f"x{self.mesh.size})"
               if self.mesh is not None else "single-device"),
            f"  flops     : {self.flops() / 1e6:.1f} MFLOP/sample",
            f"  params    : {tree_size_bytes(self.params)} bytes",
        ]
        if self.plan is not None:
            lines.append(f"  plan      : {len(self.plan.ops)} ops; "
                         f"{self.plan.describe()}")
            br = self.flops_breakdown()
            stages = {}
            for op, fl in br.items():
                stages.setdefault(op.split(".")[0], 0)
                stages[op.split(".")[0]] += fl
            lines.append("  stage MFLOP: "
                         + ", ".join(f"{k}={v / 1e6:.2f}"
                                     for k, v in stages.items()))
        return "\n".join(lines)
