"""PipelineSpec — the declarative parametrization of one pipeline variant.

HLS4PC's contribution is that sampler choice (FPS vs URS), affine mode,
bit-width, and fusion are *knobs of one template*, not code forks.  A
:class:`PipelineSpec` is that template's knob sheet: a frozen dataclass
naming every choice — topology, sampler/grouper/backend registry keys,
precision policy, fusion, batch semantics — which ``repro.api.build``
compiles once into a :class:`~repro.api.build.FrozenPipeline`.

The paper's Table 1 ladder becomes data::

    elite_spec()   # FPS, learnable affine, fp32, 1024 points
    m2_spec()      # URS, alpha/beta pruned, fp32, 512 points
    lite_spec()    # M-2 topology + int8 w8/a8 deployment

and a new ROADMAP scaling step (real-TPU backend, sharded sampler) is a
new registry entry named by a spec field — no new signatures.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

PRECISIONS = ("fp32", "int8")
AFFINE_MODES = ("affine", "norm", "center")
HEADS = ("cls", "seg", "partseg")
N_STAGES = 4
#: Topology fields mirrored one to one by ``PointMLPConfig``.
_SHAPE_FIELDS = ("in_channels", "stage_reduction", "use_xyz", "decoder",
                 "context_dims", "n_categories")


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    """One pipeline variant, fully described.

    Topology fields mirror :class:`repro.models.pointmlp.PointMLPConfig`
    (the spec is the public surface; the model config is the internal
    walk parametrization — convert with :meth:`to_model_config` /
    :meth:`from_model_config`).

    Component fields are registry keys (``repro.api.registry``):
      sampler: ``fps`` | ``urs`` (| any registered plugin)
      grouper: ``knn``
      backend: ``ref`` | ``pallas_interpret`` | ``pallas``

    Policy fields:
      precision: ``fp32`` serves fused fp32 (QAT fake-quant noise is
        dropped — deployment runs frozen arithmetic); ``int8`` exports
        fused weights to int8 (``w_bits``/``a_bits`` give the exact
        deployment precision of the Fig. 4 ladder).
      fuse: fold BN into (w, b) at build time (HLS4PC §2.2).
      shared_urs / per_sample_norm: streaming-batch semantics — one
        sampler services the whole batch and every cloud normalizes
        with its own statistics (queue-order invariance; pad lanes
        cannot leak).  See :meth:`serving`.
    """
    name: str = "pointmlp-elite"
    # ---- topology (PointMLP walk) ----
    n_points: int = 1024
    n_classes: int = 40
    embed_dim: int = 32
    k_neighbors: int = 16
    stage_expansion: Tuple[int, ...] = (2, 2, 2, 2)
    pre_blocks: Tuple[int, ...] = (1, 1, 2, 1)
    pos_blocks: Tuple[int, ...] = (1, 1, 2, 1)
    res_expansion: float = 0.25
    affine_mode: str = "affine"
    # ---- encoder shape: channels per input point (3 = xyz; 6 = xyz
    # and normals, which the embedding reads and the mapping ops skip),
    # the sample-count reduction of each stage (N -> N/r -> N/r^2 ...),
    # and ``use_xyz`` (the grouped features carry their xyz, so the
    # affine takes C+3 channels and the transfer 2C+3). ----
    in_channels: int = 3
    stage_reduction: int = 2
    use_xyz: bool = False
    # ---- part-segmentation decoder and head (``head="partseg"``):
    # one ``(width, residual blocks)`` pair per feature-propagation
    # level, coarse to fine; ``context_dims`` is (global-context width,
    # category width); ``n_categories`` the object categories a request
    # names. ----
    decoder: Tuple[Tuple[int, int], ...] = ()
    context_dims: Tuple[int, int] = (64, 64)
    n_categories: int = 16
    # ---- components (registry keys) ----
    sampler: str = "fps"
    grouper: str = "knn"
    backend: str = "ref"
    # ---- precision / fusion policy ----
    precision: str = "fp32"
    w_bits: int = 8
    a_bits: int = 8
    per_channel: bool = True
    symmetric: bool = True
    fuse: bool = True
    # ---- per-stage overrides (stage-plan lowering; None inherits the
    # spec-level field for every stage).  A 4-tuple, one entry per
    # stage: stage_precision=("int8","int8","int8","fp32") quantizes
    # stages 1-3 only (the paper's per-layer quantization ladder as a
    # spec field); stage_backend names a BACKENDS entry per stage.
    # Embed and head always follow the spec-level precision/backend. ----
    stage_precision: Optional[Tuple[str, ...]] = None
    stage_backend: Optional[Tuple[str, ...]] = None
    # ---- fused mapping path: "none", or a FUSED_OPS registry key
    # (e.g. "grouped_transfer") lowering each GroupOp + transfer-CBROp
    # pair to one gather+normalize+matmul+bias+ReLU kernel. ----
    fused_group: str = "none"
    # ---- task head: "cls" pools to one label per cloud; "seg" lowers
    # a SegHeadOp (1-NN upsample + skip concat + per-point classifier)
    # emitting per-point logits ``[B, n_points, n_classes]``;
    # "partseg" lowers the published PointMLP part-segmentation
    # decoder (one PropagateOp per ``decoder`` level) and a
    # PartSegHeadOp over a request of points and a category, emitting
    # per-point log-probabilities ``[B, n_points, n_classes]``. ----
    head: str = "cls"
    # ---- streaming mode: ``stream=True`` lowers cache-aware
    # SampleOp/GroupOp variants so a ``StreamSession``
    # (``repro.serve.streaming``) can reuse sampled indices + neighbor
    # lists across LiDAR frames whose per-point drift stays <=
    # ``stream_drift_threshold`` (max point displacement vs the cached
    # key frame, same units as the cloud coordinates). ----
    stream: bool = False
    stream_drift_threshold: float = 0.0
    # ---- batch semantics ----
    shared_urs: bool = False
    per_sample_norm: bool = False
    # ---- dispatch sharding (``repro.serve.sharding``): split every
    # batch dispatch over a 1-D device mesh, ``batch // data_shards``
    # lanes per device, params replicated.  1 = single-device (today's
    # behaviour); >1 needs that many JAX devices at build time. ----
    data_shards: int = 1
    # ---- serving policy (async engine; registry keys in
    # ``repro.serve.policy.POLICIES``) ----
    policy: str = "fixed"
    slo_ms: float = 0.0
    dispatch_ms: float = 0.0

    def __post_init__(self):
        if self.precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}, "
                             f"got {self.precision!r}")
        if self.affine_mode not in AFFINE_MODES:
            raise ValueError(f"affine_mode must be one of {AFFINE_MODES}, "
                             f"got {self.affine_mode!r}")
        if self.slo_ms < 0:
            raise ValueError(f"slo_ms must be >= 0, got {self.slo_ms!r}")
        if self.dispatch_ms < 0:
            raise ValueError(
                f"dispatch_ms must be >= 0, got {self.dispatch_ms!r}")
        if not isinstance(self.data_shards, int) or self.data_shards < 1:
            raise ValueError(f"data_shards must be a positive int, "
                             f"got {self.data_shards!r}")
        for field, allowed in (("stage_precision", PRECISIONS),
                               ("stage_backend", None)):
            val = getattr(self, field)
            if val is None:
                continue
            if isinstance(val, list):        # normalize to a hashable spec
                val = tuple(val)
                object.__setattr__(self, field, val)
            if (not isinstance(val, tuple) or len(val) != N_STAGES
                    or not all(isinstance(v, str) for v in val)):
                raise ValueError(
                    f"{field} must be a {N_STAGES}-tuple of strings "
                    f"(one per stage), got {val!r}")
            if allowed is not None and not set(val) <= set(allowed):
                raise ValueError(
                    f"{field} entries must be in {allowed}, got {val!r}")
        if not isinstance(self.fused_group, str):
            raise ValueError(f"fused_group must be a FUSED_OPS registry "
                             f"key or 'none', got {self.fused_group!r}")
        if self.head not in HEADS:
            raise ValueError(f"head must be one of {HEADS}, "
                             f"got {self.head!r}")
        self._check_topology()
        if not isinstance(self.stream, bool):
            raise ValueError(f"stream must be a bool, got {self.stream!r}")
        thr = self.stream_drift_threshold
        if (not isinstance(thr, (int, float)) or isinstance(thr, bool)
                or not thr >= 0 or thr != thr or thr == float("inf")):
            raise ValueError(
                f"stream_drift_threshold must be a finite float >= 0, "
                f"got {thr!r}")
        # Cross-field semantic checks (stream x fused_group, sharding x
        # per_sample_norm, registry keys, ...) live in the
        # repro.analysis passes — enforced by validate() / lower() /
        # build(), reported by `python -m repro.analysis`.  Keeping
        # them out of __post_init__ lets the autotuner *construct* any
        # well-shaped point of the search space and prune it by
        # analyzing, instead of crashing inside replace().

    def _check_topology(self) -> None:
        """Shape checks of the encoder/decoder fields (lists normalize to
        tuples, so a spec read from JSON stays hashable)."""
        dec = self.decoder
        if isinstance(dec, (list, tuple)):
            dec = tuple(tuple(level) if isinstance(level, list) else level
                        for level in dec)
            object.__setattr__(self, "decoder", dec)
        if isinstance(self.context_dims, list):
            object.__setattr__(self, "context_dims",
                               tuple(self.context_dims))
        if (not isinstance(dec, tuple) or not all(
                isinstance(lv, tuple) and len(lv) == 2
                and all(isinstance(v, int) and v > 0 for v in lv)
                for lv in dec)):
            raise ValueError(f"decoder must be a tuple of (width, blocks) "
                             f"pairs of positive ints, got {dec!r}")
        for field in ("in_channels", "stage_reduction", "n_categories"):
            val = getattr(self, field)
            if not isinstance(val, int) or isinstance(val, bool) or val < 1:
                raise ValueError(f"{field} must be a positive int, "
                                 f"got {val!r}")
        if self.in_channels < 3:
            raise ValueError(f"in_channels counts xyz first, so it must be "
                             f">= 3, got {self.in_channels!r}")
        if self.stage_reduction < 2:
            raise ValueError(f"stage_reduction must be >= 2 (each stage "
                             f"samples fewer points), got "
                             f"{self.stage_reduction!r}")
        if self.n_points // self.stage_reduction ** N_STAGES < 1:
            raise ValueError(
                f"n_points={self.n_points} leaves no sample at stage "
                f"{N_STAGES} under stage_reduction={self.stage_reduction}")
        if self.head == "partseg":
            if len(dec) != N_STAGES:
                raise ValueError(
                    f"head='partseg' needs one decoder level per encoder "
                    f"stage ({N_STAGES}), got decoder={dec!r}")
            cd = self.context_dims
            if (not isinstance(cd, tuple) or len(cd) != 2
                    or not all(isinstance(v, int) and v > 0 for v in cd)):
                raise ValueError(f"context_dims must be a (context, "
                                 f"category) pair of positive ints, "
                                 f"got {cd!r}")
        elif dec:
            raise ValueError(f"decoder levels are lowered only for "
                             f"head='partseg', got head={self.head!r}")

    def replace(self, **kw) -> "PipelineSpec":
        return dataclasses.replace(self, **kw)

    def serving(self, policy: str | None = None,
                slo_ms: float | None = None,
                dispatch_ms: float | None = None,
                data_shards: int | None = None) -> "PipelineSpec":
        """The streaming-deployment rendering of this spec: one sampler
        services the batch, per-cloud normalization statistics — the
        serving engines' queue-order/dispatch-invariance contract.

        Args:
          policy: async batching policy registry key (``fixed`` |
            ``deadline`` | any registered plugin); None keeps the
            current field.
          slo_ms: per-request latency objective handed to the policy
            (the ``deadline`` policy's queue-wait budget); None keeps
            the current field.
          dispatch_ms: estimated service time of one dispatch, reserved
            out of the SLO budget by deadline-style policies; None
            keeps the current field.
          data_shards: split every dispatch over this many devices
            (``repro.serve.sharding``); None keeps the current field.
        """
        kw = dict(shared_urs=True, per_sample_norm=True)
        if policy is not None:
            kw["policy"] = policy
        if slo_ms is not None:
            kw["slo_ms"] = slo_ms
        if dispatch_ms is not None:
            kw["dispatch_ms"] = dispatch_ms
        if data_shards is not None:
            kw["data_shards"] = data_shards
        return self.replace(**kw)

    def validate(self) -> "PipelineSpec":
        """Run every ``repro.analysis`` pass scope over this spec and
        enforce the findings: unknown registry keys raise ``KeyError``
        listing the registered names (RPA001-005), broken lowering /
        placement invariants raise ``ValueError`` with their ``RPAxxx``
        code, soft misconfigurations warn (RPA1xx, escalated in-tree).
        Returns self for chaining."""
        # Deferred import: repro.analysis.passes imports repro.api.
        from repro.analysis.passes import enforce_spec
        enforce_spec(self)
        return self

    # ------------------------------------------- model-config bridge ----

    def to_model_config(self):
        """The internal walk parametrization for this spec.

        ``use_bn=True`` / QAT fake-quant: the *training-shape* config —
        ``repro.api.build`` derives the deployment config (fused,
        exported) from it.  ``precision="int8"`` maps to w/a-bit QAT so
        training under a spec matches the paper's flow (QAT first, fuse
        and export after).
        """
        from repro.core.quant import QuantConfig
        from repro.models.pointmlp import PointMLPConfig
        if self.precision == "int8":
            quant = QuantConfig(w_bits=self.w_bits, a_bits=self.a_bits,
                                per_channel=self.per_channel,
                                symmetric=self.symmetric)
        else:
            quant = QuantConfig(w_bits=32, a_bits=32)
        return PointMLPConfig(
            name=self.name, n_points=self.n_points, n_classes=self.n_classes,
            embed_dim=self.embed_dim, k_neighbors=self.k_neighbors,
            stage_expansion=self.stage_expansion, pre_blocks=self.pre_blocks,
            pos_blocks=self.pos_blocks, res_expansion=self.res_expansion,
            sampler=self.sampler, affine_mode=self.affine_mode,
            head=self.head, quant=quant, **self._shape_fields())

    def _shape_fields(self) -> dict:
        """The encoder/decoder fields spec and model config share."""
        return {f: getattr(self, f) for f in _SHAPE_FIELDS}

    @classmethod
    def from_model_config(cls, cfg, **overrides) -> "PipelineSpec":
        """Lift a legacy :class:`PointMLPConfig` into a spec.

        An enabled quant config maps to ``precision="int8"`` with its
        w/a bits and scale policy preserved exactly (so
        :meth:`to_model_config` round-trips; the int8 *export* in
        ``repro.api.build`` clamps w_bits to 8 at deploy time).  Pass
        ``precision="fp32"`` in ``overrides`` to serve the fused-fp32
        deployment of a QAT-trained config.
        """
        fields = dict(
            name=cfg.name, n_points=cfg.n_points, n_classes=cfg.n_classes,
            embed_dim=cfg.embed_dim, k_neighbors=cfg.k_neighbors,
            stage_expansion=cfg.stage_expansion, pre_blocks=cfg.pre_blocks,
            pos_blocks=cfg.pos_blocks, res_expansion=cfg.res_expansion,
            sampler=cfg.sampler, affine_mode=cfg.affine_mode,
            head=cfg.head, precision="fp32",
            **{f: getattr(cfg, f) for f in _SHAPE_FIELDS})
        if cfg.quant.enabled:
            fields.update(precision="int8",
                          w_bits=cfg.quant.w_bits,
                          a_bits=cfg.quant.a_bits,
                          per_channel=cfg.quant.per_channel,
                          symmetric=cfg.quant.symmetric)
        fields.update(overrides)
        return cls(**fields)


# ------------------------------------------------- fleet serving --------


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant's serving contract, declaratively.

    A tenant is a traffic class with its own latency/accuracy deal:
    the real-time LiDAR stream takes the int8 Lite tier at a tight SLO,
    the batch-analytics backfill takes the fp32 Elite tier and can
    wait.  ``repro.serve.fleet.PipelineFleet`` routes each tenant's
    requests to pool replicas of its tier and load-sheds (typed
    :class:`~repro.serve.admission.Overloaded`) past the admission
    bounds below.

    Fields:
      name: tenant id — the key callers pass to ``fleet.submit``.
      tier: which pool pipeline serves this tenant — a
        :class:`PipelineSpec` ``name`` from the owning
        :class:`FleetSpec`'s pool.
      slo_ms: per-request latency objective.  Admission control sheds
        a request when the tier's *calibrated* cost model says the
        queue ahead of it is not servable inside this budget
        (0 = no SLO-based shedding).
      max_inflight: hard cap on this tenant's unresolved (admitted but
        unanswered) requests — the bulkhead that keeps one tenant's
        burst from queueing out everyone else.
    """
    name: str
    tier: str
    slo_ms: float = 50.0
    max_inflight: int = 64

    def __post_init__(self):
        if not self.name or not isinstance(self.name, str):
            raise ValueError(f"tenant name must be a non-empty string, "
                             f"got {self.name!r}")
        if not self.tier or not isinstance(self.tier, str):
            raise ValueError(f"tenant {self.name!r} tier must be a "
                             f"non-empty string, got {self.tier!r}")
        if self.slo_ms < 0:
            raise ValueError(f"tenant {self.name!r} slo_ms must be >= 0, "
                             f"got {self.slo_ms!r}")
        if not isinstance(self.max_inflight, int) or self.max_inflight < 1:
            raise ValueError(f"tenant {self.name!r} max_inflight must be "
                             f"a positive int, got {self.max_inflight!r}")

    def replace(self, **kw) -> "TenantSpec":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class FleetSpec:
    """A whole serving deployment, declaratively: the pipeline pool,
    the tenants, and the routing/placement policy.

    The accuracy/throughput ladder behind one front door: ``pipelines``
    are the distinct variants (elite/m2/lite, fp32/mixed/int8 — any
    :class:`PipelineSpec`, each with a unique ``name``), ``replicas``
    stamps out that many copies of each, and every tenant names its
    tier.  ``repro.serve.fleet.PipelineFleet.from_specs`` builds the
    pool (``repro.api.build.build_pool`` — shared frozen structure, no
    re-tracing) and places replicas over a 2-D ``("replica", "data")``
    device mesh when the pool is sharded.

    Pool order is ``replicas`` copies of the ``pipelines`` tuple in
    sequence (replica ``r`` of pipeline ``i`` sits at pool index
    ``r * len(pipelines) + i``) — the mesh row assignment is exactly
    this order, so placement is reproducible from the spec alone.
    """
    name: str = "fleet"
    pipelines: Tuple[PipelineSpec, ...] = ()
    tenants: Tuple[TenantSpec, ...] = ()
    replicas: int = 1
    router: str = "least-loaded"
    max_batch: int = 8

    def __post_init__(self):
        for field in ("pipelines", "tenants"):
            val = getattr(self, field)
            if isinstance(val, list):        # normalize to a hashable spec
                object.__setattr__(self, field, tuple(val))
        if not self.pipelines:
            raise ValueError("FleetSpec needs at least one pipeline")
        if not all(isinstance(p, PipelineSpec) for p in self.pipelines):
            raise ValueError("FleetSpec.pipelines must be PipelineSpecs")
        if not all(isinstance(t, TenantSpec) for t in self.tenants):
            raise ValueError("FleetSpec.tenants must be TenantSpecs")
        names = [p.name for p in self.pipelines]
        if len(set(names)) != len(names):
            raise ValueError(f"pool pipeline names must be unique (they "
                             f"key tenant tiers and params), got {names}")
        tnames = [t.name for t in self.tenants]
        if len(set(tnames)) != len(tnames):
            raise ValueError(f"tenant names must be unique, got {tnames}")
        for t in self.tenants:
            if t.tier not in names:
                raise ValueError(
                    f"tenant {t.name!r} names tier {t.tier!r} but the "
                    f"pool has only {names}")
        shards = {p.data_shards for p in self.pipelines}
        if len(shards) > 1:
            raise ValueError(
                f"pool pipelines must agree on data_shards (the 2-D "
                f"replica x data mesh is rectangular), got {sorted(shards)}")
        if not isinstance(self.replicas, int) or self.replicas < 1:
            raise ValueError(f"replicas must be a positive int, "
                             f"got {self.replicas!r}")
        if not isinstance(self.max_batch, int) or self.max_batch < 1:
            raise ValueError(f"max_batch must be a positive int, "
                             f"got {self.max_batch!r}")
        if self.max_batch % self.data_shards:
            raise ValueError(
                f"data_shards={self.data_shards} must divide "
                f"max_batch={self.max_batch} (every fixed-shape dispatch "
                f"splits across the mesh's data axis)")

    @property
    def data_shards(self) -> int:
        """The (validated-uniform) data axis of the replica x data mesh."""
        return self.pipelines[0].data_shards

    def pool_specs(self) -> Tuple[PipelineSpec, ...]:
        """The flat pool, one spec per replica, in mesh-row order."""
        return tuple(p for _ in range(self.replicas) for p in self.pipelines)

    def tier_of(self, tenant: str) -> PipelineSpec:
        """The pipeline spec serving ``tenant`` (KeyError lists tenants)."""
        for t in self.tenants:
            if t.name == tenant:
                return next(p for p in self.pipelines if p.name == t.tier)
        raise KeyError(f"unknown tenant {tenant!r}; registered tenants: "
                       f"{', '.join(t.name for t in self.tenants)}")

    def replace(self, **kw) -> "FleetSpec":
        return dataclasses.replace(self, **kw)

    def validate(self) -> "FleetSpec":
        """Run the fleet-level ``repro.analysis`` passes and enforce
        the findings: every pool pipeline through every spec scope,
        plus the router key (RPA006, ``KeyError`` listing the
        registered routers).  Tenant-tier coverage is checked at
        construction.  Returns self for chaining."""
        # Deferred import: repro.analysis.passes imports repro.api.
        from repro.analysis import enforce
        from repro.analysis.passes import analyze_fleet_spec
        enforce(analyze_fleet_spec(self))
        return self


# ------------------------------------------------- paper variants -------

def elite_spec(n_classes: int = 40, **overrides) -> PipelineSpec:
    """PointMLP-Elite: FPS, learnable affine, fp32, 1024 points."""
    fields = dict(name="pointmlp-elite", n_classes=n_classes)
    fields.update(overrides)
    return PipelineSpec(**fields)


def m2_spec(n_classes: int = 40, **overrides) -> PipelineSpec:
    """M-2 of Table 1: 512 points, URS, alpha/beta pruned, BN fused."""
    fields = dict(name="pointmlp-m2", n_points=512, sampler="urs",
                  affine_mode="norm", n_classes=n_classes)
    fields.update(overrides)
    return PipelineSpec(**fields)


def lite_spec(n_classes: int = 40, **overrides) -> PipelineSpec:
    """PointMLP-Lite: M-2 topology + 8/8 int8 deployment (Fig. 4 Pareto
    point)."""
    fields = dict(name="pointmlp-lite", precision="int8", w_bits=8,
                  a_bits=8)
    fields.update(overrides)
    return m2_spec(n_classes).replace(**fields)


def compression_ladder_specs(n_classes: int = 40) -> List[PipelineSpec]:
    """The Table 1 ladder as specs: Elite, M-1..M-4, Lite.

    Lifted from ``repro.core.compress.compression_ladder`` (deferred
    import — ``core.compress`` sits above the models in the import
    graph) so the ladder has exactly one source of truth."""
    from repro.core.compress import compression_ladder
    return [PipelineSpec.from_model_config(cfg)
            for cfg in compression_ladder(n_classes)]
