"""Spec-level analysis passes — the registry of static plan checks.

Each pass is a function ``(spec) -> List[Finding]`` registered with
:func:`register_pass` under a name and a *scope*:

  ``lowering``   invariants ``lower(spec, cfg)`` needs (registry keys,
                 fused-group preconditions, the stream-cache contract).
                 Enforced by ``lower()`` and used by
                 ``enumerate_plan_space`` / ``repro.tune`` to prune the
                 search space.
  ``serving``    invariants the async engines need (batch-policy key).
  ``placement``  invariants device placement needs (sharding requires
                 per-sample normalization).  Enforced by
                 ``repro.serve.sharding.shard_forward`` and ``build()``.
  ``perf``       advisory roofline findings (a stage whose arithmetic
                 intensity sits far off its siblings).  Reported by
                 ``spec.validate()`` and the CLI; *not* enforced by
                 ``lower()`` and excluded from the search-space pruning
                 filter — a slow spec is still a valid spec.

``spec.validate()`` enforces every scope; :func:`analyze_spec` returns
the findings without raising (the CLI / tests / tuner consume that).
Fleet specs route through :func:`analyze_fleet_spec`, which adds the
router-key check (RPA006) on top of per-pipeline analysis.

The pass registry reuses :class:`repro.api.registry.Registry`, so a
plugin check is one decorator away::

    from repro.analysis.passes import register_pass

    @register_pass("my-invariant", scope="lowering")
    def my_invariant(spec): return [...]
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

from repro.analysis import findings as F
from repro.analysis.findings import Finding, finding
from repro.api import registry
from repro.api.plan import _PALLAS_BACKENDS
from repro.api.spec import N_STAGES
from repro.kernels import tuning

SCOPES = ("lowering", "serving", "placement", "perf")

PASSES = registry.Registry("analysis-pass")


def register_pass(name: str, *, scope: str
                  ) -> Callable[[Callable], Callable]:
    """Register a spec pass under ``name`` with the given scope."""
    if scope not in SCOPES:
        raise ValueError(f"pass scope must be one of {SCOPES}, "
                         f"got {scope!r}")

    def deco(fn: Callable) -> Callable:
        fn.scope = scope
        return PASSES.register(name)(fn)
    return deco


#: Tracked RPA-skip list: seed config modules outside the point-cloud
#: pipeline space.  They are *live* (tier-1 model/system tests import
#: every one of them through ``repro.configs.get_config``), so the
#: analyzer sweep excludes rather than deletes them; the CLI reports
#: each exclusion as an RPA900 info finding so the list stays visible.
RPA_SKIP_MODULES = {
    "repro.configs.hymba": "LM seed config (tier-1 test_models arch)",
    "repro.configs.internvl2": "VLM seed config (tier-1 test_models arch)",
    "repro.configs.llama": "LM seed config (tier-1 test_models arch)",
    "repro.configs.llama_moe": "MoE seed config (tier-1 test_moe)",
    "repro.configs.minitron": "LM seed config (tier-1 test_models arch)",
    "repro.configs.moonshot": "LM seed config (tier-1 test_models arch)",
    "repro.configs.tinyllama": "LM seed config (tier-1 test_system)",
    "repro.configs.whisper": "ASR seed config (tier-1 test_models arch)",
    "repro.configs.xlstm": "LM seed config (tier-1 test_models arch)",
    "repro.configs.yi": "LM seed config (tier-1 test_models arch)",
}


def skip_list_findings() -> List[Finding]:
    """The RPA900 info findings for every tracked skip-list module."""
    return [finding("RPA900", mod, f"excluded from the analyzer sweep: "
                                   f"{why}")
            for mod, why in sorted(RPA_SKIP_MODULES.items())]


def _key_finding(code: str, reg, name: str, op: str) -> List[Finding]:
    """RPA00x for an unresolvable registry key, reusing the registry's
    own self-diagnosing message (it lists the registered names)."""
    try:
        reg.get(name)
        return []
    except KeyError as e:
        return [finding(code, op, str(e.args[0]), exc_type=KeyError)]


# ------------------------------------------------- lowering passes ------

@register_pass("registry-keys", scope="lowering")
def registry_keys(spec) -> List[Finding]:
    """RPA001-004: every component key a lowering resolves must exist."""
    out: List[Finding] = []
    out += _key_finding("RPA001", registry.SAMPLERS, spec.sampler,
                        "spec.sampler")
    out += _key_finding("RPA002", registry.GROUPERS, spec.grouper,
                        "spec.grouper")
    out += _key_finding("RPA003", registry.BACKENDS, spec.backend,
                        "spec.backend")
    for s, b in enumerate(spec.stage_backend or ()):
        out += _key_finding("RPA003", registry.BACKENDS, b,
                            f"spec.stage_backend[{s}]")
    if spec.fused_group != "none":
        out += _key_finding("RPA004", registry.FUSED_OPS,
                            spec.fused_group, "spec.fused_group")
    return out


@register_pass("fused-preconditions", scope="lowering")
def fused_preconditions(spec) -> List[Finding]:
    """RPA010-012: what the fused group->transfer lowering requires."""
    fused = spec.fused_group
    if fused == "none" or fused not in registry.FUSED_OPS:
        return []                    # RPA004 already covers unknown keys
    out: List[Finding] = []
    if spec.grouper != "knn":
        out.append(finding(
            "RPA010", "spec.grouper",
            f"fused_group={fused!r} builds its neighborhoods with the "
            f"knn distance core; grouper={spec.grouper!r} cannot lower "
            f"fused (use grouper='knn' or fused_group='none')"))
    prec = spec.stage_precision or (spec.precision,) * N_STAGES
    bad = [s + 1 for s in range(N_STAGES) if prec[s] == "int8"]
    if bad:
        out.append(finding(
            "RPA011", "spec.stage_precision",
            f"fused_group={fused!r} requires fp32 transfer layers; "
            f"stages {bad} resolve to int8 (stage_precision / "
            f"precision)"))
    if spec.use_xyz:
        out.append(finding(
            "RPA019", "spec.use_xyz",
            f"fused_group={fused!r} normalizes features alone; it cannot "
            f"carry the neighbours' xyz (use_xyz=True): use "
            f"fused_group='none'"))
    if not spec.fuse:
        out.append(finding(
            "RPA012", "spec.fuse",
            f"fused_group={fused!r} consumes BN-folded (w, b) transfer "
            f"layers; set spec.fuse=True"))
    return out


@register_pass("stream-contract", scope="lowering")
def stream_contract(spec) -> List[Finding]:
    """RPA013-015, RPA018: the stream-cache lowering contract."""
    if not getattr(spec, "stream", False):
        return []
    out: List[Finding] = []
    if spec.head == "partseg" or spec.in_channels != 3:
        out.append(finding(
            "RPA018", "spec.head",
            f"stream=True cannot serve head={spec.head!r} with "
            f"in_channels={spec.in_channels}: a stream session sends one "
            f"xyz frame [N, 3] per request, and this model needs more "
            f"per point (normals) or per request (a category); serve it "
            f"with submit(points, category) on a stream=False spec"))
    if spec.fused_group != "none":
        out.append(finding(
            "RPA013", "spec.fused_group",
            f"stream=True is incompatible with fused_group="
            f"{spec.fused_group!r}: the fused group->transfer kernel "
            f"has no cache-aware lowering (set fused_group='none')"))
    if spec.grouper in registry.GROUPERS:
        grouper_fn = registry.GROUPERS.get(spec.grouper)
        if (getattr(grouper_fn, "neighbor_index", None) is None
                or getattr(grouper_fn, "group_with_idx", None) is None):
            out.append(finding(
                "RPA014", "spec.grouper",
                f"stream=True needs a grouper exposing the "
                f"neighbor_index/group_with_idx split (stream-cache "
                f"contract); grouper {spec.grouper!r} does not"))
    if spec.sampler in registry.SAMPLERS:
        sampler_fn = registry.SAMPLERS.get(spec.sampler)
        if getattr(sampler_fn, "advances_state", None) is None:
            out.append(finding(
                "RPA015", "spec.sampler",
                f"stream=True needs a sampler declaring its "
                f"advances_state stream-cache semantics; sampler "
                f"{spec.sampler!r} does not"))
    return out


@register_pass("tpu-platform", scope="lowering")
def tpu_platform(spec) -> List[Finding]:
    """RPA016-017: what would not run compiled on a TPU.  Checked only
    when JAX's default backend is a TPU, so the CPU keeps its
    interpret-mode canaries."""
    if not tuning.on_tpu():
        return []
    out: List[Finding] = []
    backends = (spec.backend,) + tuple(spec.stage_backend or ())
    if "pallas_interpret" in backends:
        out.append(finding(
            "RPA017", "spec.backend",
            "backend 'pallas_interpret' would run every Pallas kernel in "
            "interpret mode on the TPU; use backend='pallas'"))
    if spec.fused_group == "grouped_transfer":
        # Mosaic rejects the kernel's in-kernel ``jnp.take`` gather
        # ("Shape mismatch in input, indices and output").
        out.append(finding(
            "RPA016", "spec.fused_group",
            "fused_group='grouped_transfer' does not compile on a TPU "
            "(Mosaic rejects its in-kernel gather); use fused_group='none'"))
    return out


# RPA101 (int8-pallas-fallback) is retired: since the kernel-tuning
# layer landed, an int8 stage on a pallas backend lowers to the int8
# Pallas matmul (``plan._quant_for`` binds backend="int8_pallas") —
# the spec point is a distinct, valid lowering, not a silent ref
# fallback.  The code stays reserved in ``findings.CODES``.


# ------------------------------------------------- serving passes -------

@register_pass("policy-key", scope="serving")
def policy_key(spec) -> List[Finding]:
    """RPA005: the async engines must be able to instantiate the
    spec's batch policy."""
    # Deferred import: the policy registry lives serve-side, above this
    # package in the import graph.
    from repro.serve.policy import POLICIES
    return _key_finding("RPA005", POLICIES, spec.policy, "spec.policy")


# ------------------------------------------------- placement passes -----

@register_pass("sharding-per-sample-norm", scope="placement")
def sharding_per_sample_norm(spec) -> List[Finding]:
    """RPA020: a device-split batch must not compute batch statistics."""
    if spec.data_shards <= 1 or spec.per_sample_norm:
        return []
    return [finding(
        "RPA020", "spec.per_sample_norm",
        "data_shards > 1 requires per-sample normalization "
        "(spec.per_sample_norm, e.g. via spec.serving()): "
        "batch-statistic normalization couples lanes across the "
        "whole dispatch, so a device-split batch would silently "
        "compute shard-local statistics and change results")]


# ------------------------------------------------- perf passes ----------

#: Default anomaly threshold: a stage is flagged when its arithmetic
#: intensity is more than this factor off the sibling median (in log
#: space, i.e. either direction).  Calibrated so every shipped variant
#: (elite/m2/lite, the compression ladder, their serving/int8
#: derivatives — all sit within ~3.1x of their sibling median) analyzes
#: clean while a single pathologically wide stage (e.g.
#: stage_expansion=(1,1,1,64) — 16x+ off) trips it.
INTENSITY_ANOMALY_FACTOR = 8.0


def stage_intensities(spec) -> dict:
    """Per-stage estimated arithmetic intensity (FLOPs per HBM byte),
    aggregated over each stage's ops from the lowered plan's
    :meth:`~repro.api.plan.StagePlan.cost_breakdown`.  Raises whatever
    ``lower()`` raises for an unlowerable spec."""
    from repro.api import plan as stage_plan
    cfg = spec.to_model_config()
    plan = stage_plan.lower(spec, cfg)
    agg: dict = {}
    for r in plan.cost_breakdown(cfg):
        name = r["op"].split(".")[0]
        if not name.startswith("stage"):
            continue
        fl, by = agg.get(name, (0, 0))
        agg[name] = (fl + r["flops"],
                     by + r["w_bytes"] + r["act_bytes"])
    return {name: fl / max(by, 1) for name, (fl, by) in agg.items()}


@register_pass("stage-intensity-anomaly", scope="perf")
def stage_intensity_anomaly(spec) -> List[Finding]:
    """RPA104 (warning): a stage whose estimated arithmetic intensity
    falls far off its siblings' median — one stage of the pipeline is
    disproportionately compute- or memory-bound, which usually means a
    mis-sized expansion/depth knob rather than an intended design.
    Advisory only (perf scope): never blocks lowering or the tuner."""
    import math
    try:
        intens = stage_intensities(spec)
    except Exception:
        return []          # unlowerable specs belong to other scopes
    if len(intens) < 3:
        return []          # no meaningful sibling median
    logs = sorted(math.log(max(v, 1e-12)) for v in intens.values())
    n = len(logs)
    med = (logs[n // 2] if n % 2
           else 0.5 * (logs[n // 2 - 1] + logs[n // 2]))
    cut = math.log(INTENSITY_ANOMALY_FACTOR)
    out: List[Finding] = []
    for name in sorted(intens):
        dev = math.log(max(intens[name], 1e-12)) - med
        if abs(dev) > cut:
            direction = "compute" if dev > 0 else "memory"
            out.append(finding(
                "RPA104", f"plan.{name}",
                f"{name} estimated arithmetic intensity "
                f"{intens[name]:.2f} FLOP/byte is {math.exp(abs(dev)):.0f}x "
                f"off the sibling median — disproportionately "
                f"{direction}-bound (check the stage's expansion/depth "
                f"knobs, or raise "
                f"analysis.passes.INTENSITY_ANOMALY_FACTOR)"))
    return out


# ------------------------------------------------- entry points ---------

def analyze_spec(spec, scopes: Optional[Sequence[str]] = None
                 ) -> List[Finding]:
    """Run every registered pass whose scope is in ``scopes`` (all
    scopes when None) and return the combined findings, pass-registry
    order (deterministic: sorted pass names)."""
    wanted = set(scopes) if scopes is not None else set(SCOPES)
    bad = wanted - set(SCOPES)
    if bad:
        raise ValueError(f"unknown pass scopes {sorted(bad)}; "
                         f"known scopes: {SCOPES}")
    out: List[Finding] = []
    for name in PASSES.names():
        fn = PASSES.get(name)
        if fn.scope in wanted:
            out.extend(fn(spec))
    return out


def analyze_fleet_spec(fleet_spec) -> List[Finding]:
    """Fleet-level analysis: every pool pipeline through every scope,
    plus the router key (RPA006)."""
    out: List[Finding] = []
    for p in fleet_spec.pipelines:
        for f in analyze_spec(p):
            out.append(Finding(code=f.code, severity=f.severity,
                               op=f"pipeline[{p.name}].{f.op}",
                               message=f.message, exc_type=f.exc_type))
    # Deferred import: serve sits above this package.
    from repro.serve.router import ROUTERS
    out += _key_finding("RPA006", ROUTERS, fleet_spec.router,
                        "fleet.router")
    return out


def enforce_spec(spec, scopes: Optional[Sequence[str]] = None,
                 stacklevel: int = 3) -> None:
    """Analyze + :func:`repro.analysis.findings.enforce` in one call —
    the path ``validate()`` / ``lower()`` / ``build()`` /
    ``shard_forward()`` share."""
    F.enforce(analyze_spec(spec, scopes=scopes), stacklevel=stacklevel)


def pass_names() -> Tuple[str, ...]:
    return PASSES.names()
