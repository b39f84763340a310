"""Roofline-guided spec autotuner (the paper's DSE loop, closed).

``tune(base_spec)`` walks the design space the way HLS4PC's Table 1 /
Fig. 4 exploration does — but mapping-aware, the way PointAcc argues
for: every candidate spec is first *scored statically* by lowering it
to a :class:`~repro.api.plan.StagePlan` and pushing its analytic
``cost_breakdown`` through the :mod:`repro.roofline` hardware model,
then only the top-K estimated candidates (plus the fp32-ref anchor,
always) are *measured* for real engine throughput and an
error-vs-fp32 accuracy proxy.  The measured Pareto frontier and every
estimate land in one schema-versioned ``BENCH_<rev>.json`` row set
(:mod:`repro.tune.artifact`) — the tracked perf trajectory the CI
regression gate diffs across revisions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from repro import roofline
from repro.api import plan as stage_plan
from repro.tune import artifact as art
from repro.tune.frontier import mark_frontier

ANCHOR_NAME = "fp32-ref"


@dataclasses.dataclass
class Candidate:
    """One point of the search space, scored and (maybe) measured."""
    spec: Any
    fingerprint: str
    label: str
    estimate: Optional[roofline.PlanEstimate] = None
    est_error: Optional[str] = None       # lowering failure, if any
    measured_sps: Optional[float] = None
    err_vs_fp32: Optional[float] = None
    measure_error: Optional[str] = None
    anchor: bool = False

    @property
    def est_time(self) -> float:
        return self.estimate.total_s if self.estimate else float("inf")


def quick_space(base) -> List[Any]:
    """The CI-sized search space around ``base``: precision ladder x
    {ref, pallas-interpret} x {unfused, fused group->transfer} x
    {1, N}-way sharding (N only when this host has devices for it)."""
    import jax

    n_dev = jax.device_count()
    shards = (1,) if n_dev < 2 else (1, min(8, n_dev))
    return stage_plan.enumerate_plan_space(
        base,
        stage_backends=(("ref",) * 4, ("pallas_interpret",) * 4),
        fused_groups=("none", "grouped_transfer"),
        data_shards=shards)


def anchor_spec(base):
    """The fp32 reference deployment every run measures: uniform fp32,
    reference backend, unfused, unsharded — the accuracy-proxy zero
    point and the row the CI gate can always compare."""
    return base.replace(precision="fp32", stage_precision=None,
                        stage_backend=None, backend="ref",
                        fused_group="none", data_shards=1)


def _static_prune(cand: Candidate) -> bool:
    """Analyzer gate before estimation: a candidate whose spec carries
    lowering-scope error findings (``repro.analysis``) is recorded as an
    ``est_error`` row — coded, e.g. ``RPA011: ...`` — and never lowered.
    This is what rejects statically-invalid points of an explicitly
    passed ``space`` (``quick_space`` pre-filters through the same
    passes via ``enumerate_plan_space``)."""
    from repro.analysis import ERROR, analyze_spec
    errs = [f for f in analyze_spec(cand.spec, scopes=("lowering",))
            if f.severity == ERROR]
    if errs:
        cand.est_error = "; ".join(f.render() for f in errs)
        return True
    return False


def _estimate(cand: Candidate, hw: roofline.HardwareModel) -> None:
    import warnings

    try:
        cfg = cand.spec.to_model_config()
        with warnings.catch_warnings():
            # Warning-severity findings are the tuner's normal search
            # noise, not per-candidate output.
            warnings.simplefilter("ignore")
            plan = stage_plan.lower(cand.spec, cfg)
        cand.estimate = roofline.estimate_plan(
            plan, cfg, hw, data_shards=cand.spec.data_shards)
    except (ValueError, KeyError) as e:
        cand.est_error = f"{type(e).__name__}: {e}"


def _measure(cand: Candidate, params, pts, *, max_batch: int, seed: int,
             iters: int, anchor_logits):
    """Real engine throughput + err-vs-fp32 for one candidate; returns
    the anchor logits (measured lazily on the anchor itself)."""
    import jax.numpy as jnp

    from repro.serve.pointcloud import PointCloudEngine
    try:
        # One dispatch shape for every candidate: logits are only
        # comparable across engines that chunk the queue identically
        # (the shared-URS LFSR advances per dispatch), so a candidate
        # whose shard count cannot divide the common batch is recorded
        # as unmeasurable rather than measured unfairly.
        if max_batch % cand.spec.data_shards != 0:
            raise ValueError(
                f"max_batch={max_batch} is not divisible by "
                f"data_shards={cand.spec.data_shards}; pass a max_batch "
                f"the whole search space can dispatch")
        eng = PointCloudEngine(params, cand.spec, max_batch=max_batch,
                               seed=seed)
        eng.warmup()
        logits = eng.classify(pts)
        if anchor_logits is None:         # the anchor measures first
            anchor_logits = logits
        cand.err_vs_fp32 = float(jnp.mean(jnp.abs(logits - anchor_logits)))
        eng.stats.reset()
        for _ in range(iters):
            eng.classify(pts)
        cand.measured_sps = float(eng.stats.samples_per_s)
    except Exception as e:  # noqa: BLE001 — a candidate that cannot run
        # (pallas off-TPU, too few devices) is a recorded row, not a
        # crashed search.
        cand.measure_error = f"{type(e).__name__}: {e}"
    return anchor_logits


def _row(cand: Candidate) -> Dict[str, Any]:
    derived = cand.est_error or cand.measure_error
    spec_fields = {
        "sampler": cand.spec.sampler, "grouper": cand.spec.grouper,
        "backend": cand.spec.backend, "precision": cand.spec.precision,
        "stage_precision": list(cand.spec.stage_precision or ()),
        "stage_backend": list(cand.spec.stage_backend or ()),
        "fused_group": cand.spec.fused_group,
        "data_shards": cand.spec.data_shards,
        "n_points": cand.spec.n_points}
    est = cand.estimate
    return art.new_row(
        cand.label, fingerprint=cand.fingerprint, derived=derived,
        estimated_sps=(est.sps if est else None),
        measured_sps=cand.measured_sps, err_vs_fp32=cand.err_vs_fp32,
        anchor=cand.anchor, spec=spec_fields,
        stages=(est.to_rows() if est and (cand.measured_sps is not None
                                          or cand.anchor) else None))


def tune(base_spec, params=None, *, space: Optional[List] = None,
         top_k: int = 3, hw: roofline.HardwareModel = roofline.CPU_HOST,
         max_batch: int = 8, n_requests: Optional[int] = None,
         measure_iters: int = 1, seed: int = 0,
         rev: Optional[str] = None) -> Dict[str, Any]:
    """Run the roofline-guided search; returns a validated artifact doc.

    Args:
      base_spec: the topology/policy every candidate shares (serving
        semantics are applied — the engines' batch contract).
      params: trained param tree; a fresh ``pointmlp_init`` tree when
        None (throughput and the err *proxy* don't need trained
        weights).
      space: candidate specs; :func:`quick_space` around the base when
        None.
      top_k: how many estimated-best candidates get real measurement
        (the anchor is always measured on top of these).
      max_batch: the one dispatch shape every measured candidate uses —
        err-vs-fp32 only means anything across engines that chunk the
        queue identically, so a candidate whose ``data_shards`` cannot
        divide it records an error row instead of measuring unfairly.
      hw: the static-estimate hardware model (ranking only — CPU-host
        by default since that is where the measurement runs).
      rev: artifact ``rev`` tag; resolved from ``$BENCH_REV``/git when
        None.
    """
    import jax

    from repro.data import pointclouds
    from repro.models import pointmlp as PM

    base = base_spec.serving()
    anchor = anchor_spec(base)
    anchor_fp = stage_plan.spec_fingerprint(anchor)

    cands: List[Candidate] = [Candidate(
        spec=anchor, fingerprint=anchor_fp, label=ANCHOR_NAME,
        anchor=True)]
    for spec in (space if space is not None else quick_space(base)):
        fp = stage_plan.spec_fingerprint(spec)
        if fp == anchor_fp:               # the anchor already covers it
            continue
        cands.append(Candidate(spec=spec, fingerprint=fp,
                               label=stage_plan.spec_label(spec)))

    for cand in cands:
        if not _static_prune(cand):
            _estimate(cand, hw)

    # Estimation seeds measurement: the anchor plus the top-K
    # estimated-fastest viable candidates, deterministically ordered
    # (estimated time, then fingerprint).
    ranked = sorted((c for c in cands if not c.anchor and c.estimate),
                    key=lambda c: (c.est_time, c.fingerprint))
    to_measure = [cands[0]] + ranked[:max(top_k, 0)]

    if params is None:
        params = PM.pointmlp_init(jax.random.PRNGKey(seed),
                                  base.to_model_config())
    n_req = n_requests if n_requests is not None else 2 * max_batch
    pts, _ = pointclouds.make_batch(jax.random.PRNGKey(seed + 1),
                                    base.n_points, n_req)
    anchor_logits = None
    for cand in to_measure:
        anchor_logits = _measure(cand, params, pts, max_batch=max_batch,
                                 seed=seed, iters=measure_iters,
                                 anchor_logits=anchor_logits)

    rows = [_row(c) for c in cands]
    mark_frontier(rows)
    # The anchor is the frontier's reference point by definition — a
    # bit-identical-but-faster twin (e.g. the fused fp32 plan in
    # interpret mode) may tie it at err 0, never evict it.
    if rows and rows[0]["measured_sps"] is not None:
        rows[0]["frontier"] = True
    return art.new_artifact(rows, rev=rev, source="repro.tune",
                            hw=dataclasses.asdict(hw))
