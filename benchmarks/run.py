"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Heavier rows (Table 1 /
Fig. 4 miniature training) run by default; ``--quick`` skips them.
Roofline rows are summarized from the dry-run artifacts when present
(run ``python -m repro.launch.dryrun`` first).

``--json PATH`` additionally writes every emitted row as a
schema-versioned ``BENCH_<rev>.json`` artifact (``repro.tune.artifact``
— the same row schema the autotuner emits), so humans read the CSV and
the CI regression gate (``scripts/bench_diff.py``) consumes the same
run.  ``--tune-quick`` replaces the table sweep with the roofline-guided
spec autotuner (``repro.tune``) over a CI-sized search space.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _mod, _p in (("repro", _ROOT / "src"), ("benchmarks", _ROOT)):
    try:
        __import__(_mod)
    except ImportError:
        sys.path.insert(0, str(_p))

#: Artifact rows collected by ``_emit`` for ``--json`` (shared schema
#: with the tuner: ``repro.tune.artifact.new_row``).
_ROWS: list = []

_SPS_RE = re.compile(r"(?:^|;)SPS=([0-9.eE+-]+)")
_ERR_RE = re.compile(r"(?:^|;)err_vs_fp32=([0-9.eE+-]+)")
_SHED_RE = re.compile(r"(?:^|;)shed_rate=([0-9.eE+-]+)")
_HIT_RE = re.compile(r"(?:^|;)cache_hit_rate=([0-9.eE+-]+)")


def _emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)
    from repro.tune import artifact as art
    sps = _SPS_RE.search(derived)
    err = _ERR_RE.search(derived)
    shed = _SHED_RE.search(derived)
    hit = _HIT_RE.search(derived)
    _ROWS.append(art.new_row(
        name, us_per_call=us, derived=derived,
        measured_sps=float(sps.group(1)) if sps else None,
        err_vs_fp32=float(err.group(1)) if err else None,
        shed_rate=float(shed.group(1)) if shed else None,
        cache_hit_rate=float(hit.group(1)) if hit else None))


def bench_kernels() -> None:
    from benchmarks import kernels_micro
    for name, us, derived in kernels_micro.rows():
        _emit(name, us, derived.replace(",", ";"))


def bench_table1(steps: int) -> None:
    from benchmarks import table1_compression
    t0 = time.time()
    rows = table1_compression.run(steps=steps)
    elite = next(r for r in rows if r["model"] == "pointmlp-elite")
    m2 = next(r for r in rows if r["model"] == "M-2")
    _emit("table1_compression_ladder", (time.time() - t0) * 1e6,
          f"elite_oa={elite['oa']};m2_oa={m2['oa']};"
          f"drop={elite['oa']-m2['oa']:.3f}")


def bench_fig4(parent_steps: int, qat_steps: int) -> None:
    from benchmarks import fig4_pareto
    t0 = time.time()
    rows = fig4_pareto.run(parent_steps=parent_steps, qat_steps=qat_steps)
    p88 = next(r for r in rows if r["precision"] == "8/8")
    _emit("fig4_pareto_8_8", (time.time() - t0) * 1e6,
          f"oa={p88['oa']};size={p88['size_bytes']}")


def bench_table2() -> None:
    from benchmarks import table2_throughput
    t0 = time.time()
    rows = table2_throughput.run()
    r = rows["tpu_v5e_lite_int8"]
    _emit("table2_tpu_lite_int8", (time.time() - t0) * 1e6,
          f"GOPS={r['derived_GOPS']};SPS={r['derived_SPS']};"
          f"bound={r['bound']}")


def bench_table3() -> None:
    from benchmarks import table3_platforms
    t0 = time.time()
    rows = table3_platforms.run()
    _emit("table3_platforms", (time.time() - t0) * 1e6,
          f"cpu_lite_sps={rows['cpu_lite_int8_sps']};"
          f"cpu_elite_sps={rows['cpu_elite_fp32_sps']};"
          f"tpu_lite_sps={rows['tpu_v5e_lite_derived_sps']}")


def bench_specs() -> None:
    """One row per registered backend (PipelineSpec API smoke).

    Drives ``build(spec).infer`` through the serving engine for every
    entry in the backend registry, so the CI ``--quick`` smoke exercises
    each lowering path.  Only the real ``pallas`` backend may be
    unavailable (it needs a TPU; on CPU the row reports the failure) —
    any other backend error propagates and fails the smoke.  On a TPU
    every error propagates, the real ``pallas`` one included.
    """
    import jax

    from benchmarks import serve_pointcloud as sp
    from repro.api import BACKENDS, lite_spec
    from repro.data import pointclouds
    from repro.kernels.tuning import on_tpu
    from repro.models import pointmlp as PM
    from repro.serve.pointcloud import PointCloudEngine

    # fp32 so each row genuinely lowers CBR layers through its backend
    # entry (int8 trees fall back to the reference int8 matmul).
    base = lite_spec(pointclouds.N_CLASSES).replace(
        n_points=128, embed_dim=16, k_neighbors=8,
        precision="fp32").serving()
    params = PM.pointmlp_init(jax.random.PRNGKey(0),
                              base.to_model_config())
    pts, _ = pointclouds.make_batch(jax.random.PRNGKey(1), base.n_points, 2)
    for backend in BACKENDS.names():
        spec = base.replace(backend=backend)
        t0 = time.time()
        try:
            eng = PointCloudEngine(params, spec, max_batch=2, seed=0)
            sps, _ = sp.measure(eng, pts, iters=1)
            derived = (f"backend={backend};precision={spec.precision};"
                       f"SPS={sps:.1f}")
        except Exception as e:
            if backend != "pallas" or on_tpu():  # absent only off-TPU
                raise
            derived = (f"backend={backend};"
                       f"unavailable={type(e).__name__}")
        _emit(f"spec_{backend}", (time.time() - t0) * 1e6,
              derived.replace(",", ";"))


def bench_spec_sharded() -> None:
    """The ``spec_sharded`` row: data-parallel batch dispatch.

    Splits the fixed dispatch over however many JAX devices are
    available (8 on the CI step, which forces host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``); on a
    single-device host the row reports unavailable with the recipe,
    mirroring how the real ``pallas`` row degrades off-TPU.
    """
    import jax

    from benchmarks import serve_pointcloud as sp
    from repro.api import lite_spec
    from repro.data import pointclouds
    from repro.models import pointmlp as PM
    from repro.serve.pointcloud import PointCloudEngine

    n_dev = jax.device_count()
    shards = 8 if n_dev >= 8 else (2 if n_dev >= 2 else 1)
    if shards == 1:
        _emit("spec_sharded", 0.0,
              "unavailable=single-device (set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8)")
        return
    spec = lite_spec(pointclouds.N_CLASSES).replace(
        n_points=128, embed_dim=16, k_neighbors=8,
        precision="fp32").serving(data_shards=shards)
    params = PM.pointmlp_init(jax.random.PRNGKey(0), spec.to_model_config())
    pts, _ = pointclouds.make_batch(jax.random.PRNGKey(1), spec.n_points,
                                    shards)
    eng = PointCloudEngine(params, spec, max_batch=shards, seed=0)
    eng.warmup()                 # keep compile time out of the row
    t0 = time.time()
    sps, _ = sp.measure(eng, pts, iters=1)
    _emit("spec_sharded", (time.time() - t0) * 1e6,
          f"data_shards={shards};devices={n_dev};SPS={sps:.1f}")


def bench_spec_plan() -> None:
    """Stage-plan rows: mixed precision ladder point + plan breakdown.

    ``spec_mixed`` serves a per-stage-override spec (int8 stages 1-3,
    fp32 stage 4 + head) through the engine and reports throughput plus
    an accuracy proxy (mean |logits - fp32 logits|) next to the
    all-fp32 / all-int8 endpoints — the paper's per-layer quantization
    exploration as one spec field, expected to land *between* the two
    uniform rows on both axes.  ``plan_breakdown`` prints the compiled
    plan's per-stage FLOPs / weight-bytes for the mixed row.
    """
    import time as _time

    import jax
    import jax.numpy as jnp

    from benchmarks import serve_pointcloud as sp
    from repro.api import build, lite_spec
    from repro.data import pointclouds
    from repro.models import pointmlp as PM
    from repro.serve.pointcloud import PointCloudEngine

    base = lite_spec(pointclouds.N_CLASSES).replace(
        n_points=128, embed_dim=16, k_neighbors=8,
        precision="fp32").serving()
    params = PM.pointmlp_init(jax.random.PRNGKey(0), base.to_model_config())
    pts, _ = pointclouds.make_batch(jax.random.PRNGKey(1), base.n_points, 8)

    rows = {
        "spec_allfp32": base,
        "spec_mixed": base.replace(
            stage_precision=("int8", "int8", "int8", "fp32")),
        "spec_allint8": base.replace(precision="int8"),
    }
    # Every row serves the same queue from the same seed, so the
    # per-row logits are comparable; the fp32 row is the accuracy-proxy
    # reference (its own err is 0 by construction).  Compile (warmup)
    # and the err computation stay outside the timed region — the time
    # column covers only measure(), like the sibling spec rows.
    ref_logits = None
    for name, spec in rows.items():
        eng = PointCloudEngine(params, spec, max_batch=4, seed=0)
        eng.warmup()
        logits = eng.classify(pts)
        if ref_logits is None:
            ref_logits = logits
        err = float(jnp.mean(jnp.abs(logits - ref_logits)))
        t0 = _time.time()
        sps, _ = sp.measure(eng, pts, iters=1)
        _emit(name, (_time.time() - t0) * 1e6,
              f"stage_precision="
              f"{'/'.join(eng.pipeline.plan.stage_precision)};"
              f"err_vs_fp32={err:.5f};SPS={sps:.1f}")

    pipe = build(rows["spec_mixed"], params)
    br = {}
    for row in pipe.cost_breakdown():
        stage = row["op"].split(".")[0]
        agg = br.setdefault(stage, {"flops": 0, "w_bytes": 0})
        agg["flops"] += row["flops"]
        agg["w_bytes"] += row["w_bytes"]
    _emit("plan_breakdown", 0.0,
          ";".join(f"{s}={v['flops'] / 1e6:.2f}MF/{v['w_bytes']}B"
                   for s, v in br.items()))


def bench_spec_async() -> None:
    """One row per registered batching policy (async engine smoke).

    Drives ``AsyncPointCloudEngine`` over the same tiny spec as
    ``bench_specs`` through a burst of single-cloud submissions, pumped
    sans-IO (no event loop, no sleeps), so the CI ``--quick`` smoke
    exercises the submit/pump/flush scheduler and every ``POLICIES``
    entry end-to-end.
    """
    import jax

    from repro.api import lite_spec
    from repro.api.build import build
    from repro.data import pointclouds
    from repro.models import pointmlp as PM
    from repro.serve.async_engine import AsyncPointCloudEngine
    from repro.serve.policy import POLICIES

    base = lite_spec(pointclouds.N_CLASSES).replace(
        n_points=128, embed_dim=16, k_neighbors=8,
        precision="fp32").serving(slo_ms=5.0)
    params = PM.pointmlp_init(jax.random.PRNGKey(0), base.to_model_config())
    pipeline = build(base, params)
    pts, _ = pointclouds.make_batch(jax.random.PRNGKey(1), base.n_points, 10)
    for name in POLICIES.names():
        eng = AsyncPointCloudEngine(pipeline, max_batch=4, policy=name,
                                    seed=0)
        eng.warmup()
        t0 = time.time()
        futures = [eng.submit(p) for p in pts]
        while eng.pump():
            pass
        eng.flush()
        assert all(f.done() for f in futures), f"policy {name} lost requests"
        s = eng.stats
        _emit(f"spec_async_{name}", (time.time() - t0) * 1e6,
              f"policy={name};requests={s.requests};batches={s.batches};"
              f"padded={s.padded};SPS={s.samples_per_s:.1f}")


def bench_fleet() -> None:
    """One ``fleet_<policy>`` row per batching policy (fleet smoke).

    Serves a two-tier pool (int8 lite + fp32 "elite" of the same tiny
    model) x2 replicas to two tenants — a tight-SLO real-time stream
    with a small ``max_inflight`` bulkhead and a patient bulk tenant —
    through :class:`repro.serve.fleet.PipelineFleet`, submitting both
    tenants' traffic in bursts so admission control sheds some of the
    real-time tenant's burst.  Each row reports aggregate SPS, the
    shed rate (gated by ``scripts/bench_diff.py --shed-tol``), and
    per-tenant p50/p99 wait.
    """
    import jax

    from repro.api import FleetSpec, TenantSpec, lite_spec
    from repro.data import pointclouds
    from repro.models import pointmlp as PM
    from repro.serve.fleet import Overloaded, PipelineFleet
    from repro.serve.policy import POLICIES

    base = lite_spec(pointclouds.N_CLASSES).replace(
        n_points=128, embed_dim=16, k_neighbors=8,
        precision="fp32").serving(slo_ms=5.0)
    tiers = (base.replace(name="fleet-lite", precision="int8"),
             base.replace(name="fleet-elite"))
    params = {s.name: PM.pointmlp_init(jax.random.PRNGKey(0),
                                       s.to_model_config())
              for s in tiers}
    pts, _ = pointclouds.make_batch(jax.random.PRNGKey(1),
                                    base.n_points, 12)
    for policy in POLICIES.names():
        spec = FleetSpec(
            pipelines=tuple(t.replace(policy=policy) for t in tiers),
            tenants=(TenantSpec("rt", "fleet-lite", slo_ms=0.0,
                                max_inflight=4),
                     TenantSpec("bulk", "fleet-elite", slo_ms=0.0)),
            replicas=2, max_batch=4)
        fleet = PipelineFleet.from_specs(spec, params, seed=0)
        fleet.warmup()               # keep compile time out of the row
        t0 = time.time()
        for p in pts:                # both tenants burst, no pumping:
            for tenant in ("rt", "bulk"):     # rt's bulkhead sheds
                try:
                    fleet.submit(tenant, p)
                except Overloaded:
                    pass
        while fleet.pump():
            pass
        fleet.flush()
        us = (time.time() - t0) * 1e6
        s = fleet.stats()
        ts = fleet.tenant_stats()
        offered = s["requests"] + s["shed"]
        waits = ";".join(
            f"{t}_p50={ts[t]['p50_ms']:.2f};{t}_p99={ts[t]['p99_ms']:.2f}"
            for t in sorted(ts) if ts[t]["p50_ms"] is not None)
        _emit(f"fleet_{policy}", us,
              f"policy={policy};requests={s['requests']};"
              f"shed={s['shed']};shed_rate={s['shed'] / offered:.3f};"
              f"{waits};SPS={s['samples_per_s']:.1f}")


def bench_stream() -> None:
    """``stream_cold`` / ``stream_cached`` rows: the temporal cache.

    Serves the same 16-frame coherent stream
    (``pointclouds.make_stream``, per-frame drift well under the cached
    row's threshold) through a direct
    :class:`repro.serve.streaming.StreamSession` twice:

    * ``stream_cold``  — drift threshold 0.0, so every frame misses and
      takes the full recompute path (FPS sampling + kNN every frame);
    * ``stream_cached`` — threshold 1.0, so all but frame 0 replay the
      cached FPS indices and neighbor lists (15/16 hit rate).

    The FPS sampler makes the win structural — caching skips its
    sequential selection loop *and* the kNN searches — while results
    stay bit-identical to the cold path (the ``tests/serving`` golden
    contract).  Each row reports SPS and ``cache_hit_rate``; the hit
    rate is gated by ``scripts/bench_diff.py --hit-tol``.
    """
    import jax
    import numpy as np

    from repro.api import lite_spec
    from repro.api.build import build
    from repro.data import pointclouds
    from repro.models import pointmlp as PM
    from repro.serve.streaming import StreamSession

    # 256 points (vs the 128-point spec_* rows): enough FPS + kNN work
    # that the cache win is structural, not noise-bound, on CPU CI.
    base = lite_spec(pointclouds.N_CLASSES).replace(
        n_points=256, embed_dim=16, k_neighbors=8, precision="fp32",
        sampler="fps", stream=True).serving()
    params = PM.pointmlp_init(jax.random.PRNGKey(0),
                              base.to_model_config())
    seq, _ = pointclouds.make_stream(jax.random.PRNGKey(1),
                                     base.n_points, 16, drift=0.01)
    frames = [np.asarray(f) for f in seq]
    for name, thr in (("stream_cold", 0.0), ("stream_cached", 1.0)):
        pipe = build(base.replace(stream_drift_threshold=thr), params)
        warm = StreamSession(pipe, seed=0)
        for f in frames[:2]:         # compile both paths pre-timer
            warm.infer(f)
        sess = StreamSession(pipe, seed=0)
        t0 = time.time()
        out = [sess.infer(f) for f in frames]
        jax.block_until_ready(out[-1])
        us = (time.time() - t0) * 1e6
        sps = len(frames) / (us / 1e6)
        _emit(name, us,
              f"frames={sess.stats.frames};hits={sess.stats.hits};"
              f"cache_hit_rate={sess.stats.hit_rate:.3f};SPS={sps:.1f}")


def bench_serve_pointcloud(quick: bool) -> None:
    from benchmarks import serve_pointcloud
    for name, us, derived in serve_pointcloud.rows(
            n_requests=8 if quick else 20, iters=1 if quick else 3):
        _emit(name, us, derived.replace(",", ";"))


def bench_tune_quick() -> None:
    """The roofline-guided spec autotuner, CI-sized (``--tune-quick``).

    Runs ``repro.tune.tune`` over the quick search space of a tiny
    serving spec (the same 128-point miniature the ``spec_*`` rows
    use): every candidate is scored statically from its stage plan's
    cost breakdown through the roofline hardware model, the top-K
    estimates plus the fp32-ref anchor get real measurements, and the
    rows — estimated vs measured SPS, err-vs-fp32, frontier flags —
    land in the CSV *and* the ``--json`` artifact (they are already
    artifact rows).
    """
    from repro.api import lite_spec
    from repro.data import pointclouds
    from repro.tune import tune

    base = lite_spec(pointclouds.N_CLASSES).replace(
        n_points=128, embed_dim=16, k_neighbors=8, precision="fp32")
    t0 = time.time()
    doc = tune(base, top_k=3, seed=0)
    us = (time.time() - t0) * 1e6
    measured = [r for r in doc["rows"] if r["measured_sps"] is not None]
    front = [r for r in doc["rows"] if r["frontier"]]
    _emit("tune_quick", us,
          f"candidates={len(doc['rows'])};measured={len(measured)};"
          f"frontier={len(front)};rev={doc['rev']}")
    # The tuner rows are artifact rows already — merge them verbatim
    # (dropping the odd duplicate if a quick row reused a name).
    seen = {r["name"] for r in _ROWS}
    for row in doc["rows"]:
        tag = ("anchor" if row["anchor"]
               else "frontier" if row["frontier"]
               else "measured" if row["measured_sps"] is not None
               else "est")
        est = (f"{row['estimated_sps']:.1f}"
               if row["estimated_sps"] is not None else "-")
        line = f"tune[{tag}] {row['name']}: est_sps={est}"
        if row["measured_sps"] is not None:
            line += (f" measured_sps={row['measured_sps']:.1f}"
                     f" err_vs_fp32={row['err_vs_fp32']:.5f}")
        print(line, flush=True)
        if row["name"] not in seen:
            _ROWS.append(row)


def bench_roofline_summary(dryrun_dir: str = "artifacts/dryrun/pod") -> None:
    d = pathlib.Path(dryrun_dir)
    if not d.exists():
        _emit("roofline_summary", 0.0, "no dryrun artifacts (run "
              "python -m repro.launch.dryrun)")
        return
    for f in sorted(d.glob("*/*.json")):
        rec = json.loads(f.read_text())
        if rec.get("status") != "ok" or "roofline" not in rec:
            continue
        r = rec["roofline"]
        t_bound = max(r["t_compute"], r["t_memory"], r["t_collective"])
        frac = rec.get("roofline_fraction")
        _emit(f"dryrun_{rec['arch']}_{rec['shape']}", t_bound * 1e6,
              f"bound={r['bottleneck']};frac={frac:.4f}"
              if frac else f"bound={r['bottleneck']}")


def _write_json(path: str) -> None:
    from repro.tune import artifact as art
    out = art.write_artifact(path, art.new_artifact(
        _ROWS, source="benchmarks/run.py"))
    print(f"wrote {out} ({len(_ROWS)} rows, schema {art.SCHEMA})",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the training-based tables")
    ap.add_argument("--tune-quick", action="store_true",
                    help="run only the roofline-guided spec autotuner "
                         "(CI-sized search space)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the rows as a schema-versioned "
                         "BENCH_<rev>.json artifact (repro.tune.artifact)")
    ap.add_argument("--table1-steps", type=int, default=120)
    ap.add_argument("--fig4-steps", type=int, default=100)
    args = ap.parse_args()
    from repro.launch.profile import configure_compile_cache
    configure_compile_cache()

    print("name,us_per_call,derived")
    if args.tune_quick:
        bench_tune_quick()
        if args.json:
            _write_json(args.json)
        return
    bench_kernels()
    bench_table2()
    bench_table3()
    bench_specs()
    bench_spec_plan()
    bench_spec_sharded()
    bench_spec_async()
    bench_fleet()
    bench_stream()
    bench_serve_pointcloud(args.quick)
    if not args.quick:
        bench_table1(args.table1_steps)
        bench_fig4(args.fig4_steps, max(30, args.fig4_steps // 2))
    bench_roofline_summary()
    if args.json:
        _write_json(args.json)


if __name__ == "__main__":
    main()
