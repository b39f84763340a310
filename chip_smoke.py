"""Chip smoke test: drive the point-cloud serving path once on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the paths that span chips

One process, the PipelineSpec default widths, random weights from a
fixed seed (``PM.pointmlp_init``).  One chip runs, in order:

  kernels      the int8 matmul and kNN Pallas kernels against their
               references at plan shapes;
  fps-walk     the whole-walk FPS kernel against XLA's ``sampling.fps``
               on 64 seeded clouds at every FPS stage of the benchmark's
               Elite and part-segmentation configurations: clouds may
               differ only where ``decisions.py`` finds an FPS step open;
  lite-int8    Lite (512 points, URS, W8A8, ``backend="pallas"``): a
               ragged queue through ``PointCloudEngine``, checked against
               the same W8A8 forward in plain XLA and against the ``ref``
               backend's W8A32; single clouds through
               ``AsyncPointCloudEngine`` on the same pipeline;
  elite-fp32   Elite (1024 points, FPS, fp32, ``backend="pallas"``);
  lite-stream  a Lite FPS stream session: one miss, then hits.

``--chips 4`` runs only the sharded Lite dispatch (``data_shards=4``)
and a two-tier fleet on the 2x2 replica x data mesh, each against its
one-device reference.

Every pallas forward must hold ``tpu_custom_call`` in its compiled HLO,
and every reference comparison must pass; any failure raises, so the
process exits non-zero before the last line.  Without a TPU it exits
non-zero and prints no result.  The ``setup`` lines are compile and
warm-up seconds, not throughput.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

# This checkout's sources only: a copy of this file alone must fail.
ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.api import (FleetSpec, TenantSpec, build, elite_spec,  # noqa: E402
                       lite_spec)
from repro.core import sampling  # noqa: E402
from repro.core.quant import compute_scale, quantize  # noqa: E402
from repro.data import pointclouds  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels import ref as kernel_ref  # noqa: E402
from repro.kernels.fps import fps_walk_pallas  # noqa: E402
from repro.kernels.knn import knn_pallas  # noqa: E402
from repro.launch.profile import configure_compile_cache  # noqa: E402
from repro.models import pointmlp as PM  # noqa: E402
from repro.serve.async_engine import AsyncPointCloudEngine  # noqa: E402
from repro.serve.fleet import PipelineFleet  # noqa: E402
from repro.serve.pointcloud import PointCloudEngine  # noqa: E402
from repro.serve.streaming import replay_reference  # noqa: E402
from repro.tune.kernels import plan_shapes  # noqa: E402

SEED = 0
N_CLASSES = 40
MAX_BATCH = 8
QUEUE = 11                # one full dispatch plus a padded tail of 3
ASYNC_REQUESTS = 3
STREAM_FRAMES = 4
STREAM_DRIFT = 0.01       # per-frame rigid motion of make_stream
STREAM_THRESHOLD = 0.1    # drift vs the key frame that still replays
FPS_CLOUDS = 64
#: The benchmark's configurations and modules naming their FPS stages.
BENCH = ROOT / "benchmarks" / "chip"
FPS_CONFIGS = (("pointmlp-elite", "decisions"),
               ("pointmlp-partseg", "partseg_decisions"))

#: Pallas fp32 vs the ref backend, both at "highest" matmul precision:
#: f32 rounding through ~20 layers, far below a broken kernel (O(1)).
FP32_TOL = 5e-3
#: Pallas int8 vs the same W8A8 forward in plain XLA, as a fraction of
#: the reference's max |logit|: bit-identical on the CPU; room for an
#: activation-rounding flip where XLA fuses differently, 5x under the
#: W8A8-vs-W8A32 gap and far below a broken kernel.
W8A8_REL_TOL = 0.01
#: Pallas int8 (W8A8) vs the ref backend's int8 (W8A32, no activation
#: quantization), as a fraction of the reference's max |logit|: the
#: 8-bit activation rounding through the stack, not kernel error.
INT8_REL_TOL = 0.1


class SmokeFailure(AssertionError):
    """A phase produced a wrong or missing result."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CacheEvents:
    """Counts JAX persistent-cache hits and writes between snapshots."""

    HIT = "/jax/compilation_cache/cache_hits"
    WRITE = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.hits = self.writes = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.WRITE:
            self.writes += 1

    def mark(self):
        return self.hits, self.writes

    def since(self, mark) -> str:
        hits, writes = self.hits - mark[0], self.writes - mark[1]
        state = "warm" if hits and not writes else "cold"
        return f"cache={state} hits={hits} writes={writes}"


def setup(name: str, cache: CacheEvents, fn) -> None:
    """Run ``fn`` (a compile/warm-up) and print its set-up seconds."""
    mark = cache.mark()
    t0 = time.perf_counter()
    fn()
    print(f"setup {name}: {time.perf_counter() - t0:.3f}s "
          f"{cache.since(mark)}", flush=True)


def kernels_compiled(name: str, jitted, *args) -> None:
    """The compiled forward must call Mosaic kernels, not interpret."""
    text = jitted.lower(*args).compile().as_text()
    n = text.count("tpu_custom_call")
    require(n > 0, f"{name}: no tpu_custom_call in the compiled forward")
    print(f"kernels {name}: tpu_custom_call x{n}", flush=True)


def check_logits(name: str, got, shape) -> "object":
    got = np.asarray(got)
    require(got.shape == shape, f"{name}: shape {got.shape} != {shape}")
    require(bool(np.all(np.isfinite(got))), f"{name}: non-finite logits")
    return got


def compare(name: str, got, want, tol: float) -> None:
    """Max |got - want| <= tol, and the same argmax on every row whose
    top-2 margin exceeds twice the difference (other rows cannot
    decide an argmax)."""
    diff = float(np.max(np.abs(got - want)))
    top2 = np.sort(want, axis=-1)[..., -2:]
    decisive = (top2[..., 1] - top2[..., 0]) > 2 * diff
    same = np.argmax(got, -1) == np.argmax(want, -1)
    print(f"maxabs {name}: {diff!r} tol={tol!r} "
          f"argmax_equal={int(same[decisive].sum())}/"
          f"{int(decisive.sum())} decisive rows, "
          f"{int(same.sum())}/{same.size} all rows", flush=True)
    require(diff <= tol, f"{name}: max-abs {diff} > tol {tol}")
    require(bool(np.all(same[decisive])), f"{name}: argmax differs")


def bitwise(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    require(got.shape == want.shape and np.array_equal(got, want),
            f"{name}: not bit-identical")
    print(f"bitwise {name}: identical {got.shape}", flush=True)


def init_params(spec):
    return PM.pointmlp_init(jax.random.PRNGKey(SEED), spec.to_model_config())


def clouds(n_points: int, count: int, salt: int):
    pts, _ = pointclouds.make_batch(
        jax.random.fold_in(jax.random.PRNGKey(SEED), salt), n_points, count)
    return pts


def check_forward(name: str, engine) -> None:
    pts = jnp.zeros((engine.max_batch, engine.cfg.n_points, 3), jnp.float32)
    kernels_compiled(name, engine.pipeline._fn, engine.params, pts,
                     engine.lfsr_state)


# ------------------------------------------------------------ one chip --

def phase_kernels(_cache: CacheEvents) -> None:
    """Kernel-level references at plan shapes: the int8 matmul is
    bit-identical to its integer reference; kNN (at "highest" matmul
    precision) picks neighbours whose exact distances match the exact
    k nearest."""
    key = jax.random.PRNGKey(SEED)
    m, k, n = plan_shapes(lite_spec(N_CLASSES))["int8_matmul"]
    x = jax.random.normal(key, (m, k))
    wq = jax.random.randint(jax.random.fold_in(key, 1), (k, n), -128, 128,
                            jnp.int8)
    ws = jax.random.uniform(jax.random.fold_in(key, 2), (n,)) * 0.1
    a_scale = compute_scale(x, 8)
    xq = quantize(x, a_scale, 8).astype(jnp.int8)
    want = kernel_ref.int8_matmul_ref(
        xq, wq, (a_scale * ws.reshape(1, -1)).astype(jnp.float32))
    bitwise(f"int8_matmul {m}x{k}x{n} vs integer reference",
            ops.int8_matmul(x, wq, ws), want)

    s, n_pts, kk = plan_shapes(elite_spec(N_CLASSES))["knn"]
    pts = np.asarray(clouds(n_pts, 1, salt=9)[0], np.float64)
    smp = pts[:s]
    with jax.default_matmul_precision("highest"):
        idx = np.asarray(knn_pallas(jnp.asarray(smp, jnp.float32),
                                    jnp.asarray(pts, jnp.float32), kk))
    d = ((smp[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    got = np.sort(np.take_along_axis(d, idx, axis=1), axis=1)
    exact = np.sort(d, axis=1)[:, :kk]
    err = float(np.max(np.abs(got - exact)))
    print(f"maxabs knn_pallas {s}x{n_pts} k={kk} neighbour distances: "
          f"{err!r}", flush=True)
    require(idx.shape == (s, kk), f"knn_pallas: shape {idx.shape}")
    require(err <= 1e-4, f"knn_pallas: neighbour distances off by {err}")


def fps_stages(config: str, module: str):
    """(points in, samples out) of each FPS stage of a benchmark
    configuration, as its decisions module walks them."""
    import importlib
    if str(BENCH) not in sys.path:
        sys.path.append(str(BENCH))
    c = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    outs = importlib.import_module(module).stage_samples(c)
    return list(zip([c["n_points"]] + outs[:-1], outs))


def accepted_walks(cloud: np.ndarray, m: int):
    """The FPS walks ``decisions.py`` accepts for one cloud: the walk in
    float64, and each walk taking the runner-up at one of its
    ``MAX_OPEN`` most open steps (one flip per stage, as its paths)."""
    import decisions
    pts = cloud.astype(np.float64)
    found = []
    exact = decisions._fps(pts, m, 0, None, found)
    walks = [exact]
    for o in sorted(found, key=lambda o: o.ratio)[:decisions.MAX_OPEN]:
        walk = decisions._fps(pts, m, 0, o, [])
        if walk is not None:
            walks.append(walk)
    return walks


def phase_fps_walk(cache: CacheEvents) -> None:
    """The whole-walk FPS kernel against XLA's reference walk on the
    chip; a cloud may differ only at an FPS step that float32 rounding
    leaves open (``decisions.py``'s tolerance), and then the kernel's
    walk must be one the benchmark's check accepts."""
    xla = jax.jit(sampling.fps_batched, static_argnums=1)
    for config, module in FPS_CONFIGS:
        for n, m in fps_stages(config, module):
            pts = clouds(n, FPS_CLOUDS, salt=n * 7 + m)
            mark = cache.mark()
            t0 = time.perf_counter()
            got = np.asarray(fps_walk_pallas(pts, m))
            want = np.asarray(xla(pts, m))
            print(f"setup fps-walk {n}->{m}: "
                  f"{time.perf_counter() - t0:.3f}s {cache.since(mark)}",
                  flush=True)
            host = np.asarray(pts)
            differ = [b for b in range(FPS_CLOUDS)
                      if not np.array_equal(got[b], want[b])]
            outside = [b for b in differ
                       if not any(np.array_equal(got[b], w)
                                  for w in accepted_walks(host[b], m))]
            print(f"fps-walk {config} {n}->{m}: {len(differ)} of "
                  f"{FPS_CLOUDS} clouds differ from xla, {len(outside)} "
                  f"outside the open decisions", flush=True)
            require(got.shape == (FPS_CLOUDS, m),
                    f"fps-walk {n}->{m}: shape {got.shape}")
            require(not outside, f"fps-walk {config} {n}->{m}: clouds "
                    f"{outside} differ outside the open decisions")


def phase_lite(cache: CacheEvents) -> None:
    spec = lite_spec(N_CLASSES).replace(backend="pallas").serving()
    params = init_params(spec)
    eng = PointCloudEngine(params, spec, max_batch=MAX_BATCH, seed=SEED)
    setup("lite-int8 pallas", cache, eng.warmup)
    check_forward("lite-int8", eng)
    pts = clouds(spec.n_points, QUEUE, salt=1)
    got = check_logits("lite-int8", eng.classify(pts), (QUEUE, N_CLASSES))

    # The same W8A8 arithmetic with the int8 kernel swapped for its
    # plain-XLA integer reference: the whole forward without Mosaic.
    def xla_int8(x_q, w_q, scale, out_dtype=jnp.float32, **_tiling):
        return kernel_ref.int8_matmul_ref(x_q, w_q, scale, out_dtype)

    with mock.patch.object(ops, "int8_matmul_pallas", xla_int8):
        w8a8 = PointCloudEngine(params, spec, max_batch=MAX_BATCH, seed=SEED)
        setup("lite-int8 w8a8 xla", cache, w8a8.warmup)
        want = np.asarray(w8a8.classify(pts))
    compare("lite-int8 pallas vs w8a8 xla", got, want,
            W8A8_REL_TOL * float(np.max(np.abs(want))))

    ref = PointCloudEngine(params, spec.replace(backend="ref"),
                           max_batch=MAX_BATCH, seed=SEED)
    setup("lite-int8 ref", cache, ref.warmup)
    want = np.asarray(ref.classify(pts))
    compare("lite-int8 pallas(W8A8) vs ref(W8A32)", got, want,
            INT8_REL_TOL * float(np.max(np.abs(want))))

    # Every async dispatch restarts from the seed LFSR state, as the
    # sync engine's first dispatch does: same rows, bit for bit.
    aeng = AsyncPointCloudEngine(eng.pipeline, max_batch=MAX_BATCH,
                                 seed=SEED)
    futures = [aeng.submit(p) for p in pts[:ASYNC_REQUESTS]]
    aeng.flush()
    require(all(f.done() for f in futures), "async: unresolved futures")
    bitwise("lite-int8 async vs sync",
            np.stack([np.asarray(f.result()) for f in futures]),
            got[:ASYNC_REQUESTS])
    aeng.close()


def phase_elite(cache: CacheEvents) -> None:
    spec = elite_spec(N_CLASSES).replace(backend="pallas").serving()
    params = init_params(spec)
    pts = clouds(spec.n_points, MAX_BATCH, salt=2)
    shape = (MAX_BATCH, N_CLASSES)
    eng = PointCloudEngine(params, spec, max_batch=MAX_BATCH, seed=SEED)
    setup("elite-fp32 pallas", cache, eng.warmup)
    check_forward("elite-fp32", eng)
    served = check_logits("elite-fp32", eng.classify(pts), shape)

    with jax.default_matmul_precision("highest"):
        pal = PointCloudEngine(params, spec, max_batch=MAX_BATCH, seed=SEED)
        ref = PointCloudEngine(params, spec.replace(backend="ref"),
                               max_batch=MAX_BATCH, seed=SEED)
        setup("elite-fp32 pallas highest", cache, pal.warmup)
        setup("elite-fp32 ref highest", cache, ref.warmup)
        got = check_logits("elite-fp32 highest", pal.classify(pts), shape)
        want = np.asarray(ref.classify(pts))
    compare("elite-fp32 pallas vs ref, both highest", got, want, FP32_TOL)
    # Not gated: the served forward runs at the default matmul precision
    # (kNN distances included); this line records how far it sits from
    # the f32 reference.
    print(f"maxabs elite-fp32 served(default precision) vs ref highest: "
          f"{float(np.max(np.abs(served - want)))!r} (not gated)",
          flush=True)


def phase_stream(cache: CacheEvents) -> None:
    spec = lite_spec(N_CLASSES).replace(
        backend="pallas", sampler="fps", stream=True,
        stream_drift_threshold=STREAM_THRESHOLD).serving()
    params = init_params(spec)
    eng = PointCloudEngine(params, spec, max_batch=MAX_BATCH, seed=SEED)
    seq, _ = pointclouds.make_stream(
        jax.random.fold_in(jax.random.PRNGKey(SEED), 3), spec.n_points,
        STREAM_FRAMES, drift=STREAM_DRIFT)
    frames = [np.asarray(f) for f in seq]
    pipe = eng.pipeline
    one = frames[0][None]
    lfsr = pipe.seed_state(SEED, 1)

    def compile_stream():
        _, _, cache_rows = pipe.infer_collect(one, lfsr)
        jax.block_until_ready(pipe.infer_cached(one, lfsr, cache_rows))
        kernels_compiled("lite-stream collect", pipe._fn_collect,
                         pipe.params, one, lfsr)
        kernels_compiled("lite-stream cached", pipe._fn_cached,
                         pipe.params, one, lfsr, cache_rows)

    setup("lite-stream pallas", cache, compile_stream)
    sess = eng.open_stream()
    got = [check_logits(f"lite-stream frame {i}", sess.infer(f),
                        (N_CLASSES,)) for i, f in enumerate(frames)]
    st = sess.stats
    print(f"stream: frames={st.frames} hits={st.hits} misses={st.misses}",
          flush=True)
    require(st.misses >= 1 and st.hits >= 1,
            "stream: wanted a miss, then hits")
    bitwise("lite-stream session vs replay_reference",
            np.stack(got),
            np.stack([np.asarray(r) for r in
                      replay_reference(pipe, frames, seed=SEED)]))


# ---------------------------------------------------------- four chips --

def devices_of(arr) -> set:
    return {s.device.id for s in arr.addressable_shards}


def phase_sharded(cache: CacheEvents) -> None:
    spec = lite_spec(N_CLASSES).replace(backend="pallas").serving()
    params = init_params(spec)
    pts = clouds(spec.n_points, QUEUE, salt=1)
    solo = PointCloudEngine(params, spec, max_batch=MAX_BATCH, seed=SEED)
    sharded = PointCloudEngine(params, spec.serving(data_shards=4),
                               max_batch=MAX_BATCH, seed=SEED)
    setup("lite-int8 data_shards=1", cache, solo.warmup)
    setup("lite-int8 data_shards=4", cache, sharded.warmup)
    check_forward("lite-int8 data_shards=4", sharded)
    mesh_ids = {d.id for d in sharded.pipeline.mesh.devices.flat}
    out, _ = sharded.pipeline.infer(pts[:MAX_BATCH], sharded.lfsr_state)
    print(f"sharded: mesh devices {sorted(mesh_ids)}, output shards on "
          f"{sorted(devices_of(out))}", flush=True)
    require(len(mesh_ids) == 4 and devices_of(out) == mesh_ids,
            "sharded: the dispatch does not span four devices")
    bitwise("lite-int8 data_shards=4 vs data_shards=1",
            sharded.classify(pts), solo.classify(pts))


def phase_fleet(cache: CacheEvents) -> None:
    # Two tiers x data_shards=2 fill the 2x2 replica x data mesh with one
    # replica of each (two replicas of each would need eight chips).
    tiers = {
        "rt": lite_spec(N_CLASSES).replace(name="lite", backend="pallas")
        .serving(data_shards=2),
        "bulk": elite_spec(N_CLASSES).replace(name="elite", backend="pallas")
        .serving(data_shards=2),
    }
    params = {s.name: init_params(s) for s in tiers.values()}
    fspec = FleetSpec(
        pipelines=tuple(tiers.values()),
        tenants=tuple(TenantSpec(t, s.name, slo_ms=0.0)
                      for t, s in tiers.items()),
        replicas=1, max_batch=MAX_BATCH)
    fleet = PipelineFleet.from_specs(fspec, params, seed=SEED)
    setup("fleet 2x2", cache, fleet.warmup)
    rows = [sorted(d.id for d in r.engine.pipeline.mesh.devices.flat)
            for r in fleet.replicas]
    print(f"fleet: replica rows {rows}", flush=True)
    require(len({i for row in rows for i in row}) == 4,
            "fleet: replicas do not span four distinct devices")
    for tenant, spec in tiers.items():
        pts = clouds(spec.n_points, 5, salt=4)
        futures = [fleet.submit(tenant, p) for p in pts]
        fleet.flush()
        got = np.stack([np.asarray(f.result()) for f in futures])
        solo = build(spec.serving(data_shards=1), params[spec.name])
        batch = jnp.zeros((MAX_BATCH,) + pts.shape[1:], pts.dtype)
        want, _ = solo.infer(batch.at[:len(pts)].set(pts),
                             solo.seed_state(SEED, MAX_BATCH))
        bitwise(f"fleet tenant {tenant} ({spec.name}) vs solo",
                got, np.asarray(want)[:len(pts)])
    fleet.close()


# ---------------------------------------------------------------- main --

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the paths that span four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this check never falls back to "
              "the CPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    cache_dir = configure_compile_cache()
    held = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({held} entries before this run)",
          flush=True)
    cache = CacheEvents()

    phases = ((phase_sharded, phase_fleet) if args.chips == 4 else
              (phase_kernels, phase_fps_walk, phase_lite, phase_elite,
               phase_stream))
    t0 = time.perf_counter()
    for phase in phases:
        phase(cache)
    print(f"all phases passed in {time.perf_counter() - t0:.3f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
