"""Streaming LiDAR demo: one sensor, a temporal cache, one hard cut.

A spinning LiDAR hands the engine *nearly the same* cloud every frame.
``spec.replace(stream=True)`` makes that a first-class serving mode: a
``StreamSession`` caches the expensive mapping ops (FPS/URS sample
indices, kNN neighbor lists, the seg-head upsample index) against a
key frame and replays them while per-point drift stays under
``stream_drift_threshold`` — and every replayed frame is required to
be **bit-identical** to the cold recompute, so caching is purely a
performance decision (same contract as batching and sharding).

The demo drives three phases over a synthetic drifting sequence:
smooth drift (cache hits), a scene cut (automatic miss + re-key), and
an explicit ``reset()`` (sensor re-mount).  A segmentation variant
(``head="seg"``) shows the same session API returning per-point
logits.

    PYTHONPATH=src python examples/serve_stream.py \
        [--frames 24] [--n-points 256] [--threshold 0.05]
"""
import argparse
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _mod, _p in (("repro", _ROOT / "src"), ("benchmarks", _ROOT)):
    try:
        __import__(_mod)
    except ImportError:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import build, lite_spec  # noqa: E402
from repro.data import pointclouds  # noqa: E402
from repro.launch.profile import configure_compile_cache  # noqa: E402
from repro.models import pointmlp as PM  # noqa: E402
from repro.serve.pointcloud import PointCloudEngine  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description="streaming LiDAR demo")
    ap.add_argument("--frames", type=int, default=24)
    ap.add_argument("--n-points", type=int, default=256)
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="per-point drift (max L2) that invalidates "
                         "the temporal cache")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    configure_compile_cache()

    spec = lite_spec(pointclouds.N_CLASSES).replace(
        n_points=args.n_points, embed_dim=16, k_neighbors=8,
        sampler="fps", stream=True,
        stream_drift_threshold=args.threshold).serving()
    params = PM.pointmlp_init(jax.random.PRNGKey(args.seed),
                              spec.to_model_config())
    print("serving random-init weights (see examples/serve_pointcloud.py "
          "for the trained flow)")

    engine = PointCloudEngine(params, spec, max_batch=1)
    print(f"warmup/compile: {engine.warmup():.2f}s")
    sess = engine.open_stream()

    # A drifting sequence: frame-to-frame motion well under the
    # threshold, so steady scanning replays the cached mapping.
    frames, _ = pointclouds.make_stream(jax.random.PRNGKey(1),
                                        args.n_points, args.frames,
                                        drift=0.01)
    frames = np.asarray(frames)

    # Phase 1 — steady scan: frame 0 is the cold key, the rest hit.
    t0 = time.perf_counter()
    for frame in frames:
        sess.infer(frame)
    dt = time.perf_counter() - t0
    s = sess.stats
    print(f"\nsteady scan: {s.frames} frames, {s.hits} hits "
          f"({s.hit_rate:.0%}), {len(frames) / dt:.1f} frames/s")

    # Phase 2 — scene cut: a jump past the threshold re-keys the cache
    # automatically (one miss), then hits resume on the new scene.
    cut = frames[-1] + np.float32([1.0, 0.0, 0.0])
    print(f"\nscene cut: drift {sess.drift(cut):.2f} > "
          f"{args.threshold:g} -> miss + re-key")
    sess.infer(cut)
    sess.infer(cut + np.float32(0.001))
    s = sess.stats
    print(f"  now {s.misses} misses total, hits resumed "
          f"(hit rate {s.hit_rate:.0%})")

    # Phase 3 — explicit reset (sensor re-mounted): next frame is cold
    # by decree, and the replay is still bit-identical to cold compute.
    sess.reset()
    cached = np.asarray(sess.infer(frames[3]))
    cold = np.asarray(
        PointCloudEngine(params, spec,
                         max_batch=1).classify(frames[3][None]))[0]
    print(f"\nafter reset(): resets={sess.stats.resets}, "
          f"cold-vs-stream bitwise equal: "
          f"{bool(np.array_equal(cached, cold))}")

    # Segmentation head: same session API, per-point [N, C] logits.
    seg_spec = spec.replace(head="seg")
    seg_engine = PointCloudEngine(
        PM.pointmlp_init(jax.random.PRNGKey(args.seed),
                         seg_spec.to_model_config()),
        seg_spec, max_batch=1)
    seg = seg_engine.open_stream()
    logits = seg.infer(frames[0])
    print(f"\nseg head: per-point logits {tuple(logits.shape)}, "
          f"{int(np.asarray(logits).argmax(-1).max()) + 1} classes seen")


if __name__ == "__main__":
    main()
