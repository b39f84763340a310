"""Point-cloud serving demo: train briefly, freeze, drain a ragged queue.

The deployment story of the paper end-to-end: a (miniature) QAT-trained
PointMLP-Lite is frozen into inference-only params (BN fused, optional
int8 export) and served through the batched fixed-shape engine — the
software rendering of the FPGA's streaming pipeline.

    PYTHONPATH=src python examples/serve_pointcloud.py \
        --requests 11 --batch 4 [--int8] [--train-steps 60]
"""
import argparse
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _mod, _p in (("repro", _ROOT / "src"), ("benchmarks", _ROOT)):
    try:
        __import__(_mod)
    except ImportError:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402

from repro.api import PipelineSpec, lite_spec  # noqa: E402
from repro.data import pointclouds  # noqa: E402
from repro.launch.profile import configure_compile_cache  # noqa: E402
from repro.models import pointmlp as PM  # noqa: E402
from repro.serve.pointcloud import PointCloudEngine  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=11,
                    help="ragged queue length (any size; engine pads)")
    ap.add_argument("--batch", type=int, default=4,
                    help="fixed dispatch batch of the engine")
    ap.add_argument("--int8", action="store_true",
                    help="serve the int8 deployment instead of fused fp32")
    ap.add_argument("--backend",
                    choices=("ref", "pallas_interpret", "pallas"),
                    default="ref")
    ap.add_argument("--train-steps", type=int, default=0,
                    help="miniature-train first (0 = random weights demo)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    configure_compile_cache()

    spec = lite_spec(pointclouds.N_CLASSES)
    if args.train_steps > 0:
        from benchmarks._pointmlp_train import scale_down, train_eval
        spec = PipelineSpec.from_model_config(
            scale_down(spec.to_model_config()))
        params, oa, _ = train_eval(spec.to_model_config(),
                                   steps=args.train_steps, seed=args.seed)
        print(f"trained {args.train_steps} steps: overall acc {oa:.3f}")
    else:
        params = PM.pointmlp_init(jax.random.PRNGKey(args.seed),
                                  spec.to_model_config())
        print("serving random-init weights (pass --train-steps to train)")

    # The serving spec: deployment precision + backend + streaming-batch
    # semantics (shared URS sampler, per-cloud normalization).
    spec = spec.replace(precision="int8" if args.int8 else "fp32",
                        backend=args.backend).serving()
    engine = PointCloudEngine(params, spec, max_batch=args.batch,
                              seed=args.seed)
    print(engine.describe())
    print(f"warmup/compile: {engine.warmup():.2f}s")

    pts, labels = pointclouds.make_batch(jax.random.PRNGKey(args.seed + 1),
                                         spec.n_points, args.requests)
    pred = engine.predict(pts)
    names = pointclouds.CLASS_NAMES
    for i in range(args.requests):
        print(f"  request {i:2d}: predicted {names[int(pred[i])]:<9} "
              f"(true {names[int(labels[i])]})")
    s = engine.stats
    print(f"{s.requests} requests in {s.batches} fixed-shape batches "
          f"({s.padded} pad lanes) — {s.samples_per_s:.1f} samples/s")


if __name__ == "__main__":
    main()
