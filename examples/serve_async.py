"""Async point-cloud serving demo: bursty clients, SLO-aware batching.

Clients submit single clouds at random (exponential) inter-arrival
times; a background ``serve_loop`` pumps the engine, whose batching
policy arbitrates throughput (full fixed-shape batches) against the
per-request latency SLO.  Double-buffered dispatch overlaps host-side
pad/stack of the next batch with device compute of the current one.

    PYTHONPATH=src python examples/serve_async.py \
        --requests 12 --batch 4 --policy deadline --slo-ms 20 \
        [--int8] [--gap-ms 5]
"""
import argparse
import asyncio
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

from repro.api import BACKENDS, lite_spec  # noqa: E402
from repro.api.build import build  # noqa: E402
from repro.data import pointclouds  # noqa: E402
from repro.launch.profile import configure_compile_cache  # noqa: E402
from repro.models import pointmlp as PM  # noqa: E402
from repro.serve.async_engine import AsyncPointCloudEngine  # noqa: E402
from repro.serve.policy import POLICIES  # noqa: E402


async def serve(args) -> None:
    spec = lite_spec(pointclouds.N_CLASSES).replace(
        precision="int8" if args.int8 else "fp32",
        backend=args.backend).serving(policy=args.policy,
                                      slo_ms=args.slo_ms)
    params = PM.pointmlp_init(jax.random.PRNGKey(args.seed),
                              spec.to_model_config())
    print("serving random-init weights (see examples/serve_pointcloud.py "
          "for the trained flow)")
    engine = AsyncPointCloudEngine(build(spec, params),
                                   max_batch=args.batch, seed=args.seed)
    print(engine.describe())
    print(f"warmup/compile: {engine.warmup():.2f}s")

    pts, labels = pointclouds.make_batch(jax.random.PRNGKey(args.seed + 1),
                                         spec.n_points, args.requests)
    names = pointclouds.CLASS_NAMES
    server = asyncio.create_task(engine.serve_loop(tick_s=1e-3))

    async def client(i: int) -> None:
        t0 = time.monotonic()
        logits = await engine.classify_async(pts[i])
        lat_ms = (time.monotonic() - t0) * 1e3
        print(f"  request {i:2d}: predicted "
              f"{names[int(np.argmax(logits))]:<9} "
              f"(true {names[int(labels[i])]})  latency {lat_ms:6.1f} ms")

    rng = np.random.RandomState(args.seed)
    clients = []
    for i in range(args.requests):
        clients.append(asyncio.create_task(client(i)))
        await asyncio.sleep(float(rng.exponential(args.gap_ms / 1e3)))
    # Close only after every client has submitted, and *before* awaiting
    # them: a throughput-greedy policy (fixed) holds the partial tail
    # until the serve_loop's shutdown flush — gathering first would
    # deadlock on the tail's futures.
    await asyncio.sleep(0)
    engine.close()
    await server
    await asyncio.gather(*clients)

    s = engine.stats
    line = (f"{s.requests} requests in {s.batches} fixed-shape batches "
            f"({s.padded} pad lanes) — {s.samples_per_s:.1f} samples/s")
    if engine.latencies_ms:
        lat = np.asarray(engine.latencies_ms)
        line += (f", p50/p95 queue latency "
                 f"{np.percentile(lat, 50):.1f}/"
                 f"{np.percentile(lat, 95):.1f} ms")
    print(line)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch", type=int, default=4,
                    help="fixed dispatch shape of the engine")
    ap.add_argument("--policy", choices=sorted(POLICIES.names()),
                    default="deadline")
    ap.add_argument("--slo-ms", type=float, default=20.0,
                    help="per-request latency objective (deadline policy)")
    ap.add_argument("--gap-ms", type=float, default=5.0,
                    help="mean client inter-arrival time")
    ap.add_argument("--int8", action="store_true",
                    help="serve the int8 deployment instead of fused fp32")
    ap.add_argument("--backend", choices=sorted(BACKENDS.names()),
                    default="ref")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    configure_compile_cache()
    asyncio.run(serve(args))


if __name__ == "__main__":
    main()
