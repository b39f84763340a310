"""Shared pad/dispatch/unpad core of the point-cloud serving engines.

Both engines — the synchronous queue-draining
:class:`~repro.serve.pointcloud.PointCloudEngine` and the async
double-buffered :class:`~repro.serve.async_engine.AsyncPointCloudEngine`
— serve ragged traffic against one jitted fixed-shape executable.  The
ragged->fixed plumbing lives here exactly once: queue normalization,
``max_batch`` chunking, zero pad-to-batch, request stacking, and the
stats schema both engines report.

Pad lanes are computed but never returned, and under ``spec.serving()``
semantics (shared URS sampler + per-sample normalization) they cannot
leak: a real request's logits are bit-identical no matter what occupies
the other slots of its dispatch — padding is invisible to results, so
batching is purely a throughput decision.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class PointCloudStats:
    """The serving-stats schema shared by the sync and async engines.

    The timers are host seconds on ``time.perf_counter()``, one timer per
    region of a dispatch, so they never overlap: ``host_s`` (stage the
    batch), ``enqueue_s`` (the asynchronous ``pipeline.infer*`` call),
    ``wait_s`` (block until the device finishes), ``resolve_s`` (split
    the batch into rows, resolve the futures and run their
    done-callbacks).  ``retired``, ``resolve_s`` and ``queued_s`` are the
    async engine's; the sync engine returns its rows from ``classify``.
    """
    requests: int = 0          # real samples served
    batches: int = 0           # jitted fixed-shape dispatches
    retired: int = 0           # dispatches whose futures were resolved
    padded: int = 0            # dummy pad samples computed
    compile_s: float = 0.0     # time spent in warmup compiles
    host_s: float = 0.0        # stage: queue pop, stack, pad-to-batch
    enqueue_s: float = 0.0     # the pipeline.infer* call (async enqueue)
    wait_s: float = 0.0        # block_until_ready on the dispatch
    resolve_s: float = 0.0     # rows split out, futures and callbacks
    queued_s: float = 0.0      # sum of (dispatch - submit), engine clock

    @property
    def serve_s(self) -> float:
        """Host seconds spent in the enqueue call and blocked on the
        device: ``enqueue_s + wait_s`` (staging and resolving excluded).
        The cost policy calibrates its per-dispatch service time from
        it."""
        return self.enqueue_s + self.wait_s

    @property
    def samples_per_s(self) -> float:
        """Requests over ``serve_s``, on the host clock: host-side queue
        prep (``host_s``) and resolving (``resolve_s``) are left out."""
        return self.requests / max(self.serve_s, 1e-9)

    def reset(self) -> None:
        """Zero every counter/timer (a fresh measurement window)."""
        fresh = PointCloudStats()
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(fresh, f.name))


def _request_shapes(clouds) -> str:
    """The distinct per-request shapes of a ragged input, for errors.

    Defensive by construction — it only runs inside an error path, so
    an element that is itself malformed (nested-ragged, non-numeric)
    must yield a placeholder, never a second exception.
    """
    try:
        items = list(clouds)
    except TypeError:
        return f"<{type(clouds).__name__}>"
    shapes = []
    for c in items:
        try:
            s = str(np.asarray(c).shape)
        except Exception:                     # noqa: BLE001 — see above
            s = f"<ragged {type(c).__name__}>"
        if s not in shapes:
            shapes.append(s)
    return ", ".join(shapes)


def as_point_queue(points, n_points: int) -> jnp.ndarray:
    """Normalize a ragged classify() input to a [R, N, 3] float32 queue.

    Accepts a [R, N, 3] array, a single [N, 3] cloud, a list of clouds,
    or an empty input (R == 0 passes through as an empty queue).
    Malformed input raises ``ValueError`` naming expected vs actual
    shapes (never a bare ``assert`` — those vanish under ``python -O``
    — and never a downstream broadcast error: a ragged request list is
    diagnosed here, before ``jnp.asarray`` would die stacking it).
    """
    try:
        pts = jnp.asarray(points, jnp.float32)
    except (ValueError, TypeError):
        raise ValueError(
            f"classify() takes [N={n_points}, 3] clouds of one shape; "
            f"got a ragged or malformed request list with shapes "
            f"[{_request_shapes(points)}]") from None
    if pts.size == 0:
        return pts.reshape(0, n_points, 3)
    if pts.ndim == 2:
        pts = pts[None]
    if pts.ndim != 3 or pts.shape[1:] != (n_points, 3):
        raise ValueError(
            f"engine is fixed-shape: expected [R, N={n_points}, 3] "
            f"(or one [N, 3] cloud), got {tuple(pts.shape)}")
    return pts


def check_shard_batch(max_batch: int, data_shards: int) -> None:
    """Reject dispatch shapes the device mesh cannot split evenly
    (shared by both engines' constructors, before any mesh exists)."""
    if max_batch % data_shards:
        raise ValueError(
            f"data_shards={data_shards} must divide max_batch evenly: "
            f"got max_batch={max_batch} (every fixed-shape dispatch is "
            f"split across the device mesh)")


def split_queue(pts: jnp.ndarray, max_batch: int) -> Iterator[jnp.ndarray]:
    """Split a [R, N, 3] queue into <= ``max_batch`` chunks, in order."""
    for i in range(0, pts.shape[0], max_batch):
        yield pts[i:i + max_batch]


def pad_to_batch(chunk: jnp.ndarray, max_batch: int
                 ) -> Tuple[jnp.ndarray, int]:
    """Zero-pad a [r <= max_batch, N, 3] chunk to the one dispatch shape.

    Returns ``(padded [max_batch, N, 3], n_pad)``.  The fixed shape is
    load-bearing twice over: it keeps the engines on a single jitted
    executable, and — because bit-identity of a lane's result is only
    guaranteed within one executable — it is what makes results
    independent of how the queue was partitioned into dispatches.
    """
    r, n = chunk.shape[0], chunk.shape[1]
    pad = max_batch - r
    if pad < 0:
        raise ValueError(f"chunk of {r} requests exceeds the fixed "
                         f"dispatch shape max_batch={max_batch}")
    if pad:
        chunk = jnp.concatenate(
            [chunk, jnp.zeros((pad, n, 3), jnp.float32)], axis=0)
    return chunk, pad


def stack_requests(clouds: Sequence, n_points: int) -> jnp.ndarray:
    """Stack single [N, 3] request clouds into a [r, N, 3] chunk.

    Every cloud is shape-checked *before* ``np.stack`` so a ragged
    request list raises a ``ValueError`` naming the offending shapes
    instead of np.stack's broadcast error (and instead of a bare
    ``assert`` stripped under ``python -O``).
    """
    arrs = [np.asarray(c, np.float32) for c in clouds]
    bad = [(i, a.shape) for i, a in enumerate(arrs)
           if a.shape != (n_points, 3)]
    if bad:
        raise ValueError(
            f"requests must be [N={n_points}, 3] clouds; got "
            + "; ".join(f"request {i}: shape {s}" for i, s in bad[:4])
            + (f" (+{len(bad) - 4} more)" if len(bad) > 4 else ""))
    return jnp.asarray(np.stack(arrs, axis=0))
