"""Shared pad/dispatch/unpad core of the point-cloud serving engines.

Both engines — the synchronous queue-draining
:class:`~repro.serve.pointcloud.PointCloudEngine` and the async
double-buffered :class:`~repro.serve.async_engine.AsyncPointCloudEngine`
— serve ragged traffic against one jitted fixed-shape executable.  The
ragged->fixed plumbing lives here exactly once: queue normalization,
``max_batch`` chunking, zero pad-to-batch, request stacking, and the
stats schema both engines report.

A request is a payload: one ``[N, 3]`` cloud, or for a part-segmentation
pipeline (``head="partseg"``) a tuple ``(points [N, C], category)``;
tuples are stacked and padded array by array (:func:`request_payload`,
:func:`stack_payloads`, :func:`pad_to_batch`), and a one-array payload
keeps the plain-array path.

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


def as_point_queue(points, n_points: int, channels: int = 3
                   ) -> jnp.ndarray:
    """Normalize a ragged classify() input to a [R, N, 3] float32 queue
    (``[R, N, channels]`` for points that carry more than xyz).

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
            f"classify() takes [N={n_points}, {channels}] clouds of one "
            f"shape; got a ragged or malformed request list with shapes "
            f"[{_request_shapes(points)}]") from None
    if pts.size == 0:
        return pts.reshape(0, n_points, channels)
    if pts.ndim == 2:
        pts = pts[None]
    if pts.ndim != 3 or pts.shape[1:] != (n_points, channels):
        raise ValueError(
            f"engine is fixed-shape: expected [R, N={n_points}, "
            f"{channels}] (or one [N, {channels}] cloud), got "
            f"{tuple(pts.shape)}")
    return pts


def as_category_queue(category, requests: int, n_categories: int
                      ) -> jnp.ndarray:
    """The categories of a classify() queue of ``requests`` clouds, one
    int32 per request; ``ValueError`` names the first request whose
    category is missing or out of ``[0, n_categories)``."""
    if category is None:
        raise ValueError("classify(): head='partseg' takes (points, "
                         "category); the categories are missing")
    cats = np.asarray(category)
    cats = cats.reshape(1) if cats.ndim == 0 else cats
    if cats.shape != (requests,) or not np.issubdtype(cats.dtype,
                                                      np.integer):
        raise ValueError(f"classify(): expected {requests} integer "
                         f"categories, one per request; got {category!r}")
    bad = np.nonzero((cats < 0) | (cats >= n_categories))[0]
    if bad.size:
        raise ValueError(f"request {int(bad[0])}: the category must be in "
                         f"[0, {n_categories}); got {int(cats[bad[0]])}")
    return jnp.asarray(cats, jnp.int32)


def check_shard_batch(max_batch: int, data_shards: int) -> None:
    """Reject dispatch shapes the device mesh cannot split evenly
    (shared by both engines' constructors, before any mesh exists)."""
    if max_batch % data_shards:
        raise ValueError(
            f"data_shards={data_shards} must divide max_batch evenly: "
            f"got max_batch={max_batch} (every fixed-shape dispatch is "
            f"split across the device mesh)")


def split_queue(pts, max_batch: int) -> Iterator[jnp.ndarray]:
    """Split a [R, N, 3] queue (or a tuple of per-request arrays) into
    <= ``max_batch`` chunks, in order."""
    if isinstance(pts, tuple):
        for i in range(0, pts[0].shape[0], max_batch):
            yield tuple(a[i:i + max_batch] for a in pts)
        return
    for i in range(0, pts.shape[0], max_batch):
        yield pts[i:i + max_batch]


def pad_to_batch(chunk, max_batch: int) -> Tuple[object, int]:
    """Zero-pad a [r <= max_batch, N, 3] chunk to the one dispatch shape
    (a tuple of request arrays: each along its request axis; a pad
    lane's category is 0).

    Returns ``(padded [max_batch, N, 3], n_pad)``.  The fixed shape is
    load-bearing twice over: it keeps the engines on a single jitted
    executable, and — because bit-identity of a lane's result is only
    guaranteed within one executable — it is what makes results
    independent of how the queue was partitioned into dispatches.
    """
    if isinstance(chunk, tuple):
        padded = [pad_to_batch(a, max_batch) for a in chunk]
        return tuple(a for a, _ in padded), padded[0][1]
    r = chunk.shape[0]
    pad = max_batch - r
    if pad < 0:
        raise ValueError(f"chunk of {r} requests exceeds the fixed "
                         f"dispatch shape max_batch={max_batch}")
    if pad:
        chunk = jnp.concatenate(
            [chunk, jnp.zeros((pad,) + chunk.shape[1:], chunk.dtype)],
            axis=0)
    return chunk, pad


def stack_requests(clouds: Sequence, n_points: int, channels: int = 3
                   ) -> jnp.ndarray:
    """Stack single [N, 3] request clouds into a [r, N, 3] chunk
    (``[N, channels]`` for points that carry more than xyz).

    Every cloud is shape-checked *before* ``np.stack`` so a ragged
    request list raises a ``ValueError`` naming the offending shapes
    instead of np.stack's broadcast error (and instead of a bare
    ``assert`` stripped under ``python -O``).
    """
    arrs = [np.asarray(c, np.float32) for c in clouds]
    bad = [(i, a.shape) for i, a in enumerate(arrs)
           if a.shape != (n_points, channels)]
    if bad:
        raise ValueError(
            f"requests must be [N={n_points}, {channels}] clouds; got "
            + "; ".join(f"request {i}: shape {s}" for i, s in bad[:4])
            + (f" (+{len(bad) - 4} more)" if len(bad) > 4 else ""))
    return jnp.asarray(np.stack(arrs, axis=0))


def request_payload(cfg, request: int, points, category):
    """One part-segmentation request as its pipeline takes it:
    ``(points [N, in_channels] float32, category int32)``.

    Raises ``ValueError`` naming ``request`` for a missing category, a
    category out of ``[0, n_categories)``, or points of another shape.
    """
    n, c = cfg.n_points, cfg.in_channels
    cloud = np.asarray(points, np.float32)
    if cloud.shape != (n, c):
        raise ValueError(
            f"request {request}: head={cfg.head!r} takes points "
            f"[N={n}, {c}] (xyz and normals); got shape {cloud.shape}")
    if category is None:
        raise ValueError(
            f"request {request}: head={cfg.head!r} takes (points, "
            f"category); the category is missing")
    cat = np.asarray(category)
    if (cat.shape != () or not np.issubdtype(cat.dtype, np.integer)
            or not 0 <= int(cat) < cfg.n_categories):
        raise ValueError(
            f"request {request}: the category must be one integer in "
            f"[0, {cfg.n_categories}); got {category!r}")
    return cloud, cat.astype(np.int32)


def stack_payloads(payloads: Sequence[tuple]) -> tuple:
    """Stack checked multi-array requests (:func:`request_payload`)
    array by array: ``[(points, category), ...] -> ([r, N, C], [r])``."""
    return tuple(jnp.asarray(np.stack(arrays, axis=0))
                 for arrays in zip(*payloads))


def zeros_batch(cfg, batch: int):
    """An all-zero dispatch of the pipeline's request signature (the
    warm-up shape)."""
    points = jnp.zeros((batch, cfg.n_points, cfg.in_channels), jnp.float32)
    if cfg.head == "partseg":
        return points, jnp.zeros((batch,), jnp.int32)
    return points
