"""Async point-cloud serving: futures, SLO-aware batching, double buffering.

:class:`~repro.serve.pointcloud.PointCloudEngine` drains a queue the
caller has already assembled; real traffic arrives ragged and bursty,
one cloud at a time, and a synchronous engine leaves the device idle
while the host pads and converts the next batch.
:class:`AsyncPointCloudEngine` closes both gaps over *any*
:class:`~repro.api.build.FrozenPipeline` (every registered backend —
``ref`` | ``pallas_interpret`` | ``pallas``, fp32 or int8 — gets async
serving for free):

* **Request queue + futures** — ``submit(cloud)`` (or, for part
  segmentation, ``submit(points, category)``) enqueues one request and
  returns a :class:`ServeFuture` resolved when its dispatch completes;
  requests are served FIFO.
* **Pluggable batching policy** — a
  :class:`~repro.serve.policy.BatchPolicy` (``fixed`` | ``deadline``
  from the ``POLICIES`` registry, named by ``PipelineSpec.policy`` /
  ``slo_ms``) decides on every ``pump()`` whether the queue is worth a
  fixed-shape dispatch now.
* **Double-buffered dispatch** — ``pipeline.infer`` is an asynchronous
  dispatch in JAX, so the engine enqueues batch N+1 (host-side
  stack/pad + device transfer) *before* retiring batch N, so batch N
  is retired while N+1 runs: host prep of the next batch and the
  retire of the last one overlap device compute, the software
  rendering of the stall-free deep pipelining that PointAcc / Neu et
  al. get from hardware FIFOs.  At most one dispatch is in flight; its
  futures resolve when the next dispatch is enqueued, on an idle
  ``pump()``, or at ``flush()``.
* **One host copy per dispatch** — the dispatch's logits start their
  device-to-host copy as soon as they are enqueued
  (``copy_to_host_async``), so the copy waits for that batch alone,
  never for the batch enqueued after it.  Retiring reads the one
  ``[max_batch, ...]`` host block and resolves each future with a
  read-only numpy view of its row: no device program runs per row.

Spans and counters
------------------
Each dispatch runs four host regions, each under a
``jax.profiler.TraceAnnotation`` (on the profiler's clock, beside the
device planes) and a ``PointCloudStats`` timer on
``time.perf_counter()``: ``serve.stage`` / ``host_s`` (queue pop,
stack, pad), ``serve.enqueue`` / ``enqueue_s`` (the asynchronous
``pipeline.infer*`` call), ``serve.wait`` / ``wait_s`` (block until the
device finishes, then take the dispatch's one host copy of its logits)
and ``serve.resolve`` / ``resolve_s`` (host work only: resolve futures
with their rows, run their done-callbacks, refresh stream caches).  All
four spans of one dispatch carry its sequence number as ``dispatch``.
A ``pump()`` that neither dispatches nor retires opens no span.

LFSR contract (and why it differs from the sync engine)
-------------------------------------------------------
Every dispatch starts from the engine's *seed* LFSR state instead of
threading the advanced state across dispatches.  Combined with
``spec.serving()`` semantics (shared URS sampler + per-sample norm)
and the single fixed dispatch shape, a request's logits are
bit-identical regardless of which dispatch batch it lands in, which
co-batched requests surround it, and what the policy decided —
batching is purely a performance decision, invisible to results.
This is the paper's "initialize the LFSRs with the same starting
states" deployment contract, and it is what lets ``tests/serving``
assert golden equivalence against solo sync runs.  (The sync engine
instead advances one persistent state across calls — its results
deliberately depend on the dispatch index; see its LFSR tests.)

Driving the engine
------------------
Sans-IO and deterministic — the scheduler only acts inside ``pump()``,
and all timing flows through an injectable ``clock``::

    eng = AsyncPointCloudEngine(pipeline, max_batch=8,
                                policy="deadline", clock=virtual_clock)
    fut = eng.submit(cloud)
    eng.pump()        # policy check; maybe dispatch; retire finished work
    eng.flush()       # drain everything; all futures resolve
    fut.result()

(see ``tests/serving/harness.py`` for the virtual-clock trace driver),
or under asyncio for real traffic::

    server = asyncio.create_task(eng.serve_loop())
    logits = await eng.classify_async(cloud)
    eng.close(); await server
"""
from __future__ import annotations

import asyncio
import collections
import dataclasses
import time
import warnings
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.build import FrozenPipeline, build
from repro.serve import batching
from repro.serve.batching import PointCloudStats
from repro.serve.policy import BatchPolicy, make_policy

__all__ = ["AsyncPointCloudEngine", "ServeFuture"]


def _is_ready(arr) -> bool:
    """True when the device has finished computing ``arr`` (conservative
    True when the runtime lacks a readiness probe: callers then block,
    the pre-probe behavior)."""
    probe = getattr(arr, "is_ready", None)
    return bool(probe()) if callable(probe) else True


class ServeFuture:
    """Completion handle for one submitted cloud.

    Resolved by the engine (never by callers) with the request's
    logits row (``[n_classes]``, or ``[N, parts]`` for segmentation): a
    read-only ``np.ndarray`` view of its dispatch's one host block.
    ``t_submit`` / ``t_done`` are stamped from the engine's clock — wall
    time in production, virtual time under the test harness — so
    ``latency_ms`` is exact either way.
    """

    __slots__ = ("request_id", "t_submit", "t_done", "_value", "_done",
                 "_callbacks")

    def __init__(self, request_id: int, t_submit: float):
        self.request_id = request_id
        self.t_submit = t_submit
        self.t_done: Optional[float] = None
        self._value = None
        self._done = False
        self._callbacks: List[Callable] = []

    def done(self) -> bool:
        return self._done

    def result(self) -> np.ndarray:
        """The logits row, a read-only host ``np.ndarray``; raises while
        pending (pump/flush the engine)."""
        if not self._done:
            raise RuntimeError(
                f"request {self.request_id} is still pending — drive the "
                f"engine (pump()/flush()/serve_loop) before result()")
        return self._value

    def add_done_callback(self, fn: Callable[["ServeFuture"], None]) -> None:
        """Call ``fn(self)`` on resolution (immediately if already done).

        Callback exceptions are contained (reported as a
        ``RuntimeWarning``), matching asyncio's convention — one
        client's bad callback must not strand its co-batched requests.
        """
        if self._done:
            self._run_callback(fn)
        else:
            self._callbacks.append(fn)

    def _run_callback(self, fn: Callable) -> None:
        try:
            fn(self)
        except Exception as e:  # noqa: BLE001 — containment is the point
            warnings.warn(
                f"ServeFuture done-callback for request {self.request_id} "
                f"raised {type(e).__name__}: {e}", RuntimeWarning,
                stacklevel=2)

    @property
    def latency_ms(self) -> Optional[float]:
        """Submit-to-resolve latency on the engine clock (None if pending)."""
        if self.t_done is None:
            return None
        return (self.t_done - self.t_submit) * 1e3

    def _resolve(self, value: np.ndarray, t_done: float) -> None:
        assert not self._done, "a request resolves exactly once"
        self._value = value
        self.t_done = t_done
        self._done = True
        callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            self._run_callback(fn)


@dataclasses.dataclass
class _Inflight:
    """One dispatched batch whose device compute may still be running."""
    futures: List[ServeFuture]
    logits: jax.Array            # [max_batch, ...], copying to host
    seq: int                     # dispatch sequence number (span id)
    # Per-future stream info, parallel to ``futures`` (None for plain
    # requests): ("hit", state, cache_rows) | ("miss", state, cloud).
    stream: List = dataclasses.field(default_factory=list)
    # Collect-path cache output (batch-leading pytree) for a cold
    # dispatch on a streaming pipeline; miss sessions refresh from
    # their row at retire time.  None for cached/plain dispatches.
    cache: object = None


class AsyncPointCloudEngine:
    """SLO-aware async serving over a frozen pipeline.

    Args:
      pipeline: any :class:`~repro.api.build.FrozenPipeline` (build one
        with ``repro.api.build.build(spec.serving(...), params)``), or
        use :meth:`from_params` for the sync-engine-style convenience
        surface.
      max_batch: the one fixed dispatch shape; partial dispatches are
        zero-padded to it (shared core in ``repro.serve.batching``).
      policy: a :class:`~repro.serve.policy.BatchPolicy` instance, a
        ``POLICIES`` registry key, or None to use the pipeline spec's
        ``policy`` / ``slo_ms`` fields.
      seed: LFSR seed; every dispatch restarts from this state (see the
        module docstring for the dispatch-invariance contract).
      clock: monotonic seconds source for request timing and policy
        wait computation — injectable so tests run on a virtual clock.
      calibrate_every: recalibrate a calibratable policy
        (``POLICIES["cost"]``) every this many dispatches, from the
        *sliding window* of measurements since the last calibration —
        so a long-running ``serve_loop`` tracks service-time drift
        without anyone calling :meth:`calibrate_policy` by hand
        (that explicit call remains as a forced refresh).  0 disables
        the periodic update.
    """

    def __init__(self, pipeline: FrozenPipeline, max_batch: int = 8,
                 policy=None, seed: int = 0,
                 clock: Callable[[], float] = time.monotonic,
                 calibrate_every: int = 64):
        if not isinstance(pipeline, FrozenPipeline):
            raise TypeError(
                "AsyncPointCloudEngine wraps a FrozenPipeline; build one "
                "with repro.api.build.build(spec, params) or use "
                "AsyncPointCloudEngine.from_params(params, spec, ...)")
        self.pipeline = pipeline
        self.spec = pipeline.spec
        if not (self.spec.shared_urs and self.spec.per_sample_norm):
            # The whole async contract — bit-identical results
            # regardless of batching, pad lanes that cannot leak —
            # rests on the streaming-batch semantics.
            raise ValueError(
                "AsyncPointCloudEngine needs a serving spec (shared_urs "
                "+ per_sample_norm); build the pipeline from "
                "spec.serving()")
        self.cfg = pipeline.model_config
        self.max_batch = int(max_batch)
        batching.check_shard_batch(self.max_batch, self.spec.data_shards)
        if policy is None:
            policy = self.spec.policy
        self.policy: BatchPolicy = make_policy(
            policy, slo_ms=self.spec.slo_ms,
            dispatch_ms=self.spec.dispatch_ms)
        self.stats = PointCloudStats()
        # Per-request latency log, resolve order; bounded so an
        # always-on server never grows it past the recent window.
        # ``reset_stats()`` clears it together with ``stats``.
        self.latencies_ms: collections.deque = collections.deque(
            maxlen=10_000)
        self._clock = clock
        if not isinstance(calibrate_every, int) or calibrate_every < 0:
            raise ValueError(f"calibrate_every must be a non-negative "
                             f"int, got {calibrate_every!r}")
        self.calibrate_every = calibrate_every
        # Sliding-window origin for the periodic recalibration: the
        # (batches, enqueue_s, wait_s) reading at the last calibration.
        self._cal_origin = (0, 0.0, 0.0)
        # One stream per dispatch lane, sized from max_batch (the old
        # 64-stream floor under-provisioned max_batch > 64).
        self._lfsr0 = pipeline.seed_state(seed, self.max_batch)
        self._queue: collections.deque = collections.deque()
        self._inflight: Optional[_Inflight] = None
        self._seq = 0
        self._dispatches = 0
        self._closed = False

    @classmethod
    def from_params(cls, params, spec, **kwargs) -> "AsyncPointCloudEngine":
        """Build the pipeline and the engine in one call (the sync
        engine's ``(params, spec)`` surface)."""
        spec.validate()
        return cls(build(spec, params), **kwargs)

    # ------------------------------------------------------ sans-IO ----

    def submit(self, points, category=None) -> ServeFuture:
        """Enqueue one request; returns its future (FIFO service).

        A request is one [N, 3] cloud, or for a part-segmentation
        pipeline (``head="partseg"``) the points [N, 6] (xyz and
        normals) and the object category: ``submit(points, category)``.
        """
        if self._closed:
            raise RuntimeError("engine is closed")
        if self.cfg.head == "partseg":
            cloud = batching.request_payload(self.cfg, self._seq, points,
                                             category)
        else:
            if category is not None:
                raise ValueError(
                    f"request {self._seq}: head={self.cfg.head!r} takes "
                    f"the cloud alone; got a category too")
            cloud = np.asarray(points, np.float32)
            if cloud.shape != (self.cfg.n_points, self.cfg.in_channels):
                raise ValueError(
                    f"submit() takes one [N={self.cfg.n_points}, "
                    f"{self.cfg.in_channels}] cloud; got shape {cloud.shape}")
        fut = ServeFuture(self._seq, self._clock())
        self._seq += 1
        self._queue.append((cloud, fut, None))
        return fut

    def _submit_stream(self, cloud, state, hit: bool) -> ServeFuture:
        """Internal entry point for :class:`~repro.serve.streaming.
        AsyncStreamSession` (the cloud is already validated there).
        Hit frames snapshot the session's current cache rows so a
        later ``reset()`` cannot strand a queued frame."""
        if self._closed:
            raise RuntimeError("engine is closed")
        fut = ServeFuture(self._seq, self._clock())
        self._seq += 1
        info = ("hit", state, state.cache) if hit else ("miss", state, cloud)
        self._queue.append((cloud, fut, info))
        return fut

    def open_stream(self, *, max_age=None):
        """A future-returning :class:`~repro.serve.streaming.
        AsyncStreamSession` over this engine's submit path.  Stream
        frames co-batch with plain requests and other sessions' frames
        (cache-replay dispatches and full-recompute dispatches never
        mix — see ``_dispatch``).  Requires a ``stream=True`` spec."""
        from repro.serve import streaming
        streaming._require_streaming(self.pipeline)
        return streaming.AsyncStreamSession(
            self._submit_stream, n_points=self.cfg.n_points,
            threshold=self.spec.stream_drift_threshold, max_age=max_age)

    def pump(self, block: bool = True) -> int:
        """One scheduler turn; returns how many requests were dispatched.

        Asks the policy whether the queue is worth a dispatch at the
        current clock reading.  On a dispatch, the previous in-flight
        batch is retired *after* the new one is enqueued (the double
        buffer); on an idle turn, in-flight work is retired so futures
        resolve promptly.

        Args:
          block: on an idle turn, wait for the in-flight batch to
            finish (the sans-IO default — deterministic settling for
            the virtual-clock harness).  ``block=False`` retires only
            work the device has already finished, so a cooperative
            scheduler (``serve_loop``) never stalls its event loop on
            device compute.
        """
        self._maybe_recalibrate()
        depth = len(self._queue)
        oldest_wait_ms = 0.0
        if depth:
            oldest_wait_ms = (self._clock()
                              - self._queue[0][1].t_submit) * 1e3
        n = self.policy.decide(depth=depth, oldest_wait_ms=oldest_wait_ms,
                               max_batch=self.max_batch)
        n = max(0, min(n, depth, self.max_batch))
        if n == 0:
            self._retire(wait=block)
            return 0
        self._dispatch(n)
        return n

    def flush(self) -> None:
        """Drain the queue (policy bypassed) and resolve every future."""
        while self._queue:
            self._dispatch(min(len(self._queue), self.max_batch))
        self._retire()

    @property
    def depth(self) -> int:
        """Queued (not yet dispatched) request count."""
        return len(self._queue)

    @property
    def pending(self) -> int:
        """Requests not yet resolved: queued + in flight on device."""
        inflight = len(self._inflight.futures) if self._inflight else 0
        return len(self._queue) + inflight

    def reset_stats(self) -> None:
        """Open a fresh measurement window: zero ``stats`` *and* clear
        the latency log, so window percentiles never mix eras.  The
        recalibration window origin resets with it."""
        self.stats.reset()
        self.latencies_ms.clear()
        self._cal_origin = (0, 0.0, 0.0)

    def calibrate_policy(self) -> bool:
        """Force-refresh a calibratable policy (``POLICIES["cost"]``)
        from the *cumulative* stats: the ``stats.serve_s /
        stats.batches`` per-dispatch average at this engine's
        ``max_batch``, divided by ``spec.data_shards``, becomes the
        policy's dispatch-size-aware service estimate.  Returns True
        when the policy accepted a calibration (False for fixed-model
        policies or an empty window).

        With ``calibrate_every > 0`` this runs periodically on its own
        inside :meth:`pump` (so ``serve_loop`` self-calibrates from a
        sliding window of recent dispatches); the explicit call remains
        as the forced refresh and restarts the periodic window."""
        calibrate = getattr(self.policy, "calibrate", None)
        if calibrate is None or self.stats.batches == 0:
            return False
        calibrate(self.stats, self.max_batch,
                  data_shards=self.spec.data_shards)
        self._cal_origin = self._cal_reading()
        return True

    def _maybe_recalibrate(self) -> None:
        """The periodic sliding-window update: once ``calibrate_every``
        dispatches have accrued since the last calibration, fit the
        policy's cost model from exactly that window (recent drift —
        thermal, contention, shape changes — shows up; ancient history
        does not) and restart the window."""
        if not self.calibrate_every:
            return
        calibrate = getattr(self.policy, "calibrate", None)
        if calibrate is None:
            return
        batches0, enqueue_s0, wait_s0 = self._cal_origin
        window_batches = self.stats.batches - batches0
        if window_batches < self.calibrate_every:
            return
        window = PointCloudStats(batches=window_batches,
                                 enqueue_s=self.stats.enqueue_s - enqueue_s0,
                                 wait_s=self.stats.wait_s - wait_s0)
        calibrate(window, self.max_batch,
                  data_shards=self.spec.data_shards)
        self._cal_origin = self._cal_reading()

    def _cal_reading(self):
        return (self.stats.batches, self.stats.enqueue_s,
                self.stats.wait_s)

    def warmup(self) -> float:
        """Compile the one ``(max_batch, n_points)`` executable ahead of
        traffic (no queue interaction, no LFSR consumption — dispatches
        restart from the seed state anyway).  Returns compile seconds."""
        dummy = batching.zeros_batch(self.cfg, self.max_batch)
        t0 = time.perf_counter()
        if self.pipeline.streaming:
            # Streaming dispatches run the collect/cached executables,
            # not the plain one — compile both.
            logits, _, cache = self.pipeline.infer_collect(
                dummy, jnp.array(self._lfsr0))
            cached, _ = self.pipeline.infer_cached(
                dummy, jnp.array(self._lfsr0), cache)
            jax.block_until_ready((logits, cached))
        else:
            logits, _ = self.pipeline.infer(dummy, jnp.array(self._lfsr0))
            jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        self.stats.compile_s += dt
        return dt

    def describe(self) -> str:
        return (f"{self.pipeline.describe()}\n"
                f"  max_batch : {self.max_batch}\n"
                f"  policy    : {self.policy.describe()}")

    # ------------------------------------------------ dispatch core ----

    def _dispatch(self, n: int) -> None:
        seq = self._dispatches
        self._dispatches += 1
        t_stage = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve.stage", dispatch=seq):
            taken, batch, pad, stream, cache_in = self._stage(n)
        t_enqueue = time.perf_counter()
        self.stats.host_s += t_enqueue - t_stage

        # Enqueue batch N+1 on the device, *then* retire batch N: the
        # block on N overlaps with N+1's H2D transfer + compute, and the
        # stack/pad above overlapped with N's compute.  The returned
        # LFSR state is discarded — every dispatch restarts from the
        # seed state (dispatch-invariance contract; for streams this is
        # what makes a cached frame bit-identical to its cold replay).
        with jax.profiler.TraceAnnotation("serve.enqueue", dispatch=seq):
            cache_out = None
            if cache_in is not None:
                logits, _ = self.pipeline.infer_cached(
                    batch, jnp.array(self._lfsr0), cache_in)
            elif self.pipeline.streaming:
                # Collect-path logits are bit-identical to plain infer,
                # so plain requests keep golden equivalence; only miss
                # sessions read their cache row back at retire time.
                logits, _, cache_out = self.pipeline.infer_collect(
                    batch, jnp.array(self._lfsr0))
            else:
                logits, _ = self.pipeline.infer(batch,
                                                jnp.array(self._lfsr0))
            # Start the one host copy now: it waits for this batch
            # alone, not for the batch enqueued after it.
            logits.copy_to_host_async()
        self.stats.enqueue_s += time.perf_counter() - t_enqueue
        nxt = _Inflight([f for _, f, _ in taken], logits, seq, stream,
                        cache_out)
        self._retire()
        self._inflight = nxt
        self.stats.batches += 1
        self.stats.padded += pad
        self.stats.requests += len(taken)

    def _stage(self, n: int):
        """Pop up to ``n`` queued requests and build the dispatch's host
        inputs: ``(taken, batch, pad, stream, cache_in)``, where
        ``cache_in`` holds the stacked cache rows of a cache-replay
        dispatch (None otherwise)."""
        streaming = self.pipeline.streaming
        if streaming:
            # Homogeneous-prefix run: one dispatch is either a
            # cache-replay batch (all stream hits -> infer_cached) or a
            # full-recompute batch (plain requests + stream misses ->
            # infer_collect) — never mixed.  Trim n to the longest
            # same-kind prefix; the remainder stays queued (FIFO order
            # preserved) for the next pump.
            def _is_hit(entry):
                return entry[2] is not None and entry[2][0] == "hit"
            lead = _is_hit(self._queue[0])
            run = 1
            while run < n and _is_hit(self._queue[run]) == lead:
                run += 1
            n = run
        taken = [self._queue.popleft() for _ in range(n)]
        now = self._clock()
        self.stats.queued_s += sum(now - f.t_submit for _, f, _ in taken)
        if self.cfg.head == "partseg":
            chunk = batching.stack_payloads([c for c, _, _ in taken])
        else:
            chunk = batching.stack_requests([c for c, _, _ in taken],
                                            self.cfg.n_points,
                                            self.cfg.in_channels)
        batch, pad = batching.pad_to_batch(chunk, self.max_batch)
        stream = [s for _, _, s in taken]
        cache_in = None
        if streaming and stream[0] is not None and stream[0][0] == "hit":
            # Stack the sessions' per-lane cache rows; pad lanes replay
            # zero indices (index 0 everywhere — valid, computed, never
            # returned, exactly like zero-padded clouds).
            rows = [s[2] for s in stream]
            rows += [jax.tree_util.tree_map(jnp.zeros_like, rows[0])
                     ] * pad
            cache_in = jax.tree_util.tree_map(
                lambda *r: jnp.stack(r), *rows)
        return taken, batch, pad, stream, cache_in

    def _retire(self, wait: bool = True) -> None:
        if self._inflight is None:
            return
        if not wait and not _is_ready(self._inflight.logits):
            return                       # device still busy; try later
        t_wait = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve.wait",
                                          dispatch=self._inflight.seq):
            rows = np.asarray(jax.block_until_ready(
                self._inflight.logits))
        t_resolve = time.perf_counter()
        self.stats.wait_s += t_resolve - t_wait
        inflight, self._inflight = self._inflight, None
        with jax.profiler.TraceAnnotation("serve.resolve",
                                          dispatch=inflight.seq):
            now = self._clock()
            for i, fut in enumerate(inflight.futures):
                fut._resolve(rows[i], now)
                self.latencies_ms.append(fut.latency_ms)
                info = (inflight.stream[i] if i < len(inflight.stream)
                        else None)
                if (info is not None and info[0] == "miss"
                        and inflight.cache is not None):
                    _, state, cloud = info
                    state.refresh(
                        jax.tree_util.tree_map(lambda a, i=i: a[i],
                                               inflight.cache), cloud)
        self.stats.resolve_s += time.perf_counter() - t_resolve
        self.stats.retired += 1

    # ------------------------------------------------ asyncio shell ----

    async def classify_async(self, points, category=None) -> np.ndarray:
        """Submit one cloud and await its logits row (a read-only host
        ``np.ndarray``, as :meth:`ServeFuture.result` returns).

        Needs something pumping the engine concurrently — run
        :meth:`serve_loop` as a background task.
        """
        loop = asyncio.get_running_loop()
        afut = loop.create_future()

        def on_done(fut: ServeFuture) -> None:
            def settle() -> None:
                if not afut.done():
                    afut.set_result(fut.result())
            loop.call_soon_threadsafe(settle)

        self.submit(points, category).add_done_callback(on_done)
        return await afut

    async def serve_loop(self, tick_s: float = 0.001) -> None:
        """Background dispatcher: pump every ``tick_s`` until
        :meth:`close`, then flush.  The only place the engine sleeps —
        the sans-IO core stays wall-clock free for deterministic tests.
        Pumps with ``block=False`` so an idle tick never stalls the
        event loop on device compute (submissions keep flowing while
        the in-flight batch runs).
        """
        while not self._closed:
            self.pump(block=False)
            await asyncio.sleep(tick_s)
        self.flush()

    def close(self) -> None:
        """Stop accepting requests; a running serve_loop flushes and
        exits.  Call ``flush()`` directly when driving sans-IO."""
        self._closed = True
