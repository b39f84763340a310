"""Batched point-cloud inference engine (HLS4PC deployment path).

The serving analogue of the paper's streaming FPGA pipeline: a
:class:`~repro.api.spec.PipelineSpec` is compiled once by
``repro.api.build`` — BN folded into (w, b), optional int8 export, the
sampler/grouper/backend registry keys resolved, the fixed-shape forward
jitted — and the engine drains a ragged request queue in pad-to-batch
chunks against that frozen executable:

* fused fp32 layers lower through whatever backend entry the spec
  names (``ref`` | ``pallas_interpret`` | ``pallas``);
* the URS sampler runs off a *persistent* LFSR state held by the engine
  — the deployment PRNG contract of the paper: one sampler module
  services the whole batch, so results are queue-order invariant and
  state advances deterministically across calls;
* the LFSR buffer is donated to each jitted call, and the one
  ``(max_batch, n_points)`` executable ``classify`` dispatches can be
  compiled ahead of traffic with ``warmup()``.

Legacy construction — ``PointCloudEngine(params, cfg, quantize=True,
backend="pallas")`` — still works through ``repro.api.compat`` and
emits a ``DeprecationWarning`` (escalated to an error for in-tree
callers by the pytest config).
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.api import compat
from repro.api.build import build
from repro.api.spec import PipelineSpec
from repro.serve import batching
from repro.serve.batching import PointCloudStats

__all__ = ["PointCloudEngine", "PointCloudStats"]

_UNSET = object()


class PointCloudEngine:
    """Fixed-shape batched classifier over a frozen pipeline.

    Args:
      params: trained parameter tree (BN running stats populated).
      spec: a :class:`~repro.api.spec.PipelineSpec` naming the variant
        to freeze and serve — typically ``lite_spec(...).serving()``;
        ``.serving()`` turns on the streaming-batch semantics (shared
        URS sampler + per-cloud normalization) that make results
        queue-order invariant and keep pad lanes from leaking.  A
        legacy :class:`~repro.models.pointmlp.PointMLPConfig` is also
        accepted together with the old ``quantize=``/``backend=``
        kwargs, mapped through ``repro.api.compat`` with a
        ``DeprecationWarning``.
      max_batch: fixed dispatch batch; ragged queues are padded/chunked.
      seed: LFSR seed — must match training for the paper's
        "same starting states" deployment contract.
    """

    def __init__(self, params: Dict, spec, max_batch: int = 8,
                 quantize=_UNSET, backend=_UNSET, seed: int = 0):
        if isinstance(spec, PipelineSpec):
            if quantize is not _UNSET or backend is not _UNSET:
                raise TypeError(
                    "quantize=/backend= are legacy kwargs; with a "
                    "PipelineSpec, set spec.precision / spec.backend")
            spec.validate()
        else:  # legacy (cfg, quantize=, backend=) surface
            spec = compat.engine_legacy_spec(
                spec,
                quantize=None if quantize is _UNSET else quantize,
                backend=None if backend is _UNSET else backend)
        self.max_batch = int(max_batch)
        batching.check_shard_batch(self.max_batch, spec.data_shards)
        self.pipeline = build(spec, params, donate_lfsr=True)
        self.spec = self.pipeline.spec
        self.cfg = self.pipeline.model_config
        self.params = self.pipeline.params
        self.stats = PointCloudStats()
        self._seed = int(seed)
        # One LFSR stream per dispatch lane — sized from max_batch (the
        # historical 64-stream floor silently under-provisioned
        # max_batch > 64; pipeline.infer now rejects short states).
        self._lfsr = self.pipeline.seed_state(seed, self.max_batch)

    def warmup(self) -> float:
        """Compile the ``(max_batch, n_points)`` executable — the one
        shape ``classify`` dispatches — ahead of traffic (does not
        consume LFSR state).  Returns compile seconds."""
        b = self.max_batch
        dummy = jnp.zeros((b, self.cfg.n_points, 3), jnp.float32)
        t0 = time.perf_counter()
        logits, _ = self.pipeline.infer(dummy, jnp.array(self._lfsr))
        logits.block_until_ready()
        dt = time.perf_counter() - t0
        self.stats.compile_s += dt
        return dt

    # ------------------------------------------------------- serving ----

    def _chunk_queue(self, pts: jnp.ndarray) -> List[jnp.ndarray]:
        """Host-side queue prep: split to ``max_batch`` chunks, zero-pad
        the last (shared core in ``repro.serve.batching``).  Kept out of
        the serve timer — it is array plumbing, not device throughput."""
        chunks = []
        for chunk in batching.split_queue(pts, self.max_batch):
            chunk, pad = batching.pad_to_batch(chunk, self.max_batch)
            self.stats.padded += pad
            chunks.append(chunk)
        return chunks

    def classify(self, points) -> jnp.ndarray:
        """Classify a ragged queue of point clouds.

        Args:
          points: [R, N, 3] array (or list of [N, 3] clouds) with
            N == cfg.n_points; R is arbitrary — the queue is chunked to
            ``max_batch`` and the last chunk zero-padded.

        Returns: logits [R, n_classes] — rows only for the R real
        requests; pad lanes are computed but never returned.

        ``stats.enqueue_s`` times the dispatch loop's enqueue calls and
        ``stats.wait_s`` the block on the last one (together
        ``stats.serve_s``); padding/conversion lands in ``stats.host_s``.
        """
        t_host = time.perf_counter()
        pts = batching.as_point_queue(points, self.cfg.n_points)
        if pts.shape[0] == 0:                       # drained queue
            if self.cfg.head == "seg":
                return jnp.zeros(
                    (0, self.cfg.n_points, self.cfg.n_classes),
                    jnp.float32)
            return jnp.zeros((0, self.cfg.n_classes), jnp.float32)
        r = pts.shape[0]
        chunks = self._chunk_queue(pts)
        self.stats.host_s += time.perf_counter() - t_host

        t_enqueue = time.perf_counter()
        out = []
        for j, chunk in enumerate(chunks):
            logits, self._lfsr = self.pipeline.infer(chunk, self._lfsr)
            out.append(logits[:min(self.max_batch, r - j * self.max_batch)])
            self.stats.batches += 1
        t_wait = time.perf_counter()
        self.stats.enqueue_s += t_wait - t_enqueue
        jax.block_until_ready(out[-1])
        self.stats.wait_s += time.perf_counter() - t_wait
        self.stats.requests += r
        return jnp.concatenate(out, axis=0)

    def predict(self, points) -> jnp.ndarray:
        """Top-1 class ids for a ragged queue — [R] for the cls head,
        [R, n_points] for seg."""
        return jnp.argmax(self.classify(points), axis=-1).astype(jnp.int32)

    def open_stream(self, *, max_age=None, batch=None):
        """A blocking :class:`~repro.serve.streaming.StreamSession` over
        this engine's pipeline, seeded with the engine's seed (every
        stream frame restarts from the seed LFSR state — the streaming
        transport contract — so sessions never consume or perturb the
        engine's persistent queue state).  Requires a ``stream=True``
        spec.
        """
        from repro.serve.streaming import StreamSession
        return StreamSession(self.pipeline, seed=self._seed,
                             max_age=max_age, batch=batch)

    def describe(self) -> str:
        """The frozen pipeline's description plus serving shape."""
        return (f"{self.pipeline.describe()}\n"
                f"  max_batch : {self.max_batch}")

    @property
    def lfsr_state(self) -> jnp.ndarray:
        """Persistent URS sampler state (uint32 streams).

        Returns a copy: the internal buffer is donated to the next
        ``classify`` dispatch and would otherwise be deleted under a
        caller-held reference on donation-honoring backends."""
        return jnp.array(self._lfsr)
