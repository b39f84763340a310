"""Stats-schema regression tests for both serving engines.

``PointCloudStats`` is the one schema both engines report
(``repro.serve.batching``): counters (requests/batches/padded), timers
(compile_s/host_s/enqueue_s/wait_s/resolve_s, disjoint by
construction), the derived ``serve_s`` and ``samples_per_s``, and
``reset()`` as a fresh measurement window.
"""
import dataclasses

import numpy as np
import pytest
from harness import SEED, VirtualClock

from repro.serve.async_engine import AsyncPointCloudEngine
from repro.serve.batching import PointCloudStats
from repro.serve.pointcloud import PointCloudEngine

FIELDS = ("requests", "batches", "retired", "padded", "compile_s",
          "host_s", "enqueue_s", "wait_s", "resolve_s", "queued_s")


@pytest.fixture()
def async_engine(tiny_pipeline):
    return AsyncPointCloudEngine(tiny_pipeline, max_batch=4,
                                 policy="fixed", seed=SEED,
                                 clock=VirtualClock())


@pytest.fixture(scope="module")
def _sync_engine_shared(tiny_params, tiny_spec):
    return PointCloudEngine(tiny_params, tiny_spec, max_batch=4, seed=SEED)


@pytest.fixture()
def sync_engine(_sync_engine_shared):
    """One compiled sync engine per module; each test opens a fresh
    stats window (exactly what ``reset()`` is for)."""
    _sync_engine_shared.stats.reset()
    return _sync_engine_shared


class TestSchema:
    def test_both_engines_share_the_one_stats_class(self, async_engine,
                                                    sync_engine):
        assert type(async_engine.stats) is PointCloudStats
        assert type(sync_engine.stats) is PointCloudStats
        assert tuple(f.name for f in
                     dataclasses.fields(PointCloudStats)) == FIELDS

    def test_sync_reexport_is_the_shared_class(self):
        """The pre-refactor import path keeps working."""
        from repro.serve.pointcloud import PointCloudStats as FromSync
        assert FromSync is PointCloudStats


class TestAsyncAccounting:
    def test_counters_after_mixed_dispatches(self, async_engine, clouds):
        futures = [async_engine.submit(c) for c in clouds[:7]]
        async_engine.pump()                      # full batch of 4
        async_engine.flush()                     # padded tail of 3
        s = async_engine.stats
        assert s.requests == 7 and s.batches == 2 and s.padded == 1
        assert all(f.done() for f in futures)
        assert s.serve_s > 0.0 and s.host_s >= 0.0
        assert s.samples_per_s == s.requests / s.serve_s

    def test_warmup_lands_in_compile_s_not_serve_s(self, async_engine):
        assert async_engine.warmup() > 0.0
        s = async_engine.stats
        assert s.compile_s > 0.0
        assert s.serve_s == 0.0 and s.requests == 0 and s.batches == 0

    def test_reset_opens_a_fresh_window(self, async_engine, clouds):
        async_engine.submit(clouds[0])
        async_engine.flush()
        async_engine.warmup()
        s = async_engine.stats
        assert s.requests and s.batches and s.compile_s > 0.0
        s.reset()
        for name in FIELDS:
            assert getattr(s, name) == 0, name
        # the engine keeps serving into the fresh window
        async_engine.submit(clouds[1])
        async_engine.flush()
        assert s.requests == 1 and s.batches == 1

    def test_latency_log_tracks_requests(self, async_engine, clouds):
        for c in clouds[:5]:
            async_engine.submit(c)
        async_engine.flush()
        assert len(async_engine.latencies_ms) == 5
        assert all(lat >= 0.0 for lat in async_engine.latencies_ms)

    def test_reset_stats_clears_latency_window_too(self, async_engine,
                                                   clouds):
        """Window percentiles never mix eras: ``reset_stats()`` zeroes
        the counters *and* the latency log (a bounded deque, so an
        always-on server never leaks)."""
        for c in clouds[:3]:
            async_engine.submit(c)
        async_engine.flush()
        assert len(async_engine.latencies_ms) == 3
        async_engine.reset_stats()
        assert async_engine.stats.requests == 0
        assert len(async_engine.latencies_ms) == 0
        assert async_engine.latencies_ms.maxlen is not None
        async_engine.submit(clouds[0])
        async_engine.flush()
        assert len(async_engine.latencies_ms) == 1


class TestSyncAccounting:
    """Regression coverage for the sync engine's accounting split
    (serve_s = jitted dispatch loop only; host prep in host_s)."""

    def test_host_and_serve_timers_both_populate(self, sync_engine, clouds):
        sync_engine.warmup()
        out = sync_engine.classify([np.asarray(c) for c in clouds[:3]])
        assert out.shape[0] == 3
        s = sync_engine.stats
        assert s.serve_s > 0.0 and s.host_s > 0.0
        assert s.compile_s > 0.0
        assert s.samples_per_s == s.requests / s.serve_s

    def test_empty_queue_touches_no_counters(self, sync_engine):
        sync_engine.classify([])
        s = sync_engine.stats
        assert s.requests == 0 and s.batches == 0 and s.serve_s == 0.0

    def test_reset_then_reuse(self, sync_engine, clouds):
        sync_engine.classify(clouds[:2])
        sync_engine.stats.reset()
        sync_engine.classify(clouds[:2])
        s = sync_engine.stats
        assert s.requests == 2 and s.batches == 1 and s.padded == 2
