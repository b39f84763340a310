"""Spans and counters inside the async serving engine, and the plan-op
scopes of the compiled forward.

Each dispatch runs four host regions — stage, enqueue, device wait,
resolve — each timed into its own ``PointCloudStats`` field and wrapped
in a ``serve.*`` profiler span carrying the dispatch's sequence number;
``queued_s`` sums submit-to-dispatch waits on the engine's clock.  The
forward's plan ops run under ``jax.named_scope``s that land in the
compiled HLO's ``op_name`` metadata.  The benchmark's readers of these
counters are loaded by path, as ``benchmarks/chip/run.py`` loads them.
"""
import collections
import dataclasses
import glob
import importlib.util
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import pytest
from harness import SEED, TINY, VirtualClock

from repro.serve.async_engine import AsyncPointCloudEngine

PHASES = ("stage", "enqueue", "wait", "resolve")
METRICS = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" \
    / "chip" / "metrics"


@pytest.fixture()
def engine(tiny_pipeline):
    return AsyncPointCloudEngine(tiny_pipeline, max_batch=4,
                                 policy="fixed", seed=SEED,
                                 clock=VirtualClock())


class TestCounters:
    def test_queued_s_is_exact_on_the_virtual_clock(self, engine, clouds):
        clock = engine._clock
        for c in clouds[:3]:                # submitted at 0, 1, 2 ms
            engine.submit(c)
            clock.advance(0.001)
        clock.advance(0.007)                # now 10 ms
        engine.submit(clouds[3])            # full batch: dispatch at 10
        engine.pump()
        assert engine.stats.queued_s == pytest.approx(
            (0.010 - 0.0) + (0.010 - 0.001) + (0.010 - 0.002) + 0.0,
            abs=1e-12)
        clock.advance(0.005)                # a tail of 2, flushed at 15
        engine.submit(clouds[4])
        engine.submit(clouds[5])
        engine.flush()
        assert engine.stats.queued_s == pytest.approx(0.027, abs=1e-12)
        assert engine.stats.requests == 6

    def test_retired_equals_batches_after_flush(self, engine, clouds):
        for c in clouds[:10]:
            engine.submit(c)
        engine.pump()
        assert (engine.stats.batches, engine.stats.retired) == (1, 0)
        engine.pump()                       # dispatch 2 retires 1
        assert (engine.stats.batches, engine.stats.retired) == (2, 1)
        engine.flush()
        assert engine.stats.batches == engine.stats.retired == 3

    def test_four_phase_timers_all_run(self, engine, clouds):
        for c in clouds[:4]:
            engine.submit(c)
        engine.flush()
        s = engine.stats
        for field in ("host_s", "enqueue_s", "wait_s", "resolve_s"):
            assert getattr(s, field) > 0.0, field
        assert s.serve_s == s.enqueue_s + s.wait_s

    def test_resolve_s_times_the_done_callbacks(self, engine, clouds):
        """Client code run from a done-callback is resolve time, not
        device wait: the wait is closed before any callback runs."""
        import time
        seen = []

        def slow_client(fut):
            seen.append(engine.stats.wait_s)
            time.sleep(0.05)

        fut = engine.submit(clouds[0])
        fut.add_done_callback(slow_client)
        engine.flush()
        assert len(seen) == 1
        assert engine.stats.wait_s == seen[0]
        assert engine.stats.resolve_s >= 0.05

    def test_wait_s_covers_the_host_copy(self, engine, clouds,
                                         monkeypatch):
        """The dispatch's one host copy of its logits is device wait:
        a copy that takes one second of the engine's timer clock lands
        in ``wait_s`` whole, and ``resolve_s`` holds none of it."""
        import numpy as np

        from repro.serve import async_engine
        now = [0.0]
        monkeypatch.setattr(async_engine, "time", types.SimpleNamespace(
            perf_counter=lambda: now[0]))

        class SlowCopy:
            """Dispatch logits whose host copy takes one second."""
            def __init__(self, logits):
                self.logits = logits

            def copy_to_host_async(self):
                self.logits.copy_to_host_async()

            def block_until_ready(self):
                return self

            def __array__(self, dtype=None, copy=None):
                now[0] += 1.0
                return np.asarray(self.logits)

        infer = engine.pipeline.infer
        engine.pipeline = types.SimpleNamespace(
            streaming=False,
            infer=lambda b, s: (SlowCopy(infer(b, s)[0]), None))
        fut = engine.submit(clouds[0])
        engine.flush()
        assert fut.done()
        assert (engine.stats.wait_s, engine.stats.resolve_s) == (1.0, 0.0)


def _serve_spans(logdir):
    from jax.profiler import ProfileData
    found = glob.glob(str(pathlib.Path(logdir) / "**" / "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1, found
    spans = []
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve.", "test.")):
                    spans.append((ev.name, dict(ev.stats).get("dispatch"),
                                  ev.start_ns, ev.end_ns))
    return spans


class TestSpans:
    @pytest.mark.filterwarnings(
        "ignore:builtin type event_stats:DeprecationWarning")
    def test_one_span_per_phase_per_dispatch_none_when_idle(
            self, engine, clouds, tmp_path):
        engine.warmup()

        def idle_pumps():
            with jax.profiler.TraceAnnotation("test.idle"):
                for _ in range(3):
                    assert engine.pump() == 0

        jax.profiler.start_trace(str(tmp_path))
        try:
            idle_pumps()                    # empty engine
            for c in clouds[:4]:
                engine.submit(c)
            engine.pump()                   # dispatch 0
            for c in clouds[4:7]:
                engine.submit(c)
            idle_pumps()                    # 3 queued < 4: retires 0 once
            engine.flush()                  # dispatch 1, retire 1
            idle_pumps()                    # nothing queued or in flight
        finally:
            jax.profiler.stop_trace()
        spans = _serve_spans(tmp_path)
        count = collections.Counter((n, d) for n, d, _, _ in spans
                                    if n.startswith("serve."))
        assert count == {(f"serve.{p}", d): 1
                         for p in PHASES for d in (0, 1)}
        # Only the idle turn that found dispatch 0 in flight opened any
        # span (its wait and resolve); the other idle turns opened none.
        idle = [(s, e) for n, _, s, e in spans if n == "test.idle"]
        inside = [(n, d) for n, d, s, _ in spans if n.startswith("serve.")
                  and any(a <= s < b for a, b in idle)]
        assert sorted(inside) == [("serve.resolve", 0), ("serve.wait", 0)]


def _scopes(pipeline):
    b = 2
    pts = jnp.zeros((b, pipeline.spec.n_points, 3), jnp.float32)
    lfsr = pipeline.seed_state(SEED, b)
    hlo = pipeline._fn.lower(pipeline.params, pts, lfsr).compile().as_text()
    names = re.findall(r'op_name="([^"]+)"', hlo)
    return {part for n in names for part in n.split("/")}


@pytest.mark.parametrize("variant", ["elite-fps-fp32", "lite-urs-int8"])
def test_plan_op_scopes_in_compiled_hlo(variant):
    from repro.api import elite_spec, lite_spec
    from repro.api.build import build
    from repro.models import pointmlp as PM
    if variant == "elite-fps-fp32":
        spec = elite_spec(8).replace(backend="ref", **TINY).serving()
    else:
        spec = lite_spec(8).replace(backend="ref", **TINY).serving()
    params = PM.pointmlp_init(jax.random.PRNGKey(0),
                              spec.to_model_config())
    scopes = _scopes(build(spec, params))
    for want in ("embed", "sample0", "group0", "knn", "transfer0", "res0",
                 "pool0", "pool", "head"):
        assert want in scopes, (want, sorted(s for s in scopes
                                             if len(s) < 12))


# ------------------------------------------------- benchmark readers ----

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(*stats):
    """A stub of ``run.py``'s ``RunView``: readers see only
    ``run.window.engine_stats`` (tenant -> ``asdict(engine.stats)``)."""
    return types.SimpleNamespace(window=types.SimpleNamespace(
        engine_stats={f"t{i}": s for i, s in enumerate(stats)}))


def _stats(**kw):
    from repro.serve.batching import PointCloudStats
    return dataclasses.asdict(PointCloudStats(**kw))


#: What the engines' stats held before these counters existed.
OLD = {"requests": 16, "batches": 2, "padded": 0, "compile_s": 1.0,
       "serve_s": 0.1, "host_s": 0.001}


class TestReaders:
    def test_resolve_ms_per_dispatch(self):
        read = _reader("resolve_ms_per_dispatch")
        assert read(_run(_stats(retired=4, resolve_s=0.2),
                         _stats(retired=1, resolve_s=0.05))) \
            == pytest.approx(50.0)
        assert read(_run(_stats(batches=1))) is None    # none retired
        assert read(_run(OLD)) is None

    def test_device_wait_ms_per_dispatch(self):
        read = _reader("device_wait_ms_per_dispatch")
        assert read(_run(_stats(retired=8, wait_s=0.016))) \
            == pytest.approx(2.0)
        assert read(_run(_stats())) is None
        assert read(_run(OLD)) is None

    def test_dispatch_wait_ms(self):
        read = _reader("dispatch_wait_ms")
        assert read(_run(_stats(requests=10, queued_s=0.12),
                         _stats(requests=30, queued_s=0.28))) \
            == pytest.approx(10.0)
        assert read(_run(_stats())) is None
        assert read(_run(OLD)) is None
