"""AsyncPointCloudEngine contracts, driven by the virtual-clock harness.

Golden equivalence (async == sync, bit-identical, per backend),
future ordering/resolution, pad-lane isolation, double-buffer
mechanics, and SLO-policy dispatch sizing against scripted
bursty/trickle/steady traces.  No wall-clock sleeps anywhere — every
assertion is an equality, not a timing tolerance.
"""
import jax
import numpy as np
import pytest
from harness import (SEED, VirtualClock, bursty_trace, run_trace,
                     steady_trace, tiny_serving_spec, trickle_trace)

from repro.serve.async_engine import AsyncPointCloudEngine, ServeFuture
from repro.serve.policy import POLICIES, DeadlineBatch, FixedBatch

MAX_BATCH = 4

# Spec overrides per golden variant: every registered CPU-runnable
# backend, the int8 deployment precision, and the stateless FPS sampler.
VARIANTS = {
    "ref": {},
    "pallas_interpret": {"backend": "pallas_interpret"},
    "int8": {"precision": "int8"},
    "fps": {"sampler": "fps"},
}


def make_engine(pipeline, clock, policy="fixed", max_batch=MAX_BATCH,
                seed=SEED):
    return AsyncPointCloudEngine(pipeline, max_batch=max_batch,
                                 policy=policy, seed=seed, clock=clock)


def results(futures) -> np.ndarray:
    return np.stack([np.asarray(f.result()) for f in futures])


# ------------------------------------------------------------------ #
# golden equivalence                                                 #
# ------------------------------------------------------------------ #

class TestGoldenEquivalence:
    @pytest.mark.parametrize("variant", sorted(VARIANTS))
    def test_burst_bit_identical_to_sync_engine(self, variant,
                                                tiny_params, clouds):
        """One full-batch burst: async logits == sync PointCloudEngine
        logits, bit for bit, for every CPU-available backend variant.
        The async engine wraps the sync engine's own FrozenPipeline —
        "any FrozenPipeline" includes one already in service."""
        from repro.serve.pointcloud import PointCloudEngine
        spec = tiny_serving_spec(**VARIANTS[variant])
        sync = PointCloudEngine(tiny_params, spec, max_batch=MAX_BATCH,
                                seed=SEED)
        want = np.asarray(sync.classify(clouds[:MAX_BATCH]))
        clock = VirtualClock()
        eng = make_engine(sync.pipeline, clock)
        futures = run_trace(eng, bursty_trace(clouds[:MAX_BATCH]), clock)
        np.testing.assert_array_equal(results(futures), want)

    def test_solo_request_bit_identical_to_solo_sync_run(
            self, tiny_pipeline, tiny_spec, tiny_params, clouds):
        """A single submitted cloud reproduces a fresh sync engine's
        single-request classify exactly."""
        from repro.serve.pointcloud import PointCloudEngine
        sync = PointCloudEngine(tiny_params, tiny_spec,
                                max_batch=MAX_BATCH, seed=SEED)
        want = np.asarray(sync.classify(clouds[:1]))
        clock = VirtualClock()
        eng = make_engine(sync.pipeline, clock)
        fut = eng.submit(clouds[0])
        eng.flush()
        np.testing.assert_array_equal(np.asarray(fut.result())[None], want)

    def test_long_trace_dispatch_invariant(self, tiny_pipeline,
                                           solo_reference, clouds):
        """10 requests over a trickle + deadline policy land in several
        partial dispatches; every result still equals the solo run —
        the shared-URS dispatch-invariance contract."""
        clock = VirtualClock()
        eng = make_engine(tiny_pipeline, clock, policy="deadline")
        futures = run_trace(eng, trickle_trace(clouds[:10], gap_ms=15.0),
                            clock)
        assert eng.stats.batches > len(clouds[:10]) // MAX_BATCH  # partials
        for cloud, fut in zip(clouds[:10], futures):
            np.testing.assert_array_equal(np.asarray(fut.result()),
                                          solo_reference(cloud, MAX_BATCH))

    @pytest.mark.parametrize("policy", sorted(POLICIES.names()))
    def test_results_independent_of_policy(self, policy, tiny_pipeline,
                                           solo_reference, clouds):
        """The policy only changes *when* work dispatches, never what a
        request's logits are."""
        clock = VirtualClock()
        eng = AsyncPointCloudEngine(
            tiny_pipeline, max_batch=MAX_BATCH,
            policy=POLICIES.get(policy)(slo_ms=8.0), seed=SEED,
            clock=clock)
        futures = run_trace(eng, steady_trace(clouds[:9], gap_ms=3.0),
                            clock)
        for cloud, fut in zip(clouds[:9], futures):
            np.testing.assert_array_equal(np.asarray(fut.result()),
                                          solo_reference(cloud, MAX_BATCH))

    def test_results_independent_of_cobatched_requests(self, tiny_pipeline,
                                                       clouds):
        """A request's logits do not change with the company it keeps
        in its dispatch batch."""
        clock = VirtualClock()
        alone = make_engine(tiny_pipeline, clock)
        fa = alone.submit(clouds[0])
        alone.flush()
        together = make_engine(tiny_pipeline, clock)
        futures = [together.submit(c) for c in clouds[:MAX_BATCH]]
        together.flush()
        np.testing.assert_array_equal(np.asarray(fa.result()),
                                      np.asarray(futures[0].result()))

    def test_results_independent_of_arrival_order(self, tiny_pipeline,
                                                  clouds):
        """Permuting the submission order permutes the results and
        nothing else."""
        clock = VirtualClock()
        perm = [3, 1, 0, 2]
        a = make_engine(tiny_pipeline, clock)
        fa = [a.submit(c) for c in clouds[:4]]
        a.flush()
        b = make_engine(tiny_pipeline, clock)
        fb = [b.submit(clouds[i]) for i in perm]
        b.flush()
        np.testing.assert_array_equal(results(fa)[perm], results(fb))


# ------------------------------------------------------------------ #
# futures: ordering, resolution, exactly-once                        #
# ------------------------------------------------------------------ #

class TestFutures:
    def test_resolve_in_submission_order(self, tiny_pipeline, clouds):
        clock = VirtualClock()
        eng = make_engine(tiny_pipeline, clock, policy="deadline")
        futures = run_trace(eng, bursty_trace(clouds[:8]), clock)
        assert [f.request_id for f in futures] == list(range(8))
        assert all(a.t_done <= b.t_done
                   for a, b in zip(futures, futures[1:]))   # FIFO service

    def test_pending_result_raises(self, tiny_pipeline, clouds):
        eng = make_engine(tiny_pipeline, VirtualClock())
        fut = eng.submit(clouds[0])
        assert not fut.done()
        with pytest.raises(RuntimeError, match="pending"):
            fut.result()

    def test_flush_resolves_everything(self, tiny_pipeline, clouds):
        eng = make_engine(tiny_pipeline, VirtualClock())
        futures = [eng.submit(c) for c in clouds[:7]]   # 4 + partial 3
        eng.flush()
        assert all(f.done() for f in futures)
        assert eng.pending == 0 and eng.depth == 0

    def test_each_request_answered_exactly_once(self, tiny_pipeline,
                                                clouds):
        calls = []
        eng = make_engine(tiny_pipeline, VirtualClock())
        futures = [eng.submit(c) for c in clouds[:6]]
        for f in futures:
            f.add_done_callback(lambda f: calls.append(f.request_id))
        eng.pump()
        eng.flush()
        eng.flush()                       # idempotent: no double resolve
        eng.pump()
        assert sorted(calls) == list(range(6))

    def test_done_callback_fires_immediately_when_already_done(
            self, tiny_pipeline, clouds):
        eng = make_engine(tiny_pipeline, VirtualClock())
        fut = eng.submit(clouds[0])
        eng.flush()
        seen = []
        fut.add_done_callback(lambda f: seen.append(f.request_id))
        assert seen == [0]

    def test_latency_stamped_on_virtual_clock(self, tiny_pipeline, clouds):
        clock = VirtualClock()
        eng = make_engine(tiny_pipeline, clock, policy="deadline")
        futures = run_trace(eng, trickle_trace(clouds[:3], gap_ms=20.0),
                            clock, tick_ms=1.0)
        for f in futures:
            assert f.done() and f.latency_ms is not None
            assert 0.0 <= f.latency_ms < 20.0
        assert len(eng.latencies_ms) == 3

    def test_submit_rejects_wrong_shape(self, tiny_pipeline, tiny_spec):
        eng = make_engine(tiny_pipeline, VirtualClock())
        with pytest.raises(ValueError, match="cloud"):
            eng.submit(np.zeros((tiny_spec.n_points + 1, 3), np.float32))
        with pytest.raises(ValueError, match="cloud"):
            eng.submit(np.zeros((2, tiny_spec.n_points, 3), np.float32))

    def test_closed_engine_rejects_submit(self, tiny_pipeline, clouds):
        eng = make_engine(tiny_pipeline, VirtualClock())
        eng.close()
        with pytest.raises(RuntimeError, match="closed"):
            eng.submit(clouds[0])

    def test_raising_callback_does_not_strand_cobatched_requests(
            self, tiny_pipeline, clouds):
        """One client's bad done-callback is contained (warning, not
        propagation): every other future in the batch still resolves."""
        eng = make_engine(tiny_pipeline, VirtualClock())
        futures = [eng.submit(c) for c in clouds[:4]]
        futures[0].add_done_callback(
            lambda f: (_ for _ in ()).throw(RuntimeError("client bug")))
        with pytest.warns(RuntimeWarning, match="client bug"):
            eng.flush()
        assert all(f.done() for f in futures)
        with pytest.warns(RuntimeWarning, match="client bug"):
            futures[1].add_done_callback(
                lambda f: (_ for _ in ()).throw(RuntimeError("client bug")))

    def test_engine_requires_serving_spec(self, tiny_params):
        """The batching-invariance contract needs shared_urs +
        per_sample_norm; a non-serving pipeline is rejected up front."""
        from repro.api.build import build
        spec = tiny_serving_spec().replace(shared_urs=False,
                                           per_sample_norm=False)
        with pytest.raises(ValueError, match="serving"):
            AsyncPointCloudEngine(build(spec, tiny_params),
                                  clock=VirtualClock())


# ------------------------------------------------------------------ #
# pad-lane isolation + dispatch mechanics                            #
# ------------------------------------------------------------------ #

class TestDispatchMechanics:
    def test_partial_dispatch_pads_without_leaking(self, tiny_pipeline,
                                                   clouds):
        """3 real + 1 pad lane gives bit-identical logits to the same 3
        clouds dispatched in a full batch of 4."""
        clock = VirtualClock()
        partial = make_engine(tiny_pipeline, clock)
        fp = [partial.submit(c) for c in clouds[:3]]
        partial.flush()
        assert partial.stats.padded == 1
        full = make_engine(tiny_pipeline, clock)
        ff = [full.submit(c) for c in clouds[:4]]
        full.flush()
        assert full.stats.padded == 0
        np.testing.assert_array_equal(results(fp), results(ff)[:3])

    def test_double_buffer_holds_one_inflight_batch(self, tiny_pipeline,
                                                    clouds):
        """After dispatching batch N, its futures stay pending (the
        overlap window) until batch N+1 is enqueued or an idle pump
        retires it — never more than one batch in flight."""
        eng = make_engine(tiny_pipeline, VirtualClock())
        futures = [eng.submit(c) for c in clouds[:8]]
        assert eng.pump() == MAX_BATCH
        assert not any(f.done() for f in futures)       # N in flight
        assert eng.pending == 8
        assert eng.pump() == MAX_BATCH                  # N+1 enqueued
        assert all(f.done() for f in futures[:4])       # N retired
        assert not any(f.done() for f in futures[4:])
        eng.flush()
        assert all(f.done() for f in futures)

    def test_idle_pump_retires_inflight(self, tiny_pipeline, clouds):
        eng = make_engine(tiny_pipeline, VirtualClock())
        futures = [eng.submit(c) for c in clouds[:4]]
        eng.pump()
        assert not futures[0].done()
        assert eng.pump() == 0                          # idle turn
        assert all(f.done() for f in futures)

    def test_nonblocking_pump_never_loses_work(self, tiny_pipeline,
                                               clouds):
        """``pump(block=False)`` (the serve_loop mode) may defer
        retirement while the device is busy, but repeated pumping plus
        flush always resolves everything exactly once."""
        eng = make_engine(tiny_pipeline, VirtualClock())
        futures = [eng.submit(c) for c in clouds[:4]]
        eng.pump(block=False)                           # dispatch
        for _ in range(50):
            if all(f.done() for f in futures):
                break
            eng.pump(block=False)                       # idle, no stall
        eng.flush()
        assert all(f.done() for f in futures)
        assert eng.stats.requests == 4 and eng.stats.batches == 1

    def test_warmup_compiles_without_touching_queue(self, tiny_pipeline,
                                                    clouds):
        clock = VirtualClock()
        eng = make_engine(tiny_pipeline, clock)
        fut = eng.submit(clouds[0])
        assert eng.warmup() > 0.0
        assert eng.stats.compile_s > 0.0
        assert eng.depth == 1 and not fut.done()
        other = make_engine(tiny_pipeline, clock)
        fo = other.submit(clouds[0])
        other.flush()
        eng.flush()
        np.testing.assert_array_equal(np.asarray(fut.result()),
                                      np.asarray(fo.result()))

    def test_fifo_across_many_dispatches(self, tiny_pipeline, clouds,
                                         solo_reference):
        """Requests dispatch strictly head-first; ids map to the right
        logits even when dispatches interleave with arrivals."""
        clock = VirtualClock()
        eng = make_engine(tiny_pipeline, clock, policy="deadline")
        futures = run_trace(eng, steady_trace(clouds[:12], gap_ms=2.0),
                            clock)
        for cloud, fut in zip(clouds[:12], futures):
            np.testing.assert_array_equal(np.asarray(fut.result()),
                                          solo_reference(cloud, MAX_BATCH))


# ------------------------------------------------------------------ #
# host rows: one host copy of each dispatch's logits                 #
# ------------------------------------------------------------------ #

class TestHostRows:
    def _dispatch_of_three(self, pipeline, clouds):
        eng = make_engine(pipeline, VirtualClock())
        futures = [eng.submit(c) for c in clouds[:3]]     # + 1 pad lane
        eng.flush()
        return eng, futures

    def test_result_is_a_host_ndarray(self, tiny_pipeline, tiny_spec,
                                      clouds):
        _, futures = self._dispatch_of_three(tiny_pipeline, clouds)
        for f in futures:
            row = f.result()
            assert type(row) is np.ndarray
            assert row.shape == (tiny_spec.to_model_config().n_classes,)
            assert not row.flags.writeable

    def test_cobatched_rows_share_one_host_block(self, tiny_pipeline,
                                                 clouds):
        """The rows of one dispatch are views of one [max_batch, ...]
        host block, in lane order: one copy, not one per row."""
        _, futures = self._dispatch_of_three(tiny_pipeline, clouds)
        rows = [f.result() for f in futures]
        block = rows[0].base
        assert isinstance(block, np.ndarray)
        assert block.shape == (MAX_BATCH,) + rows[0].shape
        for i, row in enumerate(rows):
            assert row.base is block
            assert np.shares_memory(row, block)
            assert np.shares_memory(row, block[i])

    def test_rows_bit_identical_to_direct_infer_on_padded_batch(
            self, tiny_pipeline, clouds):
        import jax.numpy as jnp

        from repro.serve import batching
        _, futures = self._dispatch_of_three(tiny_pipeline, clouds)
        batch, pad = batching.pad_to_batch(
            batching.stack_requests(list(clouds[:3]),
                                    tiny_pipeline.spec.n_points),
            MAX_BATCH)
        assert pad == 1
        want, _ = tiny_pipeline.infer(
            batch, jnp.array(tiny_pipeline.seed_state(SEED, MAX_BATCH)))
        want = np.asarray(want)
        for i, f in enumerate(futures):
            np.testing.assert_array_equal(f.result(), want[i])
            assert f.result().tobytes() == want[i].tobytes()

    def test_callback_cannot_write_its_row(self, tiny_pipeline, clouds):
        """A done-callback that writes to its row raises; the row and
        its co-batched neighbours keep their values."""
        eng = make_engine(tiny_pipeline, VirtualClock())
        futures = [eng.submit(c) for c in clouds[:3]]
        raised = []

        def scribble(fut):
            try:
                fut.result()[:] = 0.0
            except ValueError as e:
                raised.append(e)

        futures[0].add_done_callback(scribble)
        eng.flush()
        assert len(raised) == 1 and "read-only" in str(raised[0])
        other = make_engine(tiny_pipeline, VirtualClock())
        ref = [other.submit(c) for c in clouds[:3]]
        other.flush()
        np.testing.assert_array_equal(results(futures), results(ref))


# ------------------------------------------------------------------ #
# policies: SLO-aware dispatch sizing on scripted traces             #
# ------------------------------------------------------------------ #

class TestPolicies:
    def test_registry_has_builtins_and_diagnoses_typos(self):
        assert {"fixed", "deadline"} <= set(POLICIES.names())
        with pytest.raises(KeyError, match="deadline"):
            POLICIES.get("deadlin")

    def test_decide_tables(self):
        """The policy decision functions, exhaustively at the edges."""
        fixed = FixedBatch()
        assert fixed.decide(depth=3, oldest_wait_ms=1e9, max_batch=4) == 0
        assert fixed.decide(depth=4, oldest_wait_ms=0.0, max_batch=4) == 4
        assert fixed.decide(depth=9, oldest_wait_ms=0.0, max_batch=4) == 4
        ddl = DeadlineBatch(slo_ms=10.0)
        assert ddl.decide(depth=0, oldest_wait_ms=0.0, max_batch=4) == 0
        assert ddl.decide(depth=2, oldest_wait_ms=9.9, max_batch=4) == 0
        assert ddl.decide(depth=2, oldest_wait_ms=10.0, max_batch=4) == 2
        assert ddl.decide(depth=4, oldest_wait_ms=0.0, max_batch=4) == 4
        greedy = DeadlineBatch(slo_ms=0.0)
        assert greedy.decide(depth=1, oldest_wait_ms=0.0, max_batch=4) == 1
        reserved = DeadlineBatch(slo_ms=10.0, dispatch_ms=4.0)
        assert reserved.decide(depth=1, oldest_wait_ms=6.0, max_batch=4) == 1

    def test_fixed_policy_never_dispatches_partial(self, tiny_pipeline,
                                                   clouds):
        """Trickle + fixed: nothing dispatches until flush; then the
        tail goes out in one padded batch."""
        clock = VirtualClock()
        eng = make_engine(tiny_pipeline, clock, policy="fixed")
        futures = run_trace(eng, trickle_trace(clouds[:3], gap_ms=30.0),
                            clock, flush=False)
        assert eng.stats.batches == 0
        assert not any(f.done() for f in futures)
        eng.flush()
        assert eng.stats.batches == 1 and eng.stats.padded == 1
        assert all(f.done() for f in futures)

    def test_fixed_policy_full_batches_on_burst(self, tiny_pipeline,
                                                clouds):
        clock = VirtualClock()
        eng = make_engine(tiny_pipeline, clock, policy="fixed")
        run_trace(eng, bursty_trace(clouds[:8], burst=MAX_BATCH), clock)
        assert eng.stats.batches == 2 and eng.stats.padded == 0
        assert eng.stats.requests == 8

    def test_deadline_policy_dispatches_solo_on_trickle(self, tiny_pipeline,
                                                        clouds):
        """Arrivals far apart + tight SLO: every request ships alone
        (pad lanes are the price of the deadline) and its virtual-clock
        latency honors the SLO."""
        clock = VirtualClock()
        eng = AsyncPointCloudEngine(tiny_pipeline, max_batch=MAX_BATCH,
                                    policy=DeadlineBatch(slo_ms=10.0),
                                    seed=SEED, clock=clock)
        futures = run_trace(eng, trickle_trace(clouds[:5], gap_ms=40.0),
                            clock, tick_ms=1.0)
        assert eng.stats.batches == 5
        assert eng.stats.padded == 5 * (MAX_BATCH - 1)
        for f in futures:
            assert f.latency_ms <= 10.0 + 4.0      # SLO + retire ticks

    def test_deadline_policy_full_batches_on_burst(self, tiny_pipeline,
                                                   clouds):
        """Batch-friendly bursts never trigger the deadline path: full
        batches, zero padding."""
        clock = VirtualClock()
        eng = AsyncPointCloudEngine(tiny_pipeline, max_batch=MAX_BATCH,
                                    policy=DeadlineBatch(slo_ms=10.0),
                                    seed=SEED, clock=clock)
        run_trace(eng, bursty_trace(clouds[:12], burst=MAX_BATCH,
                                    burst_gap_ms=50.0), clock)
        assert eng.stats.batches == 3 and eng.stats.padded == 0

    def test_deadline_slo_zero_is_latency_greedy(self, tiny_pipeline,
                                                 clouds):
        clock = VirtualClock()
        eng = AsyncPointCloudEngine(tiny_pipeline, max_batch=MAX_BATCH,
                                    policy=DeadlineBatch(slo_ms=0.0),
                                    seed=SEED, clock=clock)
        futures = run_trace(eng, trickle_trace(clouds[:3], gap_ms=5.0),
                            clock)
        assert eng.stats.batches == 3          # each dispatched on arrival
        assert all(f.done() for f in futures)

    def test_steady_trace_mixes_partial_and_full(self, tiny_pipeline,
                                                 clouds):
        """Moderate-rate arrivals under a deadline policy: somewhere
        between all-full and all-solo, and every request answered."""
        clock = VirtualClock()
        eng = AsyncPointCloudEngine(tiny_pipeline, max_batch=MAX_BATCH,
                                    policy=DeadlineBatch(slo_ms=8.0),
                                    seed=SEED, clock=clock)
        futures = run_trace(eng, steady_trace(clouds[:12], gap_ms=3.0),
                            clock)
        n_batches = eng.stats.batches
        assert 12 // MAX_BATCH <= n_batches <= 12
        assert eng.stats.requests == 12
        assert all(f.done() for f in futures)

    def test_policy_resolved_from_spec_fields(self, tiny_params):
        """PipelineSpec.serving(policy=, slo_ms=) flows through build()
        into the engine's policy instance."""
        spec = tiny_serving_spec().serving(policy="deadline", slo_ms=15.0)
        assert spec.policy == "deadline" and spec.slo_ms == 15.0
        eng = AsyncPointCloudEngine.from_params(tiny_params, spec,
                                                max_batch=2,
                                                clock=VirtualClock())
        assert isinstance(eng.policy, DeadlineBatch)
        assert eng.policy.slo_ms == 15.0

    def test_spec_rejects_unknown_policy_and_negative_slo(self):
        with pytest.raises(KeyError, match="policy"):
            tiny_serving_spec().serving(policy="nope").validate()
        with pytest.raises(ValueError, match="slo_ms"):
            tiny_serving_spec().serving(slo_ms=-1.0)
        with pytest.raises(ValueError, match="dispatch_ms"):
            tiny_serving_spec().serving(dispatch_ms=-1.0)

    def test_dispatch_ms_reaches_policy_from_spec(self, tiny_params):
        """Regression: make_policy used to drop dispatch_ms, so the
        documented service-time reservation was unreachable from a
        PipelineSpec."""
        spec = tiny_serving_spec().serving(policy="deadline",
                                           slo_ms=20.0, dispatch_ms=5.0)
        eng = AsyncPointCloudEngine.from_params(tiny_params, spec,
                                                max_batch=2,
                                                clock=VirtualClock())
        assert eng.policy.dispatch_ms == 5.0
        # budget = slo - dispatch = 15ms: a 15ms-old head dispatches.
        assert eng.policy.decide(depth=1, oldest_wait_ms=14.9,
                                 max_batch=4) == 0
        assert eng.policy.decide(depth=1, oldest_wait_ms=15.0,
                                 max_batch=4) == 1

    def test_dispatch_ms_consuming_slo_warns_of_collapse(self):
        with pytest.warns(UserWarning, match="dispatch-on-arrival"):
            pol = DeadlineBatch(slo_ms=10.0, dispatch_ms=10.0)
        assert pol.decide(depth=1, oldest_wait_ms=0.0, max_batch=4) == 1

    def test_plugin_policy_without_dispatch_ms_still_instantiates(self):
        """A registry plugin whose constructor predates dispatch_ms
        keeps working; a dropped reservation warns."""
        from repro.serve.policy import (BatchPolicy, make_policy,
                                        register_policy)

        @register_policy("_test_legacy_ctor")
        class Legacy(BatchPolicy):
            def __init__(self, slo_ms: float = 0.0):
                super().__init__(slo_ms)

            def decide(self, depth, oldest_wait_ms, max_batch):
                return depth

        try:
            with pytest.warns(UserWarning, match="dispatch_ms"):
                pol = make_policy("_test_legacy_ctor", slo_ms=1.0,
                                  dispatch_ms=2.0)
            assert pol.slo_ms == 1.0
            assert make_policy("_test_legacy_ctor").slo_ms == 0.0
        finally:
            POLICIES.unregister("_test_legacy_ctor")


# ------------------------------------------------------------------ #
# asyncio shell                                                      #
# ------------------------------------------------------------------ #

class TestAsyncioShell:
    def test_classify_async_under_serve_loop(self, tiny_pipeline,
                                             solo_reference, clouds):
        """The asyncio surface returns the same bit-identical logits as
        the sans-IO core (tiny real ticks; bounded by pytest-timeout in
        CI, not by timing asserts)."""
        import asyncio

        async def scenario():
            eng = AsyncPointCloudEngine(tiny_pipeline, max_batch=MAX_BATCH,
                                        policy="deadline", seed=SEED)
            server = asyncio.create_task(eng.serve_loop(tick_s=1e-4))
            outs = await asyncio.gather(
                *[eng.classify_async(clouds[i]) for i in range(5)])
            eng.close()
            await server
            return eng, outs

        eng, outs = asyncio.run(scenario())
        assert eng.stats.requests == 5
        for i, out in enumerate(outs):
            np.testing.assert_array_equal(np.asarray(out),
                                          solo_reference(clouds[i],
                                                         MAX_BATCH))

    def test_serve_loop_flushes_tail_on_close(self, tiny_pipeline, clouds):
        import asyncio

        async def scenario():
            eng = AsyncPointCloudEngine(tiny_pipeline, max_batch=MAX_BATCH,
                                        policy="fixed", seed=SEED)
            server = asyncio.create_task(eng.serve_loop(tick_s=1e-4))
            futures = [eng.submit(c) for c in clouds[:3]]   # partial tail
            await asyncio.sleep(0)
            eng.close()
            await server
            return futures

        futures = asyncio.run(scenario())
        assert all(f.done() for f in futures)

    def test_future_is_engine_resolved_only(self, tiny_pipeline, clouds):
        eng = make_engine(tiny_pipeline, VirtualClock())
        fut = eng.submit(clouds[0])
        assert isinstance(fut, ServeFuture)
        eng.flush()
        with pytest.raises(AssertionError, match="exactly once"):
            fut._resolve(fut.result(), 0.0)


# ------------------------------------------------------------------ #
# sharded dispatch through the virtual-clock harness                 #
# ------------------------------------------------------------------ #

class TestShardedDispatch:
    @pytest.mark.skipif(
        jax.device_count() < 8,
        reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    def test_sharded_pipeline_bit_identical_on_steady_trace(
            self, tiny_params, clouds, solo_reference):
        """A data_shards=8 pipeline under the async engine, driven
        through the scripted steady trace: the scheduler needs zero
        changes and every request's logits equal its solo unsharded
        run bit for bit (dispatch invariance extended across the
        device mesh)."""
        from repro.api.build import build
        spec = tiny_serving_spec(data_shards=8)
        clock = VirtualClock()
        eng = AsyncPointCloudEngine(build(spec, tiny_params),
                                    max_batch=8, policy="fixed",
                                    seed=SEED, clock=clock)
        futures = run_trace(eng, steady_trace(clouds, gap_ms=4.0), clock)
        assert eng.stats.requests == len(clouds)
        for cloud, fut in zip(clouds, futures):
            np.testing.assert_array_equal(np.asarray(fut.result()),
                                          solo_reference(cloud, 8))
