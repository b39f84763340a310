"""Cost-model batching policy (``POLICIES["cost"]``) contracts.

The policy replaces ``DeadlineBatch``'s flat ``dispatch_ms``
reservation with a calibrated dispatch-size-aware service estimate:
``calibrate(stats, max_batch, data_shards)`` fits a per-lane cost from
``stats.serve_s / stats.batches`` divided by ``data_shards`` (the PR-4
plumbing), and ``decide`` budgets ``slo_ms - estimate_ms(depth)``.
All tests run on the virtual clock — no sleeps.
"""
import numpy as np
import pytest

from harness import SEED, VirtualClock

from repro.serve.batching import PointCloudStats
from repro.serve.policy import POLICIES, CostModelBatch, make_policy


def window(serve_s: float, batches: int) -> PointCloudStats:
    return PointCloudStats(batches=batches, enqueue_s=serve_s)


class TestCalibration:
    def test_registered_and_constructible_from_spec_fields(self):
        assert "cost" in POLICIES
        p = make_policy("cost", slo_ms=20.0, dispatch_ms=4.0)
        assert isinstance(p, CostModelBatch)
        assert (p.slo_ms, p.dispatch_ms) == (20.0, 4.0)

    def test_uncalibrated_degrades_to_flat_deadline_reservation(self):
        p = CostModelBatch(slo_ms=10.0, dispatch_ms=4.0)
        assert not p.calibrated
        assert p.estimate_ms(1) == p.estimate_ms(8) == 4.0
        # budget = 10 - 4 = 6ms, exactly DeadlineBatch semantics
        assert p.decide(depth=2, oldest_wait_ms=5.9, max_batch=8) == 0
        assert p.decide(depth=2, oldest_wait_ms=6.0, max_batch=8) == 2
        assert p.decide(depth=8, oldest_wait_ms=0.0, max_batch=8) == 8

    def test_calibrate_fits_per_lane_cost(self):
        # 100 dispatches of max_batch=8 on 1 device took 0.8s: 8ms per
        # dispatch, 1ms per lane -> estimate is linear in dispatch size.
        p = CostModelBatch(slo_ms=10.0).calibrate(window(0.8, 100),
                                                  max_batch=8)
        assert p.calibrated
        assert p.estimate_ms(8) == pytest.approx(8.0)
        assert p.estimate_ms(2) == pytest.approx(2.0)
        assert p.estimate_ms(0) == pytest.approx(1.0)   # floor: 1 lane

    def test_calibrate_divides_by_data_shards(self):
        # Same window measured on a data_shards=4 pipeline: a full
        # dispatch still costs 8ms wall, but only 2 lanes run per
        # device, so a 2-request dispatch costs one lane-step = 4ms.
        p = CostModelBatch(slo_ms=10.0).calibrate(window(0.8, 100),
                                                  max_batch=8,
                                                  data_shards=4)
        assert p.estimate_ms(8) == pytest.approx(8.0)   # reproduces window
        assert p.estimate_ms(2) == pytest.approx(4.0)
        assert p.estimate_ms(5) == pytest.approx(8.0)   # ceil(5/4)=2 lanes

    def test_empty_window_is_a_noop(self):
        p = CostModelBatch(slo_ms=10.0, dispatch_ms=3.0)
        p.calibrate(window(0.0, 0), max_batch=8)
        assert not p.calibrated
        assert p.estimate_ms(4) == 3.0

    def test_partial_dispatch_budget_is_size_aware(self):
        """The point of the policy: small queues get a small
        reservation, so they wait longer before padding a dispatch."""
        p = CostModelBatch(slo_ms=10.0).calibrate(window(0.8, 100),
                                                  max_batch=8)
        # depth=2 -> estimate 2ms -> budget 8ms
        assert p.decide(depth=2, oldest_wait_ms=7.9, max_batch=8) == 0
        assert p.decide(depth=2, oldest_wait_ms=8.0, max_batch=8) == 2
        # depth=6 -> estimate 6ms -> budget 4ms: dispatches earlier
        assert p.decide(depth=6, oldest_wait_ms=4.0, max_batch=8) == 6
        flat = CostModelBatch(slo_ms=10.0, dispatch_ms=8.0)
        # a flat full-batch reservation would have dispatched depth=2
        # at 2ms already — earlier than the SLO required
        assert flat.decide(depth=2, oldest_wait_ms=2.0, max_batch=8) == 2

    def test_uncalibrated_flat_reservation_consuming_slo_warns(self):
        """The DeadlineBatch collapse warning applies here too: until
        calibrated, a dispatch_ms >= slo_ms means dispatch-on-arrival."""
        with pytest.warns(UserWarning, match="dispatch-on-arrival"):
            CostModelBatch(slo_ms=10.0, dispatch_ms=20.0)

    def test_describe_reports_calibration_state(self):
        p = CostModelBatch(slo_ms=10.0)
        assert "uncalibrated" in p.describe()
        p.calibrate(window(0.8, 100), max_batch=8)
        assert "ms_per_lane" in p.describe()


class TestEngineIntegration:
    def test_calibrate_policy_from_live_stats(self, tiny_pipeline,
                                              clouds):
        from repro.serve.async_engine import AsyncPointCloudEngine
        clock = VirtualClock()
        eng = AsyncPointCloudEngine(tiny_pipeline, max_batch=4,
                                    policy="cost", seed=SEED,
                                    clock=clock)
        assert not eng.policy.calibrated
        assert eng.calibrate_policy() is False      # empty window
        for c in clouds[:8]:
            eng.submit(c)
        while eng.pump():
            pass
        eng.flush()
        assert eng.calibrate_policy() is True
        assert eng.policy.calibrated
        assert eng.policy.estimate_ms(4) > 0
        assert "ms_per_lane" in eng.describe()

    def test_fixed_policy_has_nothing_to_calibrate(self, tiny_pipeline):
        from repro.serve.async_engine import AsyncPointCloudEngine
        eng = AsyncPointCloudEngine(tiny_pipeline, max_batch=4,
                                    policy="fixed", seed=SEED)
        assert eng.calibrate_policy() is False

    def test_virtual_clock_dispatch_timing(self, tiny_pipeline, clouds):
        """Scripted end-to-end: two requests under a calibrated cost
        policy dispatch exactly when the size-aware budget expires."""
        from repro.serve.async_engine import AsyncPointCloudEngine
        clock = VirtualClock()
        policy = CostModelBatch(slo_ms=10.0).calibrate(window(0.8, 100),
                                                       max_batch=4)
        # ms_per_lane = 8ms / 4 lanes = 2ms -> depth=2 budget = 6ms
        eng = AsyncPointCloudEngine(tiny_pipeline, max_batch=4,
                                    policy=policy, seed=SEED,
                                    clock=clock)
        f0 = eng.submit(clouds[0])
        f1 = eng.submit(clouds[1])
        clock.advance(0.0059)
        assert eng.pump() == 0                     # 5.9ms < 6ms budget
        clock.advance(0.0002)
        assert eng.pump() == 2                     # 6.1ms >= budget
        eng.flush()
        assert f0.done() and f1.done()
        np.testing.assert_array_equal(
            np.asarray(f0.result()).shape, (tiny_pipeline.spec.n_classes,))
