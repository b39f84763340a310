"""Stage-plan IR contracts (``repro.api.plan``).

Golden equivalence: the plan interpreter — the production ``_forward``
— must be **bit-identical** to the retained pre-refactor monolithic
walk (``repro.models.pointmlp._forward_reference``) for every existing
spec variant: fp32-ref / pallas-interpret / int8, through direct
``infer``, the sync engine and the async engine, and (on a forced
8-device CPU) through a ``data_shards=8`` build.  The IR refactor is
observationally invisible until a per-stage override or the fused
grouped-transfer path is opted into.

Lowering: op-sequence shape, per-stage precision/backend override
resolution (including the selective int8 export and the int8-Pallas
matmul routing for int8 x pallas stages), invalid-override
``ValueError``/``KeyError``s, and the per-kernel shapes ``plan_shapes``
reads off a spec.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (FUSED_OPS, GROUPERS, PipelineSpec, build, elite_spec,
                       lite_spec, m2_spec, make_ball_grouper,
                       register_grouper)
from repro.api import plan as SP
from repro.api import registry as R
from repro.core import knn as knn_core
from repro.core import sampling
from repro.data import pointclouds
from repro.models import pointmlp as PM
from repro.serve.async_engine import AsyncPointCloudEngine
from repro.serve.pointcloud import PointCloudEngine

SEED = 7
N_DEV = jax.device_count()
needs8 = pytest.mark.skipif(
    N_DEV < 8,
    reason="needs 8 JAX devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

# Every pre-existing deployment variant the golden contract covers.
VARIANTS = {
    "fp32_ref": dict(precision="fp32", backend="ref"),
    "pallas_interpret": dict(precision="fp32",
                             backend="pallas_interpret"),
    "int8": dict(precision="int8", backend="ref"),
}


def tiny_spec(**overrides) -> PipelineSpec:
    over = dict(n_points=128, embed_dim=16, k_neighbors=8,
                precision="fp32", backend="ref")
    over.update(overrides)
    return lite_spec(8).replace(**over).serving()


@pytest.fixture(scope="module")
def params():
    return PM.pointmlp_init(jax.random.PRNGKey(0),
                            tiny_spec().to_model_config())


@pytest.fixture(scope="module")
def clouds():
    pts, _ = pointclouds.make_batch(jax.random.PRNGKey(1),
                                    tiny_spec().n_points, 8)
    return pts


def reference_serving_infer(pipe, pts, state):
    """The pre-refactor oracle, lane-mapped exactly as the serving
    entry lowers the walk (shared URS + per-sample norm)."""
    s = pipe.spec
    sam, grp, bk = R.resolve(s.sampler, s.grouper, s.backend)

    def lane(cloud):
        logits, _, st = PM._forward_reference(
            pipe.params, pipe.model_config, cloud[None], state,
            train=False, sampler=sam, grouper=grp, backend=bk,
            shared_urs=True, per_sample_norm=True)
        return logits[0], st

    logits, states = jax.lax.map(lane, pts)
    if state is None:
        return logits, None
    return logits, jax.tree_util.tree_map(lambda x: x[0], states)


# ------------------------------------------------------------------ #
# golden equivalence: plan interpreter vs pre-refactor walk           #
# ------------------------------------------------------------------ #

class TestGoldenPlanVsReferenceWalk:
    @pytest.mark.parametrize("variant", sorted(VARIANTS))
    def test_direct_infer_bit_identical(self, variant, params, clouds):
        pipe = build(tiny_spec(**VARIANTS[variant]), params, jit=False)
        state = sampling.seed_streams(SEED, clouds.shape[0])
        got, gst = pipe.infer(clouds, state)
        want, wst = reference_serving_infer(pipe, clouds, state)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(gst), np.asarray(wst))

    @pytest.mark.parametrize("variant", sorted(VARIANTS))
    def test_sync_engine_bit_identical(self, variant, params, clouds):
        eng = PointCloudEngine(params, tiny_spec(**VARIANTS[variant]),
                               max_batch=clouds.shape[0], seed=SEED)
        state = sampling.seed_streams(SEED, clouds.shape[0])
        got = eng.classify(clouds)
        want, _ = reference_serving_infer(eng.pipeline, clouds, state)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("variant", sorted(VARIANTS))
    def test_async_engine_bit_identical(self, variant, params, clouds):
        eng = AsyncPointCloudEngine.from_params(
            params, tiny_spec(**VARIANTS[variant]),
            max_batch=clouds.shape[0], seed=SEED)
        futures = [eng.submit(c) for c in clouds]
        eng.flush()
        got = np.stack([np.asarray(f.result()) for f in futures])
        state = sampling.seed_streams(SEED, clouds.shape[0])
        want, _ = reference_serving_infer(eng.pipeline, clouds, state)
        np.testing.assert_array_equal(got, np.asarray(want))

    def test_batch_semantics_bit_identical(self, params, clouds):
        """The non-serving (batch-statistic, per-lane URS) lowering —
        the legacy training-eval shape — also routes through the
        interpreter unchanged."""
        spec = tiny_spec().replace(shared_urs=False, per_sample_norm=False)
        pipe = build(spec, params, jit=False)
        state = sampling.seed_streams(SEED, 64)
        got, gst = pipe.infer(clouds, state)
        sam, grp, bk = R.resolve(spec.sampler, spec.grouper, spec.backend)
        want, _, wst = PM._forward_reference(
            pipe.params, pipe.model_config, clouds, state, train=False,
            sampler=sam, grouper=grp, backend=bk)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(gst), np.asarray(wst))

    def test_train_path_bit_identical_incl_bn_stats(self, params, clouds):
        cfg = tiny_spec().to_model_config()
        state = sampling.seed_streams(3, 64)
        l1, p1, s1 = PM.pointmlp_apply(params, cfg, clouds, state,
                                       train=True)
        sam, grp, bk = R.resolve(cfg.sampler, "knn", "ref")
        l2, p2, s2 = PM._forward_reference(params, cfg, clouds, state,
                                           train=True, sampler=sam,
                                           grouper=grp, backend=bk)
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @needs8
    @pytest.mark.parametrize("variant", sorted(VARIANTS))
    def test_sharded_dispatch_bit_identical(self, variant, params, clouds):
        """A data_shards=8 plan-interpreter build matches the
        pre-refactor walk (which is itself unsharded — the sharded
        dispatch contract composes with the plan refactor)."""
        pipe = build(tiny_spec(**VARIANTS[variant], data_shards=8),
                     params)
        state = sampling.seed_streams(SEED, 8)
        got, _ = pipe.infer(clouds, state)
        want, _ = reference_serving_infer(
            build(tiny_spec(**VARIANTS[variant]), params, jit=False),
            clouds, state)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------------ #
# lowering                                                           #
# ------------------------------------------------------------------ #

class TestLowering:
    def test_op_sequence(self, params):
        spec = tiny_spec()
        plan = SP.lower(spec, spec.to_model_config())
        kinds = [type(op).__name__ for op in plan.ops]
        assert kinds[0] == "EmbedOp"
        assert kinds[-1] == "HeadOp"
        assert kinds[-2] == "PoolOp"          # global pool
        cfg = spec.to_model_config()
        # per stage: Sample, Group, CBR(transfer), pre res, pool, pos res
        expect = ["EmbedOp"]
        for s in range(4):
            expect += ["SampleOp", "GroupOp", "CBROp"]
            expect += ["ResBlockOp"] * cfg.pre_blocks[s]
            expect += ["PoolOp"]
            expect += ["ResBlockOp"] * cfg.pos_blocks[s]
        expect += ["PoolOp", "HeadOp"]
        assert kinds == expect
        pools = [op for op in plan.ops if isinstance(op, SP.PoolOp)]
        assert [p.axis for p in pools] == [2, 2, 2, 2, 1]

    def test_sample_sizes_follow_config(self):
        spec = tiny_spec()
        cfg = spec.to_model_config()
        plan = SP.lower(spec, cfg)
        samples = [op.n_samples for op in plan.ops
                   if isinstance(op, SP.SampleOp)]
        assert tuple(samples) == cfg.stage_samples

    def test_uniform_lowering_and_config_lowering_agree(self):
        """`lower(spec)` and the legacy `lower_config` emit the same op
        skeleton (paths, stages, activation flags) for a uniform spec."""
        spec = tiny_spec()
        cfg = spec.to_model_config().replace(use_bn=False)
        a = SP.lower(spec, cfg)
        b = SP.lower_config(cfg, R.BACKENDS.get("ref"))
        assert len(a.ops) == len(b.ops)
        for x, y in zip(a.cbr_ops(), b.cbr_ops()):
            assert (x.path, x.stage, x.act) == (y.path, y.stage, y.act)

    def test_stage_precision_resolution(self):
        spec = tiny_spec(stage_precision=("int8", "int8", "int8", "fp32"))
        plan = SP.lower(spec, spec.to_model_config())
        assert plan.stage_precision == ("int8", "int8", "int8", "fp32")
        assert plan.mixed_precision and plan.any_int8
        for op in plan.cbr_ops():
            if op.stage is None:               # embed + head follow spec
                assert op.precision == "fp32" and op.quant is None
            elif op.stage < 3:
                assert op.precision == "int8"
                assert op.quant is not None and op.quant.w_bits == 8
            else:
                assert op.precision == "fp32" and op.quant is None

    def test_stage_backend_resolution(self):
        spec = tiny_spec(stage_backend=("ref", "ref", "pallas_interpret",
                                        "ref"))
        plan = SP.lower(spec, spec.to_model_config())
        fns = {op.stage: op.fn for op in plan.cbr_ops()
               if op.stage is not None}
        # Each op runs its stage's registered backend entry as is.
        assert fns[2] is R.BACKENDS.get("pallas_interpret")
        assert fns[2].keywords["interpret"] is True
        assert fns[0] is R.BACKENDS.get("ref")
        assert plan.stage_backend == ("ref", "ref", "pallas_interpret",
                                      "ref")

    def test_selective_int8_export(self, params):
        """Only the int8 stages' weights become export dicts — the
        plan's predicate drives quantize_tree."""
        spec = tiny_spec(stage_precision=("int8", "int8", "int8", "fp32"))
        pipe = build(spec, params)
        tree = pipe.params
        for s in range(3):
            assert isinstance(tree["stages"][s]["transfer"]["w"], dict)
            assert isinstance(tree["stages"][s]["pre"][0]["net1"]["w"],
                              dict)
        assert not isinstance(tree["stages"][3]["transfer"]["w"], dict)
        for fc in ("fc1", "fc2", "fc3"):
            assert not isinstance(tree["head"][fc]["w"], dict)
        assert not isinstance(tree["embed"]["w"], dict)

    def test_uniform_int8_export_matches_default_predicate(self, params):
        """A uniform-int8 plan exports exactly the pre-plan whole-tree
        set — the refactor cannot change which leaves quantize."""
        pipe = build(tiny_spec(precision="int8"), params)
        from repro.core import fusion, quant
        fused, _ = fusion.fuse_pointmlp(params, tiny_spec(
            precision="int8").to_model_config())
        want = quant.quantize_tree(fused, pipe.model_config.quant)
        for a, b in zip(jax.tree_util.tree_leaves(pipe.params),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_describe_surfaces_plan(self, params):
        pipe = build(tiny_spec(
            stage_precision=("int8", "int8", "int8", "fp32")), params)
        text = pipe.describe()
        assert "plan" in text and "stage 1: int8" in text
        assert "stage MFLOP" in text


class TestInvalidOverrides:
    def test_stage_precision_wrong_length(self):
        with pytest.raises(ValueError, match="stage_precision"):
            tiny_spec(stage_precision=("int8", "fp32"))

    def test_stage_precision_bad_value(self):
        with pytest.raises(ValueError, match="stage_precision"):
            tiny_spec(stage_precision=("int8", "int8", "int8", "fp64"))

    def test_stage_backend_wrong_shape(self):
        with pytest.raises(ValueError, match="stage_backend"):
            tiny_spec(stage_backend=("ref",))

    def test_stage_backend_unknown_key_lists_names(self, params):
        spec = tiny_spec(stage_backend=("ref", "ref", "tpu-v9", "ref"))
        with pytest.raises(KeyError, match="pallas_interpret"):
            build(spec, params)

    def test_fused_group_unknown_key(self, params):
        with pytest.raises(KeyError, match="grouped_transfer"):
            build(tiny_spec(fused_group="mega_fuse"), params)

    def test_fused_group_rejects_int8_stages(self, params):
        spec = tiny_spec(fused_group="grouped_transfer",
                         stage_precision=("int8", "fp32", "fp32", "fp32"))
        with pytest.raises(ValueError, match="fp32 transfer"):
            build(spec, params)

    def test_fused_group_requires_knn_grouper(self, params):
        spec = tiny_spec(fused_group="grouped_transfer", grouper="ball")
        with pytest.raises(ValueError, match="knn"):
            build(spec, params)

    def test_fused_group_requires_bn_fusion(self, params):
        spec = tiny_spec(fused_group="grouped_transfer", fuse=False)
        with pytest.raises(ValueError, match="fuse"):
            build(spec, params)

    def test_int8_stage_with_pallas_backend_lowers_to_int8_pallas(self):
        """int8 x pallas is a first-class lowering now (RPA101
        retired): the stage's quant config routes the matmuls to the
        int8 Pallas kernel, interpreted on the ``pallas_interpret``
        backend."""
        import warnings as W

        spec = tiny_spec(precision="int8",
                         stage_backend=("ref", "ref", "pallas_interpret",
                                        "ref"))
        with W.catch_warnings():
            W.simplefilter("error")          # no fallback warning left
            plan = SP.lower(spec, spec.to_model_config())
        quants = {op.stage: op.quant for op in plan.cbr_ops()
                  if op.stage is not None}
        assert quants[2].backend == "int8_pallas"
        assert quants[2].interpret is True
        assert quants[0].backend == "int8_ref"


# ------------------------------------------------------------------ #
# plan_shapes: the kernel shapes a spec's lowered plan runs          #
# ------------------------------------------------------------------ #

PLAN_SPECS = {"elite": elite_spec, "m2": m2_spec, "lite": lite_spec}


@pytest.mark.parametrize("kernel", ["fused_linear", "int8_matmul",
                                    "grouped_transfer", "fps", "knn"])
@pytest.mark.parametrize("variant", ["elite", "m2", "lite", "partseg"])
def test_plan_shapes_match_lowered_plan(bench_spec, variant, kernel):
    """Each entry equals the shape read off the lowered plan and the
    model's weights: the matmuls at the FLOP-heaviest stage transfer
    (its weight is ``[transfer_in, C]``, so xyz counts under
    ``use_xyz``), the mapping kernels at stage 1."""
    spec = (bench_spec("partseg") if variant == "partseg"
            else PLAN_SPECS[variant]())
    cfg = spec.to_model_config()
    plan = SP.lower(spec, cfg)
    params = jax.eval_shape(lambda key: PM.pointmlp_init(key, cfg),
                            jax.random.PRNGKey(0))
    samples = {op.stage: op.n_samples for op in plan.ops
               if isinstance(op, SP.SampleOp)}
    ks = {op.stage: op.k for op in plan.ops if isinstance(op, SP.GroupOp)}
    transfers = [(samples[op.stage] * ks[op.stage],
                  *SP.param_at(params, op.path)["w"].shape)
                 for op in plan.ops if isinstance(op, SP.CBROp)]
    assert len(transfers) == 4
    embed = next(op.cbr for op in plan.ops if isinstance(op, SP.EmbedOp))
    c_embed = SP.param_at(params, embed.path)["w"].shape[1]
    n, s0, k0 = spec.n_points, samples[0], ks[0]
    want = {"fused_linear": max(transfers, key=math.prod),
            "int8_matmul": max(transfers, key=math.prod),
            "grouped_transfer": (n, s0, k0, c_embed),
            "fps": (n, s0), "knn": (s0, n, k0)}[kernel]
    assert SP.plan_shapes(spec)[kernel] == want


# ------------------------------------------------------------------ #
# mixed precision (the acceptance ladder point)                      #
# ------------------------------------------------------------------ #

class TestMixedPrecision:
    MIX = ("int8", "int8", "int8", "fp32")

    def test_serves_through_both_engines(self, params, clouds):
        spec = tiny_spec(stage_precision=self.MIX)
        sync = PointCloudEngine(params, spec, max_batch=4, seed=SEED)
        got_sync = np.asarray(sync.classify(clouds))
        eng = AsyncPointCloudEngine.from_params(params, spec,
                                                max_batch=4, seed=SEED)
        futures = [eng.submit(c) for c in clouds]
        eng.flush()
        got_async = np.stack([np.asarray(f.result()) for f in futures])
        assert got_sync.shape == got_async.shape == (clouds.shape[0], 8)
        assert np.all(np.isfinite(got_sync))

    def test_lands_between_uniform_rows_on_accuracy_proxy(self, params,
                                                          clouds):
        state = lambda: sampling.seed_streams(SEED, clouds.shape[0])  # noqa: E731
        fp32, _ = build(tiny_spec(), params).infer(clouds, state())
        mixed, _ = build(tiny_spec(stage_precision=self.MIX),
                         params).infer(clouds, state())
        int8, _ = build(tiny_spec(precision="int8"),
                        params).infer(clouds, state())
        err_mixed = float(jnp.mean(jnp.abs(mixed - fp32)))
        err_int8 = float(jnp.mean(jnp.abs(int8 - fp32)))
        assert 0.0 < err_mixed <= err_int8 * 1.2, \
            f"mixed={err_mixed} int8={err_int8}"


# ------------------------------------------------------------------ #
# fused group->normalize->transfer                                   #
# ------------------------------------------------------------------ #

class TestFusedGroupTransfer:
    def test_registered(self):
        assert "grouped_transfer" in FUSED_OPS

    def test_matches_unfused_serving(self, params, clouds):
        state = sampling.seed_streams(SEED, clouds.shape[0])
        want, wst = build(tiny_spec(), params, jit=False).infer(
            clouds, state)
        got, gst = build(tiny_spec(fused_group="grouped_transfer"),
                         params, jit=False).infer(clouds, state)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(gst), np.asarray(wst))

    def test_matches_unfused_batch_sigma(self, params, clouds):
        """Batch-statistic normalization (non-serving semantics): the
        stats pass reduces over the whole batch, like normalize_group."""
        base = tiny_spec().replace(shared_urs=False,
                                   per_sample_norm=False)
        state = sampling.seed_streams(SEED, 64)
        want, _ = build(base, params, jit=False).infer(clouds, state)
        got, _ = build(base.replace(fused_group="grouped_transfer"),
                       params, jit=False).infer(clouds, state)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_matches_unfused_affine_mode(self, params, clouds):
        """The learnable-affine (Elite) epilogue fuses too."""
        spec = tiny_spec(affine_mode="affine", sampler="fps")
        state = sampling.seed_streams(SEED, clouds.shape[0])
        aff_params = PM.pointmlp_init(jax.random.PRNGKey(0),
                                      spec.to_model_config())
        want, _ = build(spec, aff_params, jit=False).infer(clouds, state)
        got, _ = build(spec.replace(fused_group="grouped_transfer"),
                       aff_params, jit=False).infer(clouds, state)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_through_engines(self, params, clouds):
        spec = tiny_spec(fused_group="grouped_transfer")
        sync = PointCloudEngine(params, spec, max_batch=4, seed=SEED)
        got = np.asarray(sync.classify(clouds))
        want = np.asarray(PointCloudEngine(
            params, tiny_spec(), max_batch=4, seed=SEED).classify(clouds))
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_fused_plan_has_no_group_or_transfer_ops(self):
        spec = tiny_spec(fused_group="grouped_transfer")
        plan = SP.lower(spec, spec.to_model_config())
        kinds = [type(op).__name__ for op in plan.ops]
        assert kinds.count("FusedGroupTransferOp") == 4
        assert "GroupOp" not in kinds
        assert kinds.count("CBROp") == 0       # transfers absorbed
        assert "grouped_transfer" in plan.describe()

    def test_rejects_unfused_transfer_params(self):
        from repro.kernels.grouped_transfer import fused_group_transfer
        xyz = jnp.zeros((1, 16, 3))
        feats = jnp.zeros((1, 16, 4))
        idx = jnp.zeros((1, 8), jnp.int32)
        with pytest.raises(ValueError, match="fused fp32"):
            fused_group_transfer(xyz, feats, idx, 4, None, "norm", True,
                                 {"w": {"q": 0, "scale": 1.0}})


# ------------------------------------------------------------------ #
# ball-query grouper                                                 #
# ------------------------------------------------------------------ #

class TestBallGrouper:
    def test_registered(self):
        assert "ball" in GROUPERS

    def test_infinite_radius_is_knn_bit_identical(self, params, clouds):
        """radius=inf degrades to plain KNN exactly — golden through
        the plan interpreter."""
        register_grouper("_test_ball_inf")(
            make_ball_grouper(float("inf")))
        try:
            state = sampling.seed_streams(SEED, clouds.shape[0])
            knn, _ = build(tiny_spec(), params, jit=False).infer(
                clouds, state)
            ball, _ = build(tiny_spec(grouper="_test_ball_inf"), params,
                            jit=False).infer(clouds, state)
            np.testing.assert_array_equal(np.asarray(ball),
                                          np.asarray(knn))
        finally:
            GROUPERS.unregister("_test_ball_inf")

    def test_default_radius_serves_finite_and_deterministic(self, params,
                                                            clouds):
        spec = tiny_spec(grouper="ball")
        pipe = build(spec, params)
        state = sampling.seed_streams(SEED, clouds.shape[0])
        a, _ = pipe.infer(clouds, jnp.array(state))
        b, _ = pipe.infer(clouds, jnp.array(state))
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_radius_cap_replaces_out_of_ball_neighbors(self):
        """A far straggler selected by KNN is replaced by the nearest
        in-ball neighbor (PointNet++ fill semantics)."""
        pts = jnp.array([[0.0, 0, 0], [0.1, 0, 0], [0.2, 0, 0],
                         [5.0, 0, 0]])
        idx = knn_core.ball_query(pts[:1], pts, k=4, radius=1.0)
        assert idx.shape == (1, 4)
        got = np.asarray(idx[0])
        assert 3 not in got          # the straggler is out of ball
        assert got[0] == 0           # nearest is the center itself
        # plain knn would have kept it:
        assert 3 in np.asarray(knn_core.knn(pts[:1], pts, 4)[0])

    def test_rejects_nonpositive_radius(self):
        """A sign-error radius must not masquerade as its absolute
        value (the in-ball test squares it)."""
        for bad in (-0.2, 0.0, float("nan")):
            with pytest.raises(ValueError, match="radius"):
                make_ball_grouper(bad)

    def test_through_async_engine(self, params, clouds):
        eng = AsyncPointCloudEngine.from_params(
            params, tiny_spec(grouper="ball"), max_batch=4, seed=SEED)
        futures = [eng.submit(c) for c in clouds[:4]]
        eng.flush()
        assert all(f.done() for f in futures)


# ------------------------------------------------------------------ #
# cost breakdown                                                     #
# ------------------------------------------------------------------ #

class TestCostBreakdown:
    def test_flops_breakdown_sums_to_total(self):
        for cfg in (PM.pointmlp_elite_config(), PM.pointmlp_m2_config(),
                    tiny_spec().to_model_config()):
            br = PM.pointmlp_flops_breakdown(cfg)
            assert sum(br.values()) == PM.pointmlp_flops(cfg)
            assert set(br) >= {"embed", "head", "stage1.transfer",
                               "stage4.pos"}

    def test_plan_cost_breakdown_matches_flops(self, params):
        pipe = build(tiny_spec(), params)
        rows = pipe.cost_breakdown()
        assert sum(r["flops"] for r in rows) == pipe.flops()
        by_op = {r["op"]: r for r in rows}
        assert by_op["stage1.group"]["act_bytes"] > 0

    def test_int8_stages_shrink_weight_bytes(self, params):
        fp32 = build(tiny_spec(), params).cost_breakdown()
        mixed = build(tiny_spec(
            stage_precision=("int8", "int8", "int8", "fp32")),
            params).cost_breakdown()
        f32 = {r["op"]: r for r in fp32}
        mix = {r["op"]: r for r in mixed}
        assert mix["stage1.transfer"]["w_bytes"] < \
            f32["stage1.transfer"]["w_bytes"]
        assert mix["stage4.transfer"]["w_bytes"] == \
            f32["stage4.transfer"]["w_bytes"]

    def test_fused_stage_halves_grouped_tensor_round_trip(self, params):
        """Fusion removes the [S,k,2C] grouped round-trip but the sigma
        stats pass still reads a [S,k,C] gather — traffic halves."""
        unfused = build(tiny_spec(), params).cost_breakdown()
        fused = build(tiny_spec(fused_group="grouped_transfer"),
                      params).cost_breakdown()
        uf = {r["op"]: r for r in unfused}
        fu = {r["op"]: r for r in fused}
        for s in range(1, 5):
            assert uf[f"stage{s}.group"]["act_bytes"] > 0
            assert fu[f"stage{s}.group"]["act_bytes"] == \
                uf[f"stage{s}.group"]["act_bytes"] // 2
