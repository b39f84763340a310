"""Static plan-verifier contracts (``repro.analysis``).

Three layers under test, mirroring the package:

* **findings** — the typed ``Finding``/``enforce`` primitives every
  caller (``validate()``, ``lower()``, ``build()``, the CLI) shares:
  warning findings warn (``AnalysisWarning``, RPA-coded message, so the
  pyproject gate escalates on the code), error findings raise their
  declared exception type, in order.
* **spec passes** — exact ``RPAxxx`` codes for known-bad spec shapes,
  and the property that the analyzer's verdict *predicts* lowering:
  clean specs build, error specs raise (hypothesis-driven when
  available, a deterministic grid otherwise).
* **trace / contracts** — planted jaxpr-level violations (a silent
  int8->float upcast, f64, a cross-shard collective, a host callback)
  are caught; the legitimate dequant idiom and every shipped variant
  stay clean; mislabeled registry metadata is detected.
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (CODES, AnalysisWarning, Finding, dedupe,
                            enforce, error_codes, finding)
from repro.analysis import contracts as C
from repro.analysis import trace as T
from repro.analysis.passes import (RPA_SKIP_MODULES, analyze_fleet_spec,
                                   analyze_spec, pass_names,
                                   skip_list_findings)
from repro.api import (build, lite_spec, register_grouper,
                       register_sampler)
from repro.api import registry as R
from repro.models import pointmlp as PM

SEED = 0


def tiny_spec(**overrides):
    # Overrides apply AFTER .serving() so tests can undo its
    # per_sample_norm/shared_urs defaults (the RPA020 shapes).
    over = dict(n_points=128, embed_dim=16, k_neighbors=8,
                precision="fp32", backend="ref")
    over.update(overrides)
    return lite_spec(8).serving().replace(**over)


@pytest.fixture(scope="module")
def params():
    return PM.pointmlp_init(jax.random.PRNGKey(SEED),
                            tiny_spec().to_model_config())


def codes(findings):
    return [f.code for f in findings]


# ------------------------------------------------------------------ #
# findings primitives                                                #
# ------------------------------------------------------------------ #

class TestFindings:
    def test_finding_derives_severity_from_code_table(self):
        assert finding("RPA011", "op", "m").severity == "error"
        assert finding("RPA101", "op", "m").severity == "warning"
        assert finding("RPA900", "op", "m").severity == "info"

    def test_unknown_code_rejected(self):
        with pytest.raises(ValueError, match="RPA999"):
            finding("RPA999", "op", "m")

    def test_render_leads_with_code(self):
        f = finding("RPA020", "spec.per_sample_norm", "needs norm")
        assert f.render() == "RPA020: needs norm"

    def test_enforce_warns_then_raises_first_error(self):
        fs = [finding("RPA101", "a", "soft"),
              finding("RPA011", "b", "hard"),
              finding("RPA001", "c", "key", exc_type=KeyError)]
        with pytest.warns(AnalysisWarning, match="RPA101"):
            with pytest.raises(ValueError, match="RPA011"):
                enforce(fs)

    def test_enforce_preserves_declared_exception_type(self):
        with pytest.raises(KeyError, match="RPA001"):
            enforce([finding("RPA001", "c", "key", exc_type=KeyError)])

    def test_enforce_clean_is_silent(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            enforce([])
            enforce([finding("RPA900", "mod", "skip-list info")])

    def test_dedupe_keys_on_code_and_op(self):
        a = finding("RPA101", "x", "m1")
        b = finding("RPA101", "x", "m2 (same site)")
        c = finding("RPA101", "y", "m3")
        assert dedupe([a, b, c]) == [a, c]

    def test_error_codes_sorted_distinct(self):
        fs = [finding("RPA011", "a", "m"), finding("RPA010", "b", "m"),
              finding("RPA011", "c", "m"), finding("RPA101", "d", "m")]
        assert error_codes(fs) == ("RPA010", "RPA011")

    def test_code_table_shape(self):
        for code, (sev, title) in CODES.items():
            assert code.startswith("RPA") and len(code) == 6, code
            assert sev in ("error", "warning", "info")
            assert title


# ------------------------------------------------------------------ #
# spec passes: exact codes for known-bad shapes                      #
# ------------------------------------------------------------------ #

class TestSpecPasses:
    def test_shipped_variants_clean(self):
        from repro.api import elite_spec, m2_spec
        for spec in (tiny_spec(), lite_spec(), elite_spec(), m2_spec()):
            assert analyze_spec(spec) == [], spec.name

    @pytest.mark.parametrize("over,code", [
        (dict(sampler="voxel"), "RPA001"),
        (dict(grouper="octree"), "RPA002"),
        (dict(backend="tpu-v9"), "RPA003"),
        (dict(stage_backend=("ref", "ref", "tpu-v9", "ref")), "RPA003"),
        (dict(fused_group="mega_fuse"), "RPA004"),
        (dict(policy="nope"), "RPA005"),
        (dict(grouper="ball", fused_group="grouped_transfer"), "RPA010"),
        (dict(precision="int8", fused_group="grouped_transfer"), "RPA011"),
        (dict(fuse=False, fused_group="grouped_transfer"), "RPA012"),
        (dict(stream=True, stream_drift_threshold=0.05,
              fused_group="grouped_transfer"), "RPA013"),
        (dict(data_shards=2, per_sample_norm=False), "RPA020"),
    ])
    def test_known_bad_shape_yields_code(self, over, code):
        assert code in codes(analyze_spec(tiny_spec(**over)))

    @pytest.mark.parametrize("over,code", [
        (dict(fused_group="grouped_transfer"), "RPA016"),
        (dict(backend="pallas_interpret"), "RPA017"),
        (dict(stage_backend=("ref", "pallas_interpret", "ref", "ref")),
         "RPA017"),
        (dict(precision="int8", backend="pallas_interpret"), "RPA017"),
    ])
    def test_tpu_refuses_what_would_not_compile(self, monkeypatch, over,
                                                code):
        """On a TPU, lowering refuses the fused gather kernel Mosaic
        rejects and any interpret-mode backend; on the CPU the same
        specs stay clean (the interpret-mode canaries)."""
        from repro.api import plan as SP
        from repro.kernels import tuning
        spec = tiny_spec(**over)
        assert code not in codes(analyze_spec(spec, scopes=("lowering",)))
        monkeypatch.setattr(tuning, "on_tpu", lambda: True)
        assert codes(analyze_spec(spec, scopes=("lowering",))) == [code]
        with pytest.raises(ValueError, match=code):
            SP.lower(spec, spec.to_model_config())

    def test_tpu_keeps_compiled_backends(self, monkeypatch):
        from repro.kernels import tuning
        monkeypatch.setattr(tuning, "on_tpu", lambda: True)
        for spec in (tiny_spec(backend="pallas"),
                     tiny_spec(precision="int8", backend="pallas")):
            assert analyze_spec(spec, scopes=("lowering",)) == []

    @pytest.mark.parametrize("tpu,interpret,want", [
        (False, None, True), (False, True, True), (False, False, False),
        (True, None, False), (True, False, False)])
    def test_resolve_interpret(self, monkeypatch, tpu, interpret, want):
        from repro.kernels import tuning
        monkeypatch.setattr(tuning, "on_tpu", lambda: tpu)
        assert tuning.resolve_interpret(interpret) is want

    def test_interpret_kernel_call_refused_on_tpu(self, monkeypatch):
        """A direct kernel call asking for the interpreter raises on a
        TPU instead of interpreting on the chip."""
        from repro.kernels import tuning
        from repro.kernels.knn import knn_pallas
        monkeypatch.setattr(tuning, "on_tpu", lambda: True)
        with pytest.raises(ValueError, match="RPA017"):
            tuning.resolve_interpret(True)
        pts = jnp.zeros((24, 3))
        with pytest.raises(ValueError, match="RPA017"):
            knn_pallas(pts, pts, 5, tile_s=8, interpret=True)

    def test_int8_pallas_analyzes_clean(self):
        # RPA101 retired: int8 x pallas lowers to the int8 Pallas
        # matmul now, so the analyzer has nothing to flag.
        spec = tiny_spec(precision="int8",
                         stage_backend=("ref", "pallas_interpret",
                                        "ref", "ref"))
        assert analyze_spec(spec) == []

    def test_stage_intensity_anomaly_yields_rpa104(self):
        # Needs lite_spec's full shapes: at tiny_spec's 128-point
        # geometry the crafted imbalance only deviates ~3x (clean).
        from repro.analysis.passes import stage_intensities
        spec = lite_spec(8).serving().replace(
            stage_expansion=(1, 1, 1, 64))
        found = analyze_spec(spec, scopes=("perf",))
        assert [(f.code, f.op) for f in found] == \
            [("RPA104", "plan.stage4")]
        assert found[0].severity == "warning"
        assert "x off" in found[0].message
        # ... and the probe itself: per-stage FLOP/byte, >= 3 stages.
        intens = stage_intensities(spec)
        assert set(intens) == {"stage1", "stage2", "stage3", "stage4"}
        assert all(v > 0 for v in intens.values())

    def test_stage_intensity_anomaly_clean_on_balanced_specs(self):
        # pre_blocks scales FLOPs and bytes together — intensity is
        # invariant, so depth changes must NOT trip the anomaly pass.
        spec = tiny_spec(pre_blocks=(1, 1, 2, 2))
        assert analyze_spec(spec, scopes=("perf",)) == []

    def test_validate_raises_coded_error(self):
        with pytest.raises(KeyError, match="RPA001"):
            tiny_spec(sampler="voxel").validate()
        with pytest.raises(ValueError, match="RPA010"):
            tiny_spec(grouper="ball",
                      fused_group="grouped_transfer").validate()

    def test_scopes_partition_the_passes(self):
        # RPA005 (serving) and RPA020 (placement) stay out of the
        # lowering scope: the tuner lowers sharded/any-policy specs for
        # roofline estimates without building them.
        spec = tiny_spec(policy="nope", data_shards=2,
                         per_sample_norm=False)
        assert codes(analyze_spec(spec, scopes=("lowering",))) == []
        assert "RPA005" in codes(analyze_spec(spec, scopes=("serving",)))
        assert "RPA020" in codes(analyze_spec(spec,
                                              scopes=("placement",)))
        with pytest.raises(ValueError, match="unknown pass scopes"):
            analyze_spec(spec, scopes=("hls",))

    def test_stream_contract_on_registry_gaps(self):
        def bare_grouper(xyz, feats, idx, k, affine, mode, per_sample):
            raise NotImplementedError            # pragma: no cover

        def bare_sampler(xyz, n, state, shared):
            raise NotImplementedError            # pragma: no cover

        register_grouper("_rpa_bare_grouper")(bare_grouper)
        register_sampler("_rpa_bare_sampler")(bare_sampler)
        try:
            spec = tiny_spec(stream=True, stream_drift_threshold=0.05,
                             grouper="_rpa_bare_grouper",
                             sampler="_rpa_bare_sampler")
            got = codes(analyze_spec(spec, scopes=("lowering",)))
            assert "RPA014" in got and "RPA015" in got
        finally:
            R.GROUPERS.unregister("_rpa_bare_grouper")
            R.SAMPLERS.unregister("_rpa_bare_sampler")

    def test_build_rejects_sharded_without_per_sample_norm(self, params):
        spec = tiny_spec(data_shards=2, per_sample_norm=False)
        with pytest.raises(ValueError, match="per_sample_norm"):
            build(spec, params)

    def test_fleet_analysis_prefixes_ops_and_checks_router(self):
        from repro.api.spec import FleetSpec, TenantSpec
        fleet = FleetSpec(
            pipelines=(tiny_spec(name="a"),
                       tiny_spec(name="b", grouper="octree")),
            tenants=(TenantSpec(name="t", tier="a"),),
            router="no-such-router")
        found = analyze_fleet_spec(fleet)
        assert "RPA006" in codes(found)
        bad = [f for f in found if f.code == "RPA002"]
        assert bad and bad[0].op.startswith("pipeline[b].")

    def test_pass_registry_is_pluggable(self):
        from repro.analysis.passes import PASSES, register_pass
        with pytest.raises(ValueError, match="scope"):
            register_pass("_rpa_bad", scope="compile")

        @register_pass("_rpa_test_pass", scope="lowering")
        def _always(spec):
            return [finding("RPA101", "test", "planted")]
        try:
            assert "_rpa_test_pass" in pass_names()
            assert "RPA101" in codes(
                analyze_spec(tiny_spec(), scopes=("lowering",)))
        finally:
            PASSES.unregister("_rpa_test_pass")
        assert analyze_spec(tiny_spec()) == []

    def test_skip_list_reported_as_info(self):
        found = skip_list_findings()
        assert len(found) == len(RPA_SKIP_MODULES)
        assert all(f.code == "RPA900" and f.severity == "info"
                   for f in found)


# ------------------------------------------------------------------ #
# analyzer verdict predicts build (property)                         #
# ------------------------------------------------------------------ #

def _verdict_matches_build(spec, params) -> None:
    found = analyze_spec(spec)
    errs = [f for f in found if f.severity == "error"]
    # Warning findings (e.g. RPA104) are legal-but-noted — silence them
    # so the in-tree escalation gate doesn't shadow the error/clean
    # split this property is about.
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", AnalysisWarning)
        if errs:
            with pytest.raises((ValueError, KeyError)):
                build(spec, params, jit=False)
        else:
            pipe = build(spec, params, jit=False)
            assert pipe.plan is not None


GRID = dict(
    precision=["fp32", "int8"],
    grouper=["knn", "ball"],
    fused_group=["none", "grouped_transfer"],
    fuse=[True, False],
    stage_backend=[None, ("ref", "ref", "pallas_interpret", "ref")],
)


def _grid_points():
    import itertools
    keys = sorted(GRID)
    for vals in itertools.product(*(GRID[k] for k in keys)):
        yield dict(zip(keys, vals))


class TestVerdictPredictsBuild:
    def test_deterministic_grid(self, params):
        # fuse=False changes the param-tree contract, not the analyzer
        # verdict; keep the grid on the frozen-tree side except for the
        # fused-group interaction RPA012 exists for.
        n_err = n_ok = 0
        for over in _grid_points():
            if not over["fuse"] and over["fused_group"] == "none":
                continue                  # unfused trees need BN stats
            spec = tiny_spec(**over)
            if [f for f in analyze_spec(spec) if f.severity == "error"]:
                n_err += 1
            else:
                n_ok += 1
            _verdict_matches_build(spec, params)
        assert n_err and n_ok            # the grid exercises both arms

    def test_hypothesis_property(self, params):
        hyp = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        @hyp.given(
            precision=st.sampled_from(GRID["precision"]),
            grouper=st.sampled_from(GRID["grouper"]),
            fused_group=st.sampled_from(GRID["fused_group"]),
            stage_backend=st.sampled_from(GRID["stage_backend"]),
            stream=st.booleans())
        @hyp.settings(max_examples=20, deadline=None)
        def prop(precision, grouper, fused_group, stage_backend, stream):
            spec = tiny_spec(precision=precision, grouper=grouper,
                             fused_group=fused_group,
                             stage_backend=stage_backend, stream=stream,
                             stream_drift_threshold=0.05 if stream
                             else 0.0)
            _verdict_matches_build(spec, params)

        prop()


# ------------------------------------------------------------------ #
# jaxpr trace pass                                                   #
# ------------------------------------------------------------------ #

def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


class TestTracePass:
    INT8_PARAMS = {"w": {"q": _sds((8, 4), jnp.int8),
                         "scale": _sds((1, 4))},
                   "b": _sds((4,))}

    def test_planted_silent_upcast_caught(self):
        def bad(p, x):               # raw q used as float weights
            return x @ p["w"]["q"].astype(x.dtype) + p["b"]
        found = T.trace_callable(bad, self.INT8_PARAMS, _sds((2, 8)),
                                 where="planted")
        assert "RPA202" in codes(found)

    def test_dequant_idiom_stays_clean(self):
        def good(p, x):
            w = p["w"]["q"].astype(x.dtype) * p["w"]["scale"]
            return x @ w + p["b"]
        assert T.trace_callable(good, self.INT8_PARAMS, _sds((2, 8)),
                                where="ok") == []

    def test_int8_ref_backend_stays_clean(self):
        fn = R.BACKENDS.get("ref")
        from repro.core.quant import QuantConfig
        q = QuantConfig(w_bits=8, a_bits=8, backend="int8_ref")
        found = T.trace_callable(
            lambda p, x: fn(p, x, q, True),
            self.INT8_PARAMS, _sds((2, 8)), where="int8_ref")
        assert found == []

    def test_f64_caught(self):
        with jax.enable_x64(True):
            found = T.trace_callable(
                lambda x: x.astype(jnp.float64) * 2.0, _sds((4,)),
                where="f64")
        assert codes(found) == ["RPA201"]

    def test_data_axis_collective_caught(self):
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        body = jax.shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                             in_specs=(P("data"),), out_specs=P(),
                             check_vma=False)
        assert "RPA204" in codes(
            T.trace_callable(body, _sds((2, 4)), where="psum"))

    def test_host_callback_in_shard_region_caught(self):
        def cb(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        assert "RPA203" in codes(
            T.trace_callable(cb, _sds((4,)), where="cb",
                             in_shard_region=True))
        # ... and is legal outside one
        assert codes(T.trace_callable(cb, _sds((4,)), where="cb")) == []

    def test_untraceable_callable_is_a_finding(self):
        def boom(x):
            raise RuntimeError("no trace for you")
        assert codes(T.trace_callable(boom, _sds((4,)),
                                      where="boom")) == ["RPA209"]

    @pytest.mark.parametrize("over", [
        dict(),
        dict(precision="int8"),
        dict(fused_group="grouped_transfer"),
        dict(stage_precision=("int8", "int8", "int8", "fp32")),
        dict(head="seg"),
    ])
    def test_shipped_plans_trace_clean(self, over):
        assert T.analyze_plan_trace(tiny_spec(**over)) == []


# ------------------------------------------------------------------ #
# determinism contracts                                              #
# ------------------------------------------------------------------ #

class TestContracts:
    def test_builtin_registries_clean(self):
        assert C.check_registry_contracts() == []

    def test_mislabeled_sampler_caught(self):
        def sneaky(xyz, n, state, shared):
            return xyz[:, :n, :], state + 1
        sneaky.advances_state = False            # lies: it advances
        register_sampler("_rpa_sneaky")(sneaky)
        try:
            found = C.check_sampler_contracts(names=["_rpa_sneaky"])
        finally:
            R.SAMPLERS.unregister("_rpa_sneaky")
        assert codes(found) == ["RPA301"]
        assert "advances" in found[0].message

    def test_honest_stateless_sampler_clean(self):
        def honest(xyz, n, state, shared):
            return xyz[:, :n, :], state
        honest.advances_state = False
        register_sampler("_rpa_honest")(honest)
        try:
            assert C.check_sampler_contracts(names=["_rpa_honest"]) == []
        finally:
            R.SAMPLERS.unregister("_rpa_honest")

    def test_order_dependent_router_caught(self):
        from repro.serve.router import ROUTERS, register_router

        @register_router("_rpa_first")
        def first(tenant, candidates, state):
            return candidates[0].replica_id      # order-dependent
        try:
            found = C.check_router_contracts(names=["_rpa_first"])
        finally:
            ROUTERS.unregister("_rpa_first")
        assert codes(found) == ["RPA303"]
        assert "order" in found[0].message

    def test_self_mutating_policy_caught(self):
        from repro.serve.policy import (POLICIES, BatchPolicy,
                                        register_policy)

        @register_policy("_rpa_countdown")
        class Countdown(BatchPolicy):
            def __init__(self, slo_ms=0.0, dispatch_ms=0.0):
                super().__init__(slo_ms, dispatch_ms)
                self.calls = 0

            def decide(self, depth, oldest_wait_ms, max_batch):
                self.calls += 1                  # impure
                return min(depth, max_batch)
        try:
            found = C.check_policy_contracts(names=["_rpa_countdown"])
        finally:
            POLICIES.unregister("_rpa_countdown")
        assert "RPA303" in codes(found)


# ------------------------------------------------------------------ #
# search-space / tuner integration                                   #
# ------------------------------------------------------------------ #

class TestTunerIntegration:
    def test_enumerate_drops_warned_and_invalid_points(self):
        from repro.api.plan import enumerate_plan_space
        specs = enumerate_plan_space(
            tiny_spec(),
            stage_backends=(("ref",) * 4, ("pallas_interpret",) * 4),
            fused_groups=("none", "grouped_transfer"))
        assert specs
        for s in specs:
            assert analyze_spec(s, scopes=("lowering",)) == []

    def test_static_prune_records_coded_est_error(self):
        from repro.api.plan import spec_fingerprint, spec_label
        from repro.tune.search import Candidate, _static_prune
        bad = tiny_spec(grouper="ball", fused_group="grouped_transfer")
        cand = Candidate(spec=bad, fingerprint=spec_fingerprint(bad),
                         label=spec_label(bad))
        assert _static_prune(cand) is True
        assert "RPA010" in cand.est_error
        good = tiny_spec()
        cand = Candidate(spec=good, fingerprint=spec_fingerprint(good),
                         label=spec_label(good))
        assert _static_prune(cand) is False and cand.est_error is None

    def test_tune_records_pruned_candidate_rows(self, params):
        from repro.tune.search import tune
        space = [tiny_spec(stage_precision=("int8",) * 4),
                 tiny_spec(grouper="ball",
                           fused_group="grouped_transfer")]
        doc = tune(tiny_spec(), params, space=space, top_k=1,
                   measure_iters=1)
        rows = {r["name"]: r for r in doc["rows"]}
        pruned = [r for r in rows.values()
                  if r["derived"] and "RPA010" in r["derived"]]
        assert pruned, "analyzer-pruned candidate missing from artifact"
        assert pruned[0]["measured_sps"] is None


# ------------------------------------------------------------------ #
# CLI                                                                #
# ------------------------------------------------------------------ #

class TestCLI:
    def test_default_run_clean(self, capsys):
        from repro.analysis.__main__ import main
        assert main(["--no-trace", "--no-contracts", "-q"]) == 0
        out = capsys.readouterr().out
        assert "SUMMARY" in out and "0 error(s)" in out

    def test_bad_spec_json_exits_nonzero(self, capsys):
        from repro.analysis.__main__ import main
        rc = main(["--spec-json",
                   json.dumps({"grouper": "ball",
                               "fused_group": "grouped_transfer"}),
                   "--no-trace", "--no-contracts"])
        assert rc == 1
        out = capsys.readouterr().out
        assert "RPA010" in out and "RPA011" in out

    def test_malformed_spec_json_exits_nonzero(self, capsys):
        from repro.analysis.__main__ import main
        assert main(["--spec-json", '{"precision": "fp64"}']) == 1

    def test_unknown_key_reports_key_code(self, capsys):
        from repro.analysis.__main__ import main
        rc = main(["--spec-json", '{"sampler": "voxel"}',
                   "--no-trace", "--no-contracts"])
        assert rc == 1
        assert "RPA001" in capsys.readouterr().out
