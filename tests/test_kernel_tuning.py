"""Kernel tuning layer contracts (``repro.kernels.tuning`` + friends).

Four layers under test, mirroring the PR:

* **tile-sweep identity** — every tunable kernel, swept over its tile
  grid *including non-divisible shapes* (padding remainders), stays
  pinned to its ``ref.py`` oracle in interpret mode: bit-identical for
  the integer kernels (kNN/FPS indices, int8's int32 accumulator) and
  for f32 kernels at a fixed reduction tile; tight allclose when ``tk``
  reassociates the accumulation.  Hypothesis widens the shape sweep
  when installed; the deterministic grid always runs.
* **threading** — ``PipelineSpec.kernel_tuning`` flows through
  ``lower()`` onto each op (backend-fn kwargs, QuantConfig tiles, the
  fused op's ``tile_s``) and out of ``describe()``; a non-default
  tuning with the same reduction tile is observationally invisible.
* **micro-autotuner** — ``repro.tune.kernels`` sweeps/caches/ranks, and
  the static candidate axis multiplies ``enumerate_plan_space``; the
  roofline estimate's ``_tile_waste`` term ranks oversized tiles worse
  on narrow layers.
* **launch profiles** — ``repro.launch.profile`` env semantics:
  explicit env wins, ``apply()`` is idempotent, unknown keys raise.
"""
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import build, lite_spec
from repro.api import plan as SP
from repro.core import sampling
from repro.core.quant import compute_scale, quantize
from repro.data import pointclouds
from repro.kernels import ref
from repro.kernels.fps import fps_pallas
from repro.kernels.fused_linear import fused_linear_pallas
from repro.kernels.grouped_transfer import grouped_transfer_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas
from repro.kernels.knn import knn_pallas
from repro.kernels.tuning import (DEFAULT_TUNING, KernelTuning,
                                  resolve_interpret)
from repro.models import pointmlp as PM

KEY = jax.random.PRNGKey(0)
SEED = 7


def tiny_spec(**overrides):
    over = dict(n_points=128, embed_dim=16, k_neighbors=8,
                precision="fp32", backend="ref")
    over.update(overrides)
    return lite_spec(8).replace(**over).serving()


# ------------------------------------------------------------------ #
# config contracts                                                   #
# ------------------------------------------------------------------ #

class TestKernelTuningConfig:
    def test_defaults_reproduce_historical_tiles(self):
        t = DEFAULT_TUNING
        assert t.fused_linear == (128, 128, 128)
        assert t.int8_matmul == (128, 128, 128)
        assert t.grouped_transfer == 64
        assert t.fps == 512 and t.knn == 128
        assert t.flash_attention == (128, 128)

    def test_hashable_and_replace(self):
        a = KernelTuning()
        b = a.replace(knn=64)
        assert hash(a) == hash(KernelTuning()) and a != b
        assert b.knn == 64 and b.fused_linear == a.fused_linear

    def test_lists_coerced_to_tuples(self):
        t = KernelTuning(fused_linear=[64, 64, 64])
        assert t.fused_linear == (64, 64, 64)
        hash(t)                              # still fingerprintable

    @pytest.mark.parametrize("bad", [
        dict(fused_linear=(64, 64)),         # arity
        dict(int8_matmul=(64, 64, 0)),       # non-positive
        dict(knn=-1),
        dict(fps=True),                      # bool is not a tile
        dict(flash_attention=(64, 64, 64)),
    ])
    def test_invalid_tiles_rejected(self, bad):
        with pytest.raises(ValueError, match="KernelTuning"):
            KernelTuning(**bad)

    def test_spec_validates_and_fingerprints_tuning(self):
        base = tiny_spec()
        tuned = base.replace(kernel_tuning=KernelTuning(knn=64))
        assert SP.spec_fingerprint(tuned) != SP.spec_fingerprint(base)
        with pytest.raises(ValueError, match="kernel_tuning"):
            base.replace(kernel_tuning=(64, 64, 64))

    def test_resolve_interpret(self):
        assert resolve_interpret(True) is True
        assert resolve_interpret(False) is False
        # this container is CPU-only: the platform default interprets
        assert resolve_interpret(None) is (jax.default_backend() != "tpu")


# ------------------------------------------------------------------ #
# tile sweep identity vs ref (interpret mode)                        #
# ------------------------------------------------------------------ #

# Non-divisible shapes on purpose: every kernel pads up to the tile and
# must mask/slice the remainder away.
KNN_SHAPES = [(50, 70, 5), (128, 256, 8)]
MM_SHAPES = [(50, 36, 20), (128, 128, 64)]


class TestTileSweepIdentity:
    @pytest.mark.parametrize("tile_s", [32, 48, 128])
    @pytest.mark.parametrize("s,n,k", KNN_SHAPES)
    def test_knn_bit_identical_across_tiles(self, tile_s, s, n, k):
        k1, k2 = jax.random.split(jax.random.fold_in(KEY, s * n))
        smp = jax.random.normal(k1, (s, 3))
        pts = jax.random.normal(k2, (n, 3))
        got = knn_pallas(smp, pts, k, tile_s=tile_s, interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(ref.knn_ref(smp, pts, k)))

    @pytest.mark.parametrize("tile_n", [100, 256, 512])
    def test_fps_bit_identical_across_tiles(self, tile_n):
        pts = jax.random.normal(KEY, (150, 3))    # 150 % 100 != 0
        got = fps_pallas(pts, 40, interpret=True, tile_n=tile_n)
        # pure-jnp oracle: the same greedy walk via fps_update_ref
        dists = jnp.full((150,), jnp.inf)
        idxs = [jnp.int32(0)]
        for _ in range(39):
            dists, nxt = ref.fps_update_ref(pts, pts[idxs[-1]], dists)
            idxs.append(nxt)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(jnp.stack(idxs)))

    @pytest.mark.parametrize("tiles", [(32, 32, 32), (48, 64, 96),
                                       (128, 128, 128)])
    @pytest.mark.parametrize("m,k,n", MM_SHAPES)
    def test_int8_matmul_bit_identical_across_tiles(self, tiles, m, k, n):
        kk = jax.random.fold_in(KEY, m + k + n)
        xq = jax.random.randint(kk, (m, k), -128, 128, jnp.int8)
        wq = jax.random.randint(jax.random.fold_in(kk, 1), (k, n),
                                -128, 128, jnp.int8)
        sc = jax.random.uniform(jax.random.fold_in(kk, 2), (1, n)) * 0.1
        tm, tk, tn = tiles
        got = int8_matmul_pallas(xq, wq, sc, tm=tm, tk=tk, tn=tn,
                                 interpret=True)
        # int32 accumulation is order-independent: exact across tk too.
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref.int8_matmul_ref(xq, wq, sc)))

    @pytest.mark.parametrize("tm,tn", [(32, 32), (48, 96), (128, 128)])
    @pytest.mark.parametrize("m,k,n", MM_SHAPES)
    def test_fused_linear_bit_identical_at_fixed_tk(self, tm, tn, m, k, n):
        kk = jax.random.fold_in(KEY, m * 3 + n)
        x = jax.random.normal(kk, (m, k))
        w = jax.random.normal(jax.random.fold_in(kk, 1), (k, n)) * 0.05
        b = jax.random.normal(jax.random.fold_in(kk, 2), (n,)) * 0.1
        want = fused_linear_pallas(x, w, b, activation="relu",
                                   tm=128, tk=128, tn=128, interpret=True)
        got = fused_linear_pallas(x, w, b, activation="relu",
                                  tm=tm, tk=128, tn=tn, interpret=True)
        # same reduction tile -> identical accumulation order
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("tk", [32, 48, 128])
    def test_fused_linear_allclose_across_tk(self, tk):
        m, k, n = 50, 130, 20                 # 130 % 48 != 0
        x = jax.random.normal(KEY, (m, k))
        w = jax.random.normal(jax.random.fold_in(KEY, 1), (k, n)) * 0.05
        b = jnp.zeros((n,))
        got = fused_linear_pallas(x, w, b, activation="relu",
                                  tm=64, tk=tk, tn=64, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(ref.fused_linear_ref(x, w, b, "relu")),
            atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("tile_s", [16, 48, 64])
    @pytest.mark.parametrize("s", [50, 64])
    def test_grouped_transfer_matches_oracle_across_tiles(self, tile_s, s):
        n, k, c = 90, 6, 12
        kk = jax.random.fold_in(KEY, s + tile_s)
        feats = jax.random.normal(kk, (n, c))
        nidx = jax.random.randint(jax.random.fold_in(kk, 1), (s, k),
                                  0, n, jnp.int32)
        cen = feats[jax.random.randint(jax.random.fold_in(kk, 2), (s,),
                                       0, n, jnp.int32)]
        alpha = jax.random.normal(jax.random.fold_in(kk, 3), (1, c))
        beta = jax.random.normal(jax.random.fold_in(kk, 4), (1, c)) * 0.1
        w = jax.random.normal(jax.random.fold_in(kk, 5),
                              (2 * c, c)) * 0.05
        b = jnp.zeros((1, c))
        got = grouped_transfer_pallas(feats, nidx, cen, None, alpha,
                                      beta, w, b, k=k, normalize=True,
                                      affine=True, act=True,
                                      tile_s=tile_s, interpret=True)
        # jnp oracle of the two-pass kernel (in-kernel sigma stats)
        eps = 1e-5
        off = feats[nidx] - cen[:, None, :]          # [s, k, c]
        sigma = jnp.sqrt(jnp.sum(off * off) / (s * k * c) + eps)
        offn = off / (sigma + eps) * alpha[0] + beta[0]
        cen_b = jnp.broadcast_to(cen[:, None, :], (s, k, c))
        x = jnp.concatenate([offn, cen_b], -1).reshape(s * k, 2 * c)
        want = jnp.maximum(x @ w + b[0], 0.0).reshape(s, k, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("tq,tk", [(64, 64), (64, 128), (128, 128)])
    def test_flash_attention_allclose_across_tiles(self, tq, tk):
        from repro.kernels.flash_attention import flash_attention_pallas
        k1, k2, k3 = jax.random.split(KEY, 3)
        q = jax.random.normal(k1, (1, 4, 200, 32))   # 200 % 64 != 0
        kkv = jax.random.normal(k2, (1, 2, 200, 32))
        v = jax.random.normal(k3, (1, 2, 200, 32))
        got = flash_attention_pallas(q, kkv, v, causal=True, tq=tq,
                                     tk=tk, interpret=True)
        want = ref.attention_ref(q, kkv, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_hypothesis_property_int_kernels_exact(self):
        hyp = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        @hyp.given(s=st.integers(4, 80), n=st.integers(16, 120),
                   k=st.integers(1, 8),
                   tile_s=st.sampled_from([16, 48, 64, 128]))
        @hyp.settings(max_examples=15, deadline=None)
        def prop(s, n, k, tile_s):
            kk = jax.random.fold_in(KEY, s * 131 + n * 7 + k)
            smp = jax.random.normal(kk, (s, 3))
            pts = jax.random.normal(jax.random.fold_in(kk, 1), (n, 3))
            got = knn_pallas(smp, pts, min(k, n), tile_s=tile_s,
                             interpret=True)
            np.testing.assert_array_equal(
                np.asarray(got),
                np.asarray(ref.knn_ref(smp, pts, min(k, n))))

        prop()


# ------------------------------------------------------------------ #
# int8 Pallas CBR path                                               #
# ------------------------------------------------------------------ #

class TestInt8PallasCBR:
    @pytest.mark.parametrize("tiles", [(32, 32, 32), (64, 64, 64),
                                       (128, 128, 128)])
    def test_ops_int8_matmul_bit_identical_across_tiles(self, tiles):
        """The A8 wrapper (on-the-fly activation quant + int8 kernel)
        equals its ref composition exactly, any tile."""
        from repro.kernels import ops
        m, k, n = 50, 36, 20
        x = jax.random.normal(KEY, (m, k))
        wq = jax.random.randint(jax.random.fold_in(KEY, 1), (k, n),
                                -128, 128, jnp.int8)
        ws = jax.random.uniform(jax.random.fold_in(KEY, 2), (n,)) * 0.1
        got = ops.int8_matmul(x, wq, ws, tiles=tiles, interpret=True)
        a_scale = compute_scale(x, 8)
        xq = quantize(x, a_scale, 8).astype(jnp.int8)
        want = ref.int8_matmul_ref(
            xq, wq, (a_scale * ws.reshape(1, -1)).astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_int8_pallas_pipeline_builds_and_serves(self):
        """precision=int8 x backend=pallas_interpret is a first-class
        deployment: lowers clean, serves finite and deterministic, and
        matches a rebuilt twin bit-for-bit."""
        spec = tiny_spec(precision="int8", backend="pallas_interpret")
        params = PM.pointmlp_init(jax.random.PRNGKey(0),
                                  spec.to_model_config())
        clouds, _ = pointclouds.make_batch(jax.random.PRNGKey(1),
                                           spec.n_points, 4)
        state = sampling.seed_streams(SEED, 4)
        pipe = build(spec, params, jit=False)
        a, _ = pipe.infer(clouds, state)
        b, _ = build(spec, params, jit=False).infer(clouds, state)
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        text = pipe.describe()
        assert "int8_pallas matmul" in text
        assert "tiles 128x128x128" in text

    def test_int8_pallas_tile_choice_is_semantics_free(self):
        """Different int8 tiles, same logits bit-for-bit (the int32
        accumulator is order-independent)."""
        params = PM.pointmlp_init(jax.random.PRNGKey(0),
                                  tiny_spec().to_model_config())
        clouds, _ = pointclouds.make_batch(jax.random.PRNGKey(1),
                                           tiny_spec().n_points, 4)
        state = sampling.seed_streams(SEED, 4)
        outs = []
        for tiles in ((64, 64, 64), (128, 128, 128)):
            spec = tiny_spec(
                precision="int8", backend="pallas_interpret",
                kernel_tuning=KernelTuning(int8_matmul=tiles))
            got, _ = build(spec, params, jit=False).infer(clouds, state)
            outs.append(np.asarray(got))
        np.testing.assert_array_equal(outs[0], outs[1])


# ------------------------------------------------------------------ #
# tuning threading: spec -> lower() -> ops -> describe()             #
# ------------------------------------------------------------------ #

class TestTuningThreading:
    CUSTOM = KernelTuning(fused_linear=(64, 64, 64),
                          int8_matmul=(32, 64, 96),
                          grouped_transfer=32, fps=256, knn=64)

    def test_lowering_binds_fp32_tiles_onto_backend_fn(self):
        spec = tiny_spec(backend="pallas_interpret",
                         kernel_tuning=self.CUSTOM)
        plan = SP.lower(spec, spec.to_model_config())
        for op in plan.cbr_ops():
            assert op.fn.keywords["tiles"] == (64, 64, 64)
        assert "tiles 64x64x64" in plan.describe()

    def test_lowering_binds_int8_tiles_onto_quant(self):
        spec = tiny_spec(precision="int8", backend="pallas_interpret",
                         kernel_tuning=self.CUSTOM)
        plan = SP.lower(spec, spec.to_model_config())
        quants = [op.quant for op in plan.cbr_ops()]
        assert quants and all(q.backend == "int8_pallas" for q in quants)
        assert all(q.tiles == (32, 64, 96) for q in quants)

    def test_lowering_binds_tile_s_onto_fused_op(self):
        spec = tiny_spec(fused_group="grouped_transfer",
                         kernel_tuning=self.CUSTOM)
        plan = SP.lower(spec, spec.to_model_config())
        fused = [op for op in plan.ops
                 if type(op).__name__ == "FusedGroupTransferOp"]
        assert fused
        assert "tile_s=32" in plan.describe()

    def test_non_default_tiles_bit_identical_same_tk(self):
        """Same reduction tile, different tm/tn: the golden contract
        holds bit-for-bit through a real build."""
        params = PM.pointmlp_init(jax.random.PRNGKey(0),
                                  tiny_spec().to_model_config())
        clouds, _ = pointclouds.make_batch(jax.random.PRNGKey(1),
                                           tiny_spec().n_points, 4)
        state = sampling.seed_streams(SEED, 4)
        base = tiny_spec(backend="pallas_interpret")
        want, _ = build(base, params, jit=False).infer(clouds, state)
        tuned = base.replace(kernel_tuning=KernelTuning(
            fused_linear=(64, 128, 64)))
        got, _ = build(tuned, params, jit=False).infer(clouds, state)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------------ #
# micro-autotuner                                                    #
# ------------------------------------------------------------------ #

class TestMicroAutotuner:
    @pytest.fixture(autouse=True)
    def _fresh_cache(self):
        from repro.tune import kernels as K
        K.clear_cache()
        yield
        K.clear_cache()

    def test_sweep_returns_sorted_and_caches(self):
        from repro.tune import kernels as K
        table = K.sweep("knn", (40, 70, 5), quick=True, iters=1,
                        interpret=True)
        assert len(table) == len(K.TILE_GRIDS["knn"]["quick"])
        times = [us for _, us in table]
        assert times == sorted(times) and all(us > 0 for us in times)
        assert K.sweep("knn", (40, 70, 5), quick=True) is table  # cached

    def test_best_tile_comes_from_grid(self):
        from repro.tune import kernels as K
        tile = K.best_tile("fps", (100, 30), quick=True, iters=1,
                           interpret=True)
        assert tile in K.TILE_GRIDS["fps"]["quick"]

    def test_failed_tiles_skip_and_empty_sweep_raises(self):
        from repro.tune import kernels as K
        # a 2-tuple cannot unpack into (tm, tk, tn): every tile fails
        with pytest.raises(ValueError, match="every tile failed"):
            K.sweep("fused_linear", (32, 32, 32), grid=((64, 64),),
                    iters=1, interpret=True)
        # ...but one good tile among bad ones is a skip, not a fatal
        table = K.sweep("fused_linear", (32, 32, 32),
                        grid=((64, 64), (64, 64, 64)), iters=1,
                        interpret=True)
        assert [t for t, _ in table] == [(64, 64, 64)]

    def test_unknown_kernel_raises_with_names(self):
        from repro.tune import kernels as K
        with pytest.raises(KeyError, match="grouped_transfer"):
            K.sweep("conv3d", (8, 8), iters=1)

    def test_plan_shapes_covers_pipeline_kernels(self):
        from repro.tune import kernels as K
        shapes = K.plan_shapes(tiny_spec())
        assert set(shapes) == {"fused_linear", "int8_matmul",
                               "grouped_transfer", "fps", "knn"}
        cfg = tiny_spec().to_model_config()
        assert shapes["fps"] == (cfg.n_points, cfg.stage_samples[0])
        m, k2, n = shapes["fused_linear"]
        assert m > 0 and k2 % 2 == 0 and n in cfg.stage_dims

    def test_plan_tuning_returns_swept_kernel_tuning(self):
        from repro.tune import kernels as K
        kt = K.plan_tuning(tiny_spec(), quick=True, iters=1,
                           interpret=True)
        assert isinstance(kt, KernelTuning)
        assert kt.fused_linear in K.TILE_GRIDS["fused_linear"]["quick"]
        assert kt.knn in K.TILE_GRIDS["knn"]["quick"]
        # flash_attention has no pipeline site: stays at the default
        assert kt.flash_attention == DEFAULT_TUNING.flash_attention

    def test_tuning_candidates_distinct_and_hashable(self):
        from repro.tune.kernels import tuning_candidates
        quick = tuning_candidates(quick=True)
        full = tuning_candidates(quick=False)
        assert DEFAULT_TUNING in quick
        assert len(set(quick)) == len(quick) >= 2
        assert len(set(full)) > len(set(quick))


# ------------------------------------------------------------------ #
# search axis + roofline tile waste                                  #
# ------------------------------------------------------------------ #

class TestSearchIntegration:
    def test_enumerate_plan_space_multiplies_tunings(self):
        cands = tuple(KernelTuning(knn=t) for t in (64, 128))
        specs = SP.enumerate_plan_space(tiny_spec(),
                                        kernel_tunings=cands)
        seen = {s.kernel_tuning for s in specs}
        assert seen >= set(cands)

    def test_quick_space_carries_tuning_axis(self):
        from repro.tune.search import quick_space
        tunings = {s.kernel_tuning for s in quick_space(tiny_spec())}
        assert len(tunings) >= 2

    def test_artifact_row_records_tile_numerics(self):
        from repro.tune.search import Candidate, _row
        spec = tiny_spec(kernel_tuning=KernelTuning(knn=64))
        cand = Candidate(spec=spec,
                         fingerprint=SP.spec_fingerprint(spec),
                         label=SP.spec_label(spec))
        row = _row(cand)
        kt = row["spec"]["kernel_tuning"]
        assert kt["knn"] == 64
        assert kt["fused_linear"] == [128, 128, 128]

    def test_ceil_waste(self):
        from repro.roofline import _ceil_waste
        assert _ceil_waste(128, 64) == 1.0
        assert _ceil_waste(100, 64) == pytest.approx(1.28)
        assert _ceil_waste(10, 128) == pytest.approx(12.8)

    def test_tile_waste_ranks_oversized_tiles_worse(self):
        """On tiny layers, 128-tiles pad massively; the static estimate
        must prefer the smaller tiling (what the search axis ranks on)."""
        from repro import roofline
        small = tiny_spec(backend="pallas_interpret",
                          kernel_tuning=KernelTuning(
                              fused_linear=(32, 32, 32)))
        big = tiny_spec(backend="pallas_interpret")
        waste = {}
        for name, spec in (("small", small), ("big", big)):
            cfg = spec.to_model_config()
            plan = SP.lower(spec, cfg)
            op = next(r["op"] for r in plan.cost_breakdown(cfg)
                      if r["op"].endswith(".transfer"))
            waste[name] = roofline._tile_waste(plan, cfg, op)
        assert waste["small"] < waste["big"]
        assert waste["big"] > 1.0

    def test_estimate_plan_runs_with_tuning(self):
        from repro import roofline
        spec = tiny_spec(backend="pallas_interpret",
                         kernel_tuning=KernelTuning(knn=64))
        cfg = spec.to_model_config()
        est = roofline.estimate_plan(SP.lower(spec, cfg), cfg,
                                     roofline.CPU_HOST)
        assert est.total_s > 0


# ------------------------------------------------------------------ #
# launch profiles                                                    #
# ------------------------------------------------------------------ #

class TestLaunchProfiles:
    def test_explicit_env_wins(self):
        from repro.launch.profile import PROFILES
        prof = PROFILES["cpu-ci"]
        out = prof.launch_env(base={"JAX_PLATFORMS": "tpu",
                                    "XLA_FLAGS": "--mine"})
        assert "JAX_PLATFORMS" not in out and "XLA_FLAGS" not in out
        fresh = prof.launch_env(base={})
        assert fresh["JAX_PLATFORMS"] == "cpu"
        assert "--xla_force_host_platform_device_count=1" \
            in fresh["XLA_FLAGS"]

    def test_apply_is_idempotent_and_undoable(self):
        from repro.launch.profile import PROFILES
        prof = PROFILES["cpu-ci"]
        first = prof.apply()
        try:
            assert prof.apply() == {}        # everything now set
        finally:
            for k in first:
                os.environ.pop(k, None)

    def test_shell_prefix_renders_recipe(self):
        from repro.launch.profile import PROFILES
        prefix = PROFILES["cpu-ci"].shell_prefix()
        assert "JAX_PLATFORMS=cpu" in prefix
        assert "XLA_FLAGS=" in prefix

    def test_tpu_profile_skips_missing_tcmalloc(self):
        from repro.launch.profile import PROFILES, TCMALLOC
        env = PROFILES["tpu"].launch_env(base={})
        if not os.path.exists(TCMALLOC):
            assert "LD_PRELOAD" not in env
        else:                                # pragma: no cover
            assert env["LD_PRELOAD"] == TCMALLOC

    def test_compile_cache_env_var_wins(self, monkeypatch):
        """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and
        the helper sets no other directory."""
        from repro.launch import profile
        before = jax.config.jax_compilation_cache_dir
        min_s = jax.config.jax_persistent_cache_min_compile_time_secs
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        try:
            assert profile.configure_compile_cache() == "/elsewhere/cache"
            assert jax.config.jax_compilation_cache_dir == before
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        finally:
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              min_s)

    def test_compile_cache_defaults_to_fixed_checkout_path(self,
                                                           monkeypatch):
        from repro.launch import profile
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        min_s = jax.config.jax_persistent_cache_min_compile_time_secs
        try:
            first = profile.configure_compile_cache()
            assert jax.config.jax_compilation_cache_dir == first
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
            assert profile.configure_compile_cache() == first
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              min_s)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert pathlib.Path(first) == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text()

    def test_resolution_and_unknown_key(self):
        from repro.launch.profile import launch_profile
        assert launch_profile().name in ("cpu-ci", "gpu", "tpu")
        assert launch_profile("gpu").name == "gpu"
        with pytest.raises(KeyError, match="cpu-ci"):
            launch_profile("fpga")


# ------------------------------------------------------------------ #
# bench integration                                                  #
# ------------------------------------------------------------------ #

class TestBenchRows:
    def test_tile_rows_emit_tile_numerics(self):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        os.pardir, "benchmarks"))
        try:
            import kernels_micro
        finally:
            sys.path.pop(0)
        from repro.tune import kernels as K
        K.clear_cache()
        rows = kernels_micro.tile_rows(quick=True)
        assert {r[0] for r in rows} == {
            "ktune_fused_linear", "ktune_int8_matmul",
            "ktune_grouped_transfer", "ktune_fps", "ktune_knn"}
        for name, us, derived, spec in rows:
            assert us > 0 and "tile=" in derived
            assert isinstance(spec["tile"], (int, list))
            assert all(isinstance(v, int) for v in spec["shape"])
