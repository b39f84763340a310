"""Per-kernel contracts: Pallas (interpret mode) vs the ref.py oracle.

* shapes and dtypes, at each kernel's own tiles;
* tile sweeps, including non-divisible shapes (padding remainders):
  bit-identical for the integer kernels (kNN indices, int8's int32
  accumulator) and for f32 kernels at a fixed reduction tile, tight
  allclose when ``tk`` reassociates the accumulation;
* the int8 Pallas CBR path, from ``ops.int8_matmul`` to a served plan;
* each kernel at the shape a chip benchmark configuration runs it
  (``repro.api.plan.plan_shapes``);
* interpret resolution against the platform.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import build, lite_spec
from repro.api.plan import plan_shapes
from repro.core import sampling
from repro.core.quant import compute_scale, quantize
from repro.data import pointclouds
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_linear import fused_linear_pallas
from repro.kernels.grouped_transfer import grouped_transfer_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas, w8_matmul_pallas
from repro.kernels.knn import knn_pallas
from repro.kernels.tuning import resolve_interpret
from repro.models import pointmlp as PM

KEY = jax.random.PRNGKey(0)
SEED = 7


def tiny_spec(**overrides):
    over = dict(n_points=128, embed_dim=16, k_neighbors=8,
                precision="fp32", backend="ref")
    over.update(overrides)
    return lite_spec(8).replace(**over).serving()


class TestKNNKernel:
    @pytest.mark.parametrize("s,n,c,k", [
        (16, 64, 3, 4), (100, 300, 3, 8), (128, 512, 16, 16),
        (33, 257, 3, 16), (256, 1024, 3, 16),
    ])
    def test_matches_ref(self, s, n, c, k):
        k1, k2 = jax.random.split(jax.random.fold_in(KEY, s * n))
        samples = jax.random.normal(k1, (s, c))
        points = jax.random.normal(k2, (n, c))
        got = knn_pallas(samples, points, k)
        want = ref.knn_ref(samples, points, k)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        k1, k2 = jax.random.split(KEY)
        samples = jax.random.normal(k1, (32, 3)).astype(dtype)
        points = jax.random.normal(k2, (128, 3)).astype(dtype)
        got = knn_pallas(samples, points, 8)
        want = ref.knn_ref(samples.astype(jnp.float32),
                           points.astype(jnp.float32), 8)
        # bf16 distance ties can reorder equidistant far neighbors;
        # require the nearest half to agree exactly
        np.testing.assert_array_equal(np.asarray(got)[:, :4],
                                      np.asarray(want)[:, :4])

    def test_selection_order_ascending(self):
        k1, k2 = jax.random.split(KEY)
        s = jax.random.normal(k1, (8, 3))
        p = jax.random.normal(k2, (64, 3))
        idx = np.asarray(knn_pallas(s, p, 8))
        d = np.asarray(jnp.sum((s[:, None] - p[None]) ** 2, -1))
        for i in range(8):
            picked = d[i, idx[i]]
            assert (np.diff(picked) >= -1e-6).all()


class TestInt8Matmul:
    @pytest.mark.parametrize("m,k,n", [
        (16, 32, 8), (128, 128, 128), (50, 70, 90), (200, 300, 130),
    ])
    def test_matches_ref(self, m, k, n):
        kk = jax.random.fold_in(KEY, m * k * n)
        k1, k2, k3 = jax.random.split(kk, 3)
        xq = jax.random.randint(k1, (m, k), -128, 128, jnp.int8)
        wq = jax.random.randint(k2, (k, n), -128, 128, jnp.int8)
        sc = jnp.abs(jax.random.normal(k3, (1, n))) * 0.01
        got = int8_matmul_pallas(xq, wq, sc)
        want = ref.int8_matmul_ref(xq, wq, sc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    def test_scalar_scale(self):
        k1, k2 = jax.random.split(KEY)
        xq = jax.random.randint(k1, (32, 64), -128, 128, jnp.int8)
        wq = jax.random.randint(k2, (64, 32), -128, 128, jnp.int8)
        sc = jnp.array([[0.02]], jnp.float32)
        got = int8_matmul_pallas(xq, wq, sc)
        want = ref.int8_matmul_ref(xq, wq, sc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("m,k,n", [(33, 65, 129), (128, 256, 64)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_w8a16_matches_ref(self, m, k, n, dtype):
        kk = jax.random.fold_in(KEY, m + k + n)
        k1, k2, k3 = jax.random.split(kk, 3)
        x = jax.random.normal(k1, (m, k)).astype(dtype)
        wq = jax.random.randint(k2, (k, n), -128, 128, jnp.int8)
        sc = (jnp.abs(jax.random.normal(k3, (1, n))) * 0.01 + 1e-3)
        got = w8_matmul_pallas(x, wq, sc)
        # oracle at f32: the kernel keeps an f32 VMEM accumulator + f32
        # scales (TPU semantics), so it is *more* accurate than a pure
        # bf16 matmul; compare both against the f32 truth with
        # per-K-tile accumulation-order slack
        want = ref.w8_matmul_ref(x.astype(jnp.float32), wq, sc)
        tol = (5e-4, 5e-4) if dtype == jnp.float32 else (2e-2, 0.5)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol[0], atol=tol[1])


class TestFusedLinear:
    @pytest.mark.parametrize("act", ["relu", "gelu", "none"])
    @pytest.mark.parametrize("m,k,n", [(32, 48, 24), (130, 70, 250)])
    def test_matches_ref(self, act, m, k, n):
        kk = jax.random.fold_in(KEY, m + 7 * n)
        k1, k2, k3 = jax.random.split(kk, 3)
        x = jax.random.normal(k1, (m, k))
        w = jax.random.normal(k2, (k, n)) * 0.1
        b = jax.random.normal(k3, (n,))
        got = fused_linear_pallas(x, w, b, activation=act)
        want = ref.fused_linear_ref(x, w, b, act)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_bn_fused_equals_conv_bn_relu(self):
        """End-to-end paper path: fold BN, run the fused kernel, compare
        against unfused conv->BN->ReLU."""
        from repro.core import fusion as F
        k1, k2 = jax.random.split(KEY)
        w = jax.random.normal(k1, (24, 16)) * 0.2
        b = jnp.zeros((16,))
        bn = {"gamma": jnp.abs(jax.random.normal(k2, (16,))) + 0.5,
              "beta": jax.random.normal(k1, (16,)) * 0.1,
              "mean": jax.random.normal(k2, (16,)) * 0.1,
              "var": jnp.abs(jax.random.normal(k1, (16,))) + 0.5}
        x = jax.random.normal(k2, (40, 24))
        want = jax.nn.relu(F.batchnorm_apply(x @ w + b, bn))
        wf, bf = F.fuse_conv_bn(w, b, bn)
        got = fused_linear_pallas(x, wf, bf, activation="relu")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("tq,tk,causal,window", [
        (128, 128, True, 0), (200, 200, True, 0), (64, 256, True, 0),
        (200, 200, False, 0), (200, 200, True, 64), (1, 200, True, 0),
    ])
    def test_matches_ref(self, tq, tk, causal, window):
        kk = jax.random.fold_in(KEY, tq * 7 + tk + window)
        k1, k2, k3 = jax.random.split(kk, 3)
        q = jax.random.normal(k1, (2, 8, tq, 64))
        k = jax.random.normal(k2, (2, 2, tk, 64))
        v = jax.random.normal(k3, (2, 2, tk, 64))
        got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                     tq=64, tk=64)
        want = ref.attention_ref(q, k, v, causal=causal,
                                 sliding_window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_bf16(self):
        k1, k2, k3 = jax.random.split(KEY, 3)
        q = jax.random.normal(k1, (1, 4, 128, 32)).astype(jnp.bfloat16)
        k = jax.random.normal(k2, (1, 4, 128, 32)).astype(jnp.bfloat16)
        v = jax.random.normal(k3, (1, 4, 128, 32)).astype(jnp.bfloat16)
        got = flash_attention_pallas(q, k, v, tq=64, tk=64)
        want = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2)

    def test_mha_no_gqa(self):
        k1, k2, k3 = jax.random.split(KEY, 3)
        q = jax.random.normal(k1, (2, 4, 96, 32))
        k = jax.random.normal(k2, (2, 4, 96, 32))
        v = jax.random.normal(k3, (2, 4, 96, 32))
        got = flash_attention_pallas(q, k, v, tq=32, tk=32)
        want = ref.attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


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


class TestInt8PallasCBR:
    @pytest.mark.parametrize("m,k,n", [(50, 36, 20), (128, 128, 64),
                                       (200, 300, 130)])
    def test_ops_int8_matmul_bit_identical_across_tiles(self, m, k, n):
        """The A8 wrapper (on-the-fly activation quant + int8 kernel)
        equals its ref composition exactly, whether the shape fills the
        kernel's tiles, leaves a remainder or spans several K tiles."""
        from repro.kernels import ops
        x = jax.random.normal(KEY, (m, k))
        wq = jax.random.randint(jax.random.fold_in(KEY, 1), (k, n),
                                -128, 128, jnp.int8)
        ws = jax.random.uniform(jax.random.fold_in(KEY, 2), (n,)) * 0.1
        got = ops.int8_matmul(x, wq, ws, interpret=True)
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


class TestAtBenchmarkShapes:
    """Each kernel against its oracle, interpreted, at the shape the
    chip benchmark's configuration runs it."""

    @pytest.mark.parametrize("config", ["elite", "lite", "partseg"])
    def test_knn(self, bench_spec, config):
        s, n, k = plan_shapes(bench_spec(config))["knn"]
        k1, k2 = jax.random.split(jax.random.fold_in(KEY, n))
        points = jax.random.normal(k1, (n, 3))
        samples = points[jax.random.permutation(k2, n)[:s]]
        got = knn_pallas(samples, points, k, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref.knn_ref(samples, points, k)))

    @pytest.mark.parametrize("config", ["elite", "partseg"])
    def test_fused_linear(self, bench_spec, config):
        m, k, n = plan_shapes(bench_spec(config))["fused_linear"]
        k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, m * k), 3)
        x = jax.random.normal(k1, (m, k))
        w = jax.random.normal(k2, (k, n)) / np.sqrt(k)
        b = jax.random.normal(k3, (n,)) * 0.1
        got = fused_linear_pallas(x, w, b, activation="relu",
                                  interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.fused_linear_ref(x, w, b)),
            rtol=1e-5, atol=1e-5)

    def test_int8_matmul(self, bench_spec):
        m, k, n = plan_shapes(bench_spec("lite"))["int8_matmul"]
        k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, m * k), 3)
        xq = jax.random.randint(k1, (m, k), -128, 128, jnp.int8)
        wq = jax.random.randint(k2, (k, n), -128, 128, jnp.int8)
        sc = jax.random.uniform(k3, (1, n)) * 1e-3
        got = int8_matmul_pallas(xq, wq, sc, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref.int8_matmul_ref(xq, wq, sc)))


def test_resolve_interpret():
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    # this container is CPU-only: the platform default interprets
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
