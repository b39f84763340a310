"""``fps_walk_pallas``: a whole FPS walk as one kernel program per cloud.

The kernel runs interpreted here.  Its indices must equal the reference
walk ``sampling.fps`` bit for bit (start at point 0, ties to the lowest
index), at every shape a pipeline stage gives it; the ``fps`` sampler
must take it on a TPU and the reference walk elsewhere; and a served
forward through either route must give the same answers.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import build, elite_spec
from repro.api.registry import SAMPLERS
from repro.api.spec import PipelineSpec
from repro.core import sampling
from repro.kernels import fps as fps_kernels
from repro.kernels.fps import fps_walk_pallas
from repro.models import pointmlp as PM

KEY = jax.random.PRNGKey(2 ** 31 + 17)


def reference(points, n_samples):
    return np.stack([np.asarray(sampling.fps(p, n_samples)) for p in points])


@pytest.mark.parametrize("n,s", [
    (1024, 512), (512, 128), (257, 32), (32, 8), (40, 40), (64, 8),
    (1024, 128), (2048, 512),
    # padding remainders: N off the (8, 128) tile, S off a tile row
    (100, 50), (1000, 250), (1025, 64),
])
def test_walk_equals_reference(n, s):
    pts = jax.random.normal(jax.random.fold_in(KEY, n * s), (1, n, 3))
    got = fps_walk_pallas(pts, s)
    assert got.shape == (1, s) and got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), reference(pts, s))


@pytest.mark.parametrize("cloud", ["mirrored", "repeated"])
def test_ties_go_to_the_lowest_index(cloud):
    if cloud == "mirrored":
        # points 1 and 2 lie equally far from point 0, as do 3 and 4
        pts = jnp.array([[0., 0., 0.], [1., 0., 0.], [-1., 0., 0.],
                         [0., 2., 0.], [0., -2., 0.]])
        want = [0, 3, 4, 1, 2]
    else:
        # 16 distinct points, each four times: once all 16 are taken,
        # every distance is 0 and each step takes index 0 again
        base = jax.random.normal(KEY, (16, 3))
        pts = jnp.tile(base, (4, 1))
        want = list(np.asarray(sampling.fps(pts, 24)))
        assert want[16:] == [0] * 8
    got = fps_walk_pallas(pts[None], len(want))
    np.testing.assert_array_equal(np.asarray(got)[0], want)
    np.testing.assert_array_equal(np.asarray(got),
                                  reference(pts[None], len(want)))


def test_batch_equals_single_calls():
    pts = jax.random.normal(jax.random.fold_in(KEY, 3), (3, 300, 3))
    got = np.asarray(fps_walk_pallas(pts, 64))
    singles = np.concatenate([np.asarray(fps_walk_pallas(p[None], 64))
                              for p in pts])
    np.testing.assert_array_equal(got, singles)
    np.testing.assert_array_equal(got, reference(pts, 64))


@pytest.fixture
def on_tpu(monkeypatch):
    """Report a TPU to the platform probe; the kernel, which cannot
    compile here, runs interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fps_kernels, "resolve_interpret", lambda _: True)


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_sampler_routes_by_platform(request, monkeypatch, platform):
    if platform == "tpu":
        request.getfixturevalue("on_tpu")
    calls = []

    def spy(name, fn):
        def wrapped(*a, **kw):
            calls.append(name)
            return fn(*a, **kw)
        return wrapped

    monkeypatch.setattr(fps_kernels, "fps_walk_pallas",
                        spy("kernel", fps_kernels.fps_walk_pallas))
    monkeypatch.setattr(sampling, "fps_batched",
                        spy("reference", sampling.fps_batched))
    xyz = jax.random.normal(jax.random.fold_in(KEY, 5), (2, 128, 3))
    state = object()
    idx, out_state = SAMPLERS.get("fps")(xyz, 32, state, True)
    assert calls == ["kernel" if platform == "tpu" else "reference"]
    assert out_state is state
    assert idx.shape == (2, 32) and idx.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(idx), reference(xyz, 32))


#: Part segmentation's published topology at a CPU size (stages keep a
#: quarter of the points: 256 -> 64, 16, 4, 1).
PARTSEG = dict(name="tiny-partseg", n_points=256, n_classes=6,
               n_categories=4, in_channels=6, embed_dim=8, k_neighbors=4,
               stage_reduction=4, stage_expansion=(2, 2, 2, 2),
               pre_blocks=(1, 1, 1, 1), pos_blocks=(1, 1, 1, 1),
               res_expansion=1.0, use_xyz=True, affine_mode="affine",
               decoder=((16, 1), (16, 1), (8, 1), (8, 1)),
               context_dims=(8, 8), head="partseg", sampler="fps",
               backend="ref", precision="fp32")


def tiny(model):
    if model == "elite":
        spec = elite_spec(8).replace(backend="ref", n_points=128,
                                     embed_dim=16, k_neighbors=8)
        points = jax.random.normal(jax.random.fold_in(KEY, 7), (4, 128, 3))
        return spec.serving(), (points,)
    points = jax.random.normal(jax.random.fold_in(KEY, 11), (4, 256, 6))
    category = jnp.array([0, 3, 1, 2], jnp.int32)
    return PipelineSpec(**PARTSEG).serving(), (points, category)


def serve(spec, params, payload):
    pipe = build(spec, params)
    out, _ = pipe.infer(payload if len(payload) > 1 else payload[0],
                        sampling.seed_streams(5, len(payload[0])))
    return np.asarray(out)


@pytest.mark.parametrize("model", ["elite", "partseg"])
def test_forward_through_kernel_equals_reference_route(
        request, monkeypatch, model):
    spec, payload = tiny(model)
    params = PM.pointmlp_init(jax.random.fold_in(KEY, 13),
                              spec.to_model_config())
    want = serve(spec, params, payload)
    request.getfixturevalue("on_tpu")
    walks = []
    kernel = fps_kernels.fps_walk_pallas
    monkeypatch.setattr(fps_kernels, "fps_walk_pallas",
                        lambda xyz, s: walks.append(s) or kernel(xyz, s))
    got = serve(spec, params, payload)
    assert walks == list(spec.to_model_config().stage_samples)
    np.testing.assert_array_equal(got, want)
