"""PointMLP part segmentation (``head="partseg"``) on the served path.

The published decoder and head, at a tiny size on the CPU: answers of
``FrozenPipeline.infer`` and ``AsyncPointCloudEngine`` against the plain
reference of the benchmark's ``pointmlp-partseg`` configuration
(``benchmarks/chip/partseg_reference.py``, loaded by file path: it
imports nothing of the program), the 3-NN interpolation against a numpy
brute force, answers independent of how the queue is dispatched and of
``data_shards``, the two-array request's errors, and the cost model at
the published widths.
"""
import importlib.util
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import build
from repro.api import plan as stage_plan
from repro.api.spec import PipelineSpec
from repro.core import knn as knn_core
from repro.core import sampling
from repro.models import pointmlp as PM
from repro.serve.async_engine import AsyncPointCloudEngine
from repro.serve.pointcloud import PointCloudEngine

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
SEED = 2 ** 31 + 11

#: The published configuration's topology cut to a CPU size: four
#: stages each keeping a quarter of the points (256 -> 64, 16, 4, 1),
#: xyz carried into the grouping, a four-level decoder (its first level
#: interpolates from the one coarsest point).
TINY = {"name": "tiny-partseg", "n_points": 256, "n_classes": 6,
        "n_categories": 4, "in_channels": 6, "embed_dim": 8,
        "k_neighbors": 4, "stage_reduction": 4,
        "stage_expansion": [2, 2, 2, 2], "pre_blocks": [1, 1, 1, 1],
        "pos_blocks": [1, 1, 1, 1], "res_expansion": 1.0, "use_xyz": True,
        "affine_mode": "affine", "decoder": [[16, 1], [16, 1], [8, 1],
                                             [8, 1]],
        "context_dims": [8, 8], "head": "partseg", "sampler": "fps",
        "backend": "ref", "precision": "fp32"}

#: Widest |served - reference| over every element of a request's
#: log-probabilities.  Both sides are float32 and take the same
#: decisions; they differ only in the order of their float32 sums
#: (lane-mapped XLA dots against vmapped jnp.matmul), which moves these
#: O(1) answers by a few 1e-6.  The same reference with its matmuls in
#: the three-pass bfloat16 product ("high") lands further off than
#: this, which ``test_tolerance_is_under_lower_precision`` checks.
TOL = 1.5e-5


def spec_of(c, **over):
    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in c.items() if k in PipelineSpec.__dataclass_fields__}
    fields.update(over)
    return PipelineSpec(**fields).serving()


@pytest.fixture(scope="module")
def ref():
    """The benchmark's part-segmentation reference module."""
    if str(CHIP) not in sys.path:
        sys.path.append(str(CHIP))
    spec = importlib.util.spec_from_file_location(
        "partseg_reference_under_test", CHIP / "partseg_reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(SEED & 0xFFFFFFFF)


@pytest.fixture(scope="module")
def pipe(key):
    spec = spec_of(TINY)
    init = jax.jit(PM.pointmlp_init, static_argnums=1)
    return build(spec, init(key, spec.to_model_config()))


@pytest.fixture(scope="module")
def pool(ref):
    points, category = ref.make_pool(jax.random.PRNGKey(3), TINY, 6)
    return np.asarray(points), np.asarray(category)


@pytest.fixture(scope="module")
def want(ref, key, pool):
    answers, _ = ref.forward(ref.deploy_params(key, TINY), TINY, pool,
                             lfsr_seed=0, mode="highest")
    return answers


def widest(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


def infer(pipe, points, category):
    state = sampling.seed_streams(SEED, len(points))
    out, _ = pipe.infer((jnp.asarray(points), jnp.asarray(category)), state)
    return np.asarray(out)


def test_infer_matches_reference(pipe, pool, want):
    got = infer(pipe, *pool)
    assert got.shape == (6, 256, 6)
    assert widest(got, want) <= TOL
    np.testing.assert_allclose(np.exp(got).sum(-1), 1.0, rtol=1e-5)


def test_async_engine_matches_reference(pipe, pool, want):
    eng = AsyncPointCloudEngine(pipe, max_batch=4, seed=SEED)
    futs = [eng.submit(p, c) for p, c in zip(*pool)]
    eng.flush()
    got = np.stack([f.result() for f in futs])
    assert widest(got, want) <= TOL


def test_tolerance_is_under_lower_precision(ref, key, pool, want):
    high, _ = ref.forward(ref.deploy_params(key, TINY), TINY, pool,
                          lfsr_seed=0, mode="high")
    assert widest(high, want) > TOL


def test_interpolate_matches_brute_force():
    """3-NN inverse squared distance weights, with fine points that are
    their own coarse source (weight ~1 on themselves, no NaN)."""
    rng = np.random.default_rng(0)
    fine = rng.normal(size=(1, 40, 3)).astype(np.float32)
    coarse = fine[:, ::4]                      # every 4th is a source
    feats = rng.normal(size=(1, 10, 5)).astype(np.float32)
    got = np.asarray(knn_core.interpolate(jnp.asarray(fine),
                                          jnp.asarray(coarse),
                                          jnp.asarray(feats)))
    want = np.empty((40, 5))
    for i, f in enumerate(fine[0].astype(np.float64)):
        d = np.sum((coarse[0] - f) ** 2, -1)
        near = np.argsort(d)[:3]
        w = 1.0 / (d[near] + knn_core.INTERP_EPS)
        want[i] = (w / w.sum()) @ feats[0, near]
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[0, ::4], feats[0], rtol=1e-6, atol=1e-6)


def test_interpolate_takes_what_the_coarse_level_has():
    """A coarse level of fewer than 3 points gives every one of them."""
    fine = jnp.asarray(np.random.default_rng(1).normal(size=(1, 6, 3)),
                       jnp.float32)
    feats = jnp.ones((1, 1, 4))
    got = knn_core.interpolate(fine, fine[:, :1], feats)
    np.testing.assert_allclose(np.asarray(got), 1.0, rtol=1e-6)


@pytest.mark.parametrize("max_batch,pumps", [(4, [1, 3, 2]), (2, [2, 2, 2]),
                                             (8, [6])])
def test_answers_independent_of_dispatch(pipe, pool, max_batch, pumps):
    """However the queue is split into dispatches, every request's
    answer is bit-identical to a solo run (pad lanes cannot leak)."""
    solo = np.stack([infer(pipe, pool[0][i:i + 1], pool[1][i:i + 1])[0]
                     for i in range(6)])
    eng = AsyncPointCloudEngine(pipe, max_batch=max_batch, seed=SEED)
    futs, i = [], 0
    for n in pumps:
        futs += [eng.submit(pool[0][j], pool[1][j]) for j in range(i, i + n)]
        i += n
        eng.flush()
    got = np.stack([f.result() for f in futs])
    # A solo run pads to its own batch of 1; the engine pads to
    # max_batch: lanes are mapped one at a time, so both agree bitwise.
    np.testing.assert_array_equal(got, solo)


def test_sync_engine_classify(pipe, pool):
    eng = PointCloudEngine(pipe.params, spec_of(TINY, fuse=False),
                           max_batch=4, seed=SEED)
    got = np.asarray(eng.classify(*pool))
    np.testing.assert_array_equal(got[:4], infer(pipe, pool[0][:4],
                                                 pool[1][:4]))
    assert eng.predict(*pool).shape == (6, 256)


def test_data_shards_one_and_two_agree():
    """A fresh interpreter with two forced CPU devices: the dispatch
    split over two devices, each request's arrays scattered by lane,
    equals the one-device dispatch bit for bit."""
    import repro
    src = str(pathlib.Path(list(repro.__path__)[0]).resolve().parent)
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    code = textwrap.dedent(f"""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.api import build
        from repro.api.spec import PipelineSpec
        from repro.models import pointmlp as PM
        from repro.serve.async_engine import AsyncPointCloudEngine
        assert jax.device_count() == 2, jax.device_count()
        c = {TINY!r}
        f = {{k: tuple(tuple(x) if isinstance(x, list) else x for x in v)
              if isinstance(v, list) else v for k, v in c.items()
              if k in PipelineSpec.__dataclass_fields__}}
        spec = PipelineSpec(**f).serving()
        cfg = spec.to_model_config()
        rng = np.random.default_rng(0)

        def draw(a):   # conv weights N(0, 1/c_in); BN and affine near 1
            if a.ndim == 2:
                return (rng.normal(size=a.shape)
                        / np.sqrt(a.shape[0])).astype(a.dtype)
            return rng.uniform(0.5, 1.5, a.shape).astype(a.dtype)

        params = jax.tree_util.tree_map(draw, jax.eval_shape(
            lambda: PM.pointmlp_init(jax.random.PRNGKey(0), cfg)))
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        pts = np.asarray(jax.random.normal(k1, (4, 256, 6)))
        cat = np.asarray(jax.random.randint(k2, (4,), 0, 4))
        outs = []
        for shards in (1, 2):
            eng = AsyncPointCloudEngine(
                build(spec.replace(data_shards=shards), params),
                max_batch=4, seed=3)
            futs = [eng.submit(p, k) for p, k in zip(pts, cat)]
            eng.flush()
            outs.append(np.stack([f.result() for f in futs]))
        assert np.isfinite(outs[0]).all() and np.ptp(outs[0]) > 0.1
        np.testing.assert_array_equal(outs[0], outs[1])
    """)
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=300)


@pytest.mark.parametrize("args,match", [
    (lambda p, c: (p, None), "request 0: .*category is missing"),
    (lambda p, c: (p[:, :3], c), r"request 0: .*\[N=256, 6\]"),
    (lambda p, c: (p, 4), r"request 0: .*\[0, 4\)"),
    (lambda p, c: (p, np.array([c, c])), "request 0: .*one integer"),
    (lambda p, c: (p, 1.0), "request 0: .*one integer"),
])
def test_bad_request_names_it(pipe, pool, args, match):
    eng = AsyncPointCloudEngine(pipe, max_batch=4, seed=SEED)
    with pytest.raises(ValueError, match=match):
        eng.submit(*args(pool[0][0], pool[1][0]))
    assert eng.depth == 0


def test_bad_request_after_good_ones_names_it(pipe, pool):
    eng = AsyncPointCloudEngine(pipe, max_batch=4, seed=SEED)
    eng.submit(pool[0][0], pool[1][0])
    eng.submit(pool[0][1], pool[1][1])
    with pytest.raises(ValueError, match="request 2: .*missing"):
        eng.submit(pool[0][2])


def test_sync_engine_names_bad_category(pipe, pool):
    eng = PointCloudEngine(pipe.params, spec_of(TINY, fuse=False),
                           max_batch=4)
    cats = pool[1].copy()
    cats[3] = 9
    with pytest.raises(ValueError, match="request 3"):
        eng.classify(pool[0], cats)
    with pytest.raises(ValueError, match="categories are missing"):
        eng.classify(pool[0])


def test_one_array_pipeline_refuses_a_category():
    from repro.api import lite_spec
    spec = lite_spec(4).replace(n_points=64, embed_dim=8, k_neighbors=4,
                                precision="fp32", backend="ref").serving()
    params = jax.tree_util.tree_map(
        lambda a: jnp.ones(a.shape, a.dtype),
        jax.eval_shape(lambda: PM.pointmlp_init(jax.random.PRNGKey(0),
                                                spec.to_model_config())))
    eng = AsyncPointCloudEngine(build(spec, params), max_batch=2)
    with pytest.raises(ValueError, match="request 0: .*cloud alone"):
        eng.submit(np.zeros((64, 3), np.float32), 1)


def test_stream_spec_refuses_partseg():
    spec = spec_of(TINY).replace(stream=True, stream_drift_threshold=0.1)
    with pytest.raises(ValueError, match="RPA018.*category"):
        spec.validate()


@pytest.mark.parametrize("over,match", [
    (dict(decoder=((16, 1),)), "one decoder level per encoder stage"),
    (dict(head="cls"), "decoder levels are lowered only"),
    (dict(stage_reduction=8), "leaves no sample"),
    (dict(in_channels=2), "in_channels"),
])
def test_spec_refuses_bad_topology(over, match):
    with pytest.raises(ValueError, match=match):
        spec_of(TINY, **over)


def test_fused_group_refuses_use_xyz():
    spec = spec_of(TINY, fused_group="grouped_transfer")
    with pytest.raises(ValueError, match="RPA019"):
        spec.validate()


def test_cost_model_counts_the_published_model(ref):
    """At the published widths the program's own FLOP count, conv rows
    and distance rows apart, equals what the reference module counts:
    12.4 GFLOP of convs per cloud."""
    import json
    c = json.loads((CHIP / "configs" / "pointmlp-partseg.json").read_text())
    spec = spec_of(c)
    cfg = spec.to_model_config()
    fl = PM.pointmlp_flops_breakdown(cfg)
    mapping = {k for k in fl if k.endswith((".group", ".interp"))}
    convs = sum(v for k, v in fl.items() if k not in mapping)
    assert convs == sum(layer.flops for layer in ref.cbr_layers(c))
    assert sum(fl[k] for k in mapping) == ref.knn_flops(c) + ref.nn3_flops(c)
    assert convs == pytest.approx(12.4e9, rel=0.01)
    rows = {r["op"]: r["flops"]
            for r in stage_plan.lower(spec, cfg).cost_breakdown(cfg)}
    assert rows == fl
    assert PM.pointmlp_flops(cfg) == convs + sum(fl[k] for k in mapping)
