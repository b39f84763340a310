"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a topology that is
only described, which catches what interpret mode cannot (a primitive
Mosaic has no lowering for, an unaligned block, too much VMEM).  Every
kernel is compiled with ``interpret=False`` at the shapes a real-width
plan runs (``repro.api.plan.plan_shapes``), and every compiled
program must call a Mosaic kernel (``tpu_custom_call``).  Code that asks
``jax.default_backend()`` still sees the CPU here, so the kernels are
called directly with ``interpret=False`` rather than through a pipeline
that would resolve to interpret mode.

The topology is described inside a module fixture, never at import: the
TPU library may be loaded by one process at a time, and every test
worker imports this file.  Keep these tests in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import build, elite_spec, lite_spec
from repro.api.spec import PipelineSpec
from repro.api.plan import plan_shapes
from repro.kernels.fps import fps_walk_pallas
from repro.kernels.fused_linear import fused_linear_pallas
from repro.kernels.grouped_transfer import grouped_transfer_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas
from repro.kernels.knn import knn_pallas
from repro.models import pointmlp as PM


def _partseg_spec():
    """The benchmark's published part-segmentation configuration."""
    import json
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
            / "chip" / "configs" / "pointmlp-partseg.json")
    c = json.loads(path.read_text())
    fields = {k: tuple(tuple(x) if isinstance(x, list) else x for x in v)
              if isinstance(v, list) else v
              for k, v in c.items() if k in PipelineSpec.__dataclass_fields__}
    return PipelineSpec(**fields).serving()


SPECS = {"lite": lite_spec(40), "elite": elite_spec(40),
         "partseg": _partseg_spec()}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile
    cache off (an entry compiled for a described chip cannot be read
    back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("variant", sorted(SPECS))
def test_fused_linear_compiles(one_chip, variant):
    m, k, n = plan_shapes(SPECS[variant])["fused_linear"]
    _compiled_text(
        lambda x, w, b: fused_linear_pallas(x, w, b, interpret=False),
        _sds(one_chip, (m, k)), _sds(one_chip, (k, n)),
        _sds(one_chip, (n,)))


@pytest.mark.parametrize("variant", sorted(SPECS))
def test_int8_matmul_compiles(one_chip, variant):
    m, k, n = plan_shapes(SPECS[variant])["int8_matmul"]
    _compiled_text(
        lambda x, w, s: int8_matmul_pallas(x, w, s, out_dtype=jnp.float32,
                                           interpret=False),
        _sds(one_chip, (m, k), jnp.int8), _sds(one_chip, (k, n), jnp.int8),
        _sds(one_chip, (1, n)))


@pytest.mark.parametrize("n,s", [(512, 256), (256, 128), (128, 64)],
                         ids=["stage2", "stage3", "stage4"])
def test_fps_compiles(one_chip, n, s):
    """Elite's stage 2-4 walks through the whole-walk kernel, at a
    serving lane's batch of one."""
    text = _compiled_text(lambda p: fps_walk_pallas(p, s, interpret=False),
                          _sds(one_chip, (1, n, 3)))
    assert "fps_walk" in text


@pytest.mark.parametrize("n,s", [(1024, 512), (2048, 512), (32, 8)],
                         ids=["elite", "partseg", "smallest-stage"])
def test_fps_walk_compiles(one_chip, n, s):
    """The whole-walk kernel the ``fps`` sampler runs on a TPU, at a
    serving lane's batch of one."""
    text = _compiled_text(lambda p: fps_walk_pallas(p, s, interpret=False),
                          _sds(one_chip, (1, n, 3)))
    assert "fps_walk" in text


@pytest.mark.parametrize("variant", sorted(SPECS))
def test_knn_compiles(one_chip, variant):
    s, n, k = plan_shapes(SPECS[variant])["knn"]
    _compiled_text(lambda a, b: knn_pallas(a, b, k, interpret=False),
                   _sds(one_chip, (s, 3)), _sds(one_chip, (n, 3)))


def test_grouped_transfer_still_rejected_by_mosaic(one_chip):
    """The reason lowering refuses ``fused_group="grouped_transfer"`` on
    a TPU (RPA016): Mosaic rejects the kernel's in-kernel gather.  When
    this starts compiling, lift the refusal (the ``tpu-platform`` pass in
    ``repro.analysis.passes``) and turn this into a compile test."""
    n, s, k, c = plan_shapes(SPECS["lite"])["grouped_transfer"]
    with pytest.raises(ValueError, match="Shape mismatch"):
        jax.jit(lambda f, ni, cen, al, be, w, b: grouped_transfer_pallas(
            f, ni, cen, None, al, be, w, b, k=k, interpret=False)).lower(
            _sds(one_chip, (n, c)), _sds(one_chip, (s, k), jnp.int32),
            _sds(one_chip, (s, c)), _sds(one_chip, (1, c)),
            _sds(one_chip, (1, c)), _sds(one_chip, (2 * c, c)),
            _sds(one_chip, (1, c))).compile()


def test_grouped_transfer_refused_by_lowering_on_tpu(monkeypatch):
    from repro.api import plan as SP
    from repro.kernels import tuning
    monkeypatch.setattr(tuning, "on_tpu", lambda: True)
    spec = elite_spec(40).replace(fused_group="grouped_transfer",
                                  backend="pallas")
    with pytest.raises(ValueError, match="RPA016"):
        SP.lower(spec, spec.to_model_config())


@pytest.mark.parametrize("spec", [
    lite_spec(40).replace(backend="pallas").serving(),
    elite_spec(40).replace(backend="pallas").serving(),
], ids=["lite-int8", "elite-fp32"])
def test_serving_forward_compiles(one_chip, spec):
    """The whole jitted serving forward ``chip_smoke.py`` runs, at the
    spec's widths and batch 8, compiles for the chip with its kernels."""
    pipe = build(spec, PM.pointmlp_init(jax.random.PRNGKey(0),
                                        spec.to_model_config()))
    params = jax.tree_util.tree_map(
        lambda a: _sds(one_chip, a.shape, a.dtype), pipe.params)
    text = pipe._fn.lower(
        params, _sds(one_chip, (8, spec.n_points, 3)),
        _sds(one_chip, (8,), jnp.uint32)).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("model", ["elite", "partseg"])
def test_serving_forward_compiles_through_fps_walk(one_chip, monkeypatch,
                                                  model):
    """With the platform probe reporting a TPU, the ``fps`` sampler
    takes the whole-walk kernel: the serving forward of each FPS cell,
    at its published widths and batch 8, compiles with it."""
    from repro.kernels import tuning
    monkeypatch.setattr(tuning, "on_tpu", lambda: True)
    spec = (elite_spec(40).replace(backend="pallas").serving()
            if model == "elite" else SPECS["partseg"])
    pipe = build(spec, PM.pointmlp_init(jax.random.PRNGKey(0),
                                        spec.to_model_config()))
    params = jax.tree_util.tree_map(
        lambda a: _sds(one_chip, a.shape, a.dtype), pipe.params)
    points = _sds(one_chip, (8, spec.n_points, spec.in_channels))
    payload = (points if model == "elite"
               else (points, _sds(one_chip, (8,), jnp.int32)))
    text = pipe._fn.lower(params, payload,
                          _sds(one_chip, (8,), jnp.uint32)).compile().as_text()
    assert "fps_walk" in text


def test_chip_smoke_refuses_without_tpu(capsys):
    """``chip_smoke.py`` never falls back to the CPU: it exits non-zero
    and prints no result line."""
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out
