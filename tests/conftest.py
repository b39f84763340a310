"""Shared test scaffolding.

* Path bootstrap: makes ``repro`` (src layout) and the ``benchmarks``
  helpers importable whether the suite runs via ``pip install -e .`` or
  the bare checkout (tier-1: ``PYTHONPATH=src python -m pytest``).
* ``prng_seed`` / ``rng_key``: the session-fixed PRNG contract — every
  test derives randomness from one seed so failures reproduce exactly.
* ``bench_spec``: the served spec of a chip benchmark configuration.
"""
from __future__ import annotations

import pathlib
import sys

import pytest

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for module, path in (("repro", _ROOT / "src"), ("benchmarks", _ROOT)):
    try:
        __import__(module)
    except ImportError:
        sys.path.insert(0, str(path))


PRNG_SEED = 0


@pytest.fixture(scope="session")
def prng_seed() -> int:
    """The one seed all test randomness derives from."""
    return PRNG_SEED


@pytest.fixture()
def rng_key(prng_seed):
    import jax
    return jax.random.PRNGKey(prng_seed)


@pytest.fixture(scope="session")
def bench_spec():
    """``bench_spec(name)``: the served :class:`PipelineSpec` of the chip
    benchmark's ``pointmlp-<name>`` configuration
    (``benchmarks/chip/configs``), its lists read as tuples."""
    import json

    from repro.api.spec import PipelineSpec

    def make(name: str):
        path = (_ROOT / "benchmarks" / "chip" / "configs"
                / f"pointmlp-{name}.json")
        c = json.loads(path.read_text())
        fields = {k: tuple(tuple(x) if isinstance(x, list) else x
                           for x in v) if isinstance(v, list) else v
                  for k, v in c.items()
                  if k in PipelineSpec.__dataclass_fields__}
        return PipelineSpec(**fields).serving()
    return make
