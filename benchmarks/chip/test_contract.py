"""Everything model-specific comes from the module a configuration's
``reference`` key names, and the harness takes any answer shape.

The PointMLP configurations read, at the ``TINY`` sizes, exactly what
they read when the harness called ``reference.py`` and ``decisions.py``
itself (numbers pinned from that harness).  A test-only configuration
under ``testdata/``, with a two-array payload and per-point answers, is
checked through the harness as it stands.
"""
import functools
import hashlib
import json

import numpy as np
import pytest

import harness
import run
import trace_reduce
import work
from conftest import TINY
from test_trace_reduce import view

TESTDATA = harness.HERE / "testdata"
SEED = 2 ** 31 + 11

#: Read at the TINY sizes by the harness before configurations brought
#: their own modules: pools of 6 clouds, the reference's accepted
#: answers for each (own forward, then every open decision path), a
#: digest of the decision paths, and the work counts of one cloud.
PINNED = {
    "pointmlp-elite": {
        "answer_sums": [-3.411381784360856, -3.6163447904400527,
                        -2.6236227967310697, -5.017960958182812,
                        -1.4324144199490547, 0.22439324110746384],
        "answer_head": [-2.2292842864990234, 1.3401859998703003,
                        2.0769271850585938, 1.6584839820861816],
        "paths_sha256": "8ed38f45dc47d78901830afe59508319fe5992a8a712f43c"
                        "65a94b8eea9fdddb",
        "cbr_bound_s": 1.2177582417582417e-06,
    },
    "pointmlp-lite": {
        "answer_sums": [-0.8056317064911127, -1.3238564282655716,
                        -4.687819009646773, -4.5819256217218935,
                        -1.5239831507205963, -2.7403209136537043],
        "answer_head": [-2.676441192626953, 1.3938195705413818,
                        3.3372342586517334, 2.4257640838623047],
        "paths_sha256": "5c47811e0c301fd19678de0048a817486615ae856d561c02"
                        "fdb31cc1483f7882",
        "cbr_bound_s": 3.941098901098901e-07,
    },
}
POOL_SUM, POOL_SUMSQ = -9.116483852267265e-07, 193.62535073604766
POOL_HEAD = [0.2469969093799591, 0.24769820272922516, -0.8279861211776733]
CLOUD_FLOPS, CLOUD_FLOPS_REPLAYED = 1620928, 1604608
#: At the published sizes, by the same harness: ``step_mfu`` on the
#: recorded chip trace, and the CBR roofline time of one cloud.
PINNED_FULL = {"pointmlp-elite": (0.6284618227911416, 7.747258608058609e-05),
               "pointmlp-lite": (0.1573796428820371, 2.3677225885225886e-05)}


def served_for(c, pool, seed=SEED):
    """Just enough of a run's set-up for the reference check."""
    return harness.Served("engine", None, {}, {"": c}, {"": pool}, None,
                          seed & 0xFFFFFFFF, c["max_batch"])


def requests(n):
    return {"": [harness.Rec(0.0, cloud=i) for i in range(n)]}


@pytest.mark.parametrize("config", sorted(PINNED))
def test_pointmlp_reads_what_it_read_before(config):
    pin = PINNED[config]
    c = harness.load_config(config)
    c.update(TINY)
    pool = harness.make_pool(
        c, harness.prng_key(SEED, harness.SALT_CLOUDS), 6)
    assert [a.shape for a in pool] == [(6, 64, 3)]
    xyz = pool[0].astype(np.float64)
    np.testing.assert_allclose(xyz.sum(), POOL_SUM, atol=1e-9)
    np.testing.assert_allclose((xyz ** 2).sum(), POOL_SUMSQ, rtol=1e-9)
    np.testing.assert_array_equal(pool[0][0, 0], np.float32(POOL_HEAD))

    want = harness.reference_answers(served_for(c, pool), requests(6),
                                     SEED)[""]
    assert [a.shape for a in want] == [(2, 40)] * 6
    sums = [a.astype(np.float64).sum(-1) for a in want]
    np.testing.assert_allclose(sums, [[s, s] for s in pin["answer_sums"]],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(want[0][0, :4], pin["answer_head"],
                               rtol=1e-6)

    mode = harness.reference_mode(c)
    paths = c[work.MODULE].decision_paths(c, pool, SEED & 0xFFFFFFFF,
                                          harness.cross_rounding(mode))
    digest = hashlib.sha256()
    for per_cloud in paths:
        for idx, nbr in per_cloud:
            for leaf in idx + nbr:
                digest.update(np.ascontiguousarray(leaf, np.int32).tobytes())
    assert digest.hexdigest() == pin["paths_sha256"]

    assert work.cloud_flops(c) == CLOUD_FLOPS
    assert work.cloud_flops(c, replayed=True) == CLOUD_FLOPS_REPLAYED
    assert work.cbr_bound_s(c, "TPU v5 lite") == pin["cbr_bound_s"]


@pytest.mark.parametrize("config", sorted(PINNED_FULL))
def test_trace_readers_read_what_they_read_before(config):
    mfu, bound_s = PINNED_FULL[config]
    chip = trace_reduce.reduce(trace_reduce.load(
        TESTDATA / "lite-int8.poisson.xplane.pb.gz"))
    v = view(chip, config=config)
    assert run.reader("step_mfu.open")(v) == pytest.approx(mfu, rel=1e-12)
    assert work.cbr_bound_s(v.configs[""], "TPU v5 lite") == bound_s


def test_a_two_input_per_point_configuration_is_checked_end_to_end():
    """Points with normals and a category in, per-point answers out:
    loaded, pooled, answered by its reference, gapped and counted with
    no change to the harness."""
    c = harness.load_config("toy-partseg", TESTDATA)
    assert harness.pool_shapes(c) == [(1, 32, 6), (1,)]
    pool = harness.make_pool(
        c, harness.prng_key(SEED, harness.SALT_CLOUDS), 4)
    assert harness.payload(pool, 2)[1] == pool[1][2]

    want = harness.reference_answers(served_for(c, pool), requests(4),
                                     SEED)[""]
    assert [a.shape for a in want] == [(2, 32, 5)] * 4
    got = np.stack([a[0] for a in want])
    gaps = harness.request_gaps(got, want)
    np.testing.assert_array_equal(gaps, 0.0)

    gap = c["limits"]["request_gap"]
    got[1, 7, 3] += 4 * gap                 # one point of one request
    gaps = harness.request_gaps(got, want)
    assert gaps[1] == pytest.approx(4 * gap, rel=1e-3)
    assert list(gaps[[0, 2, 3]]) == [0.0, 0.0, 0.0]
    assert harness.off_share(gaps, gap) == 25.0

    assert work.cloud_flops(c) == 2 * 32 * (12 + 3) * 5 + 2 * 32 * 4 * 3
    assert work.cloud_flops(c, replayed=True) == 2 * 32 * 15 * 5


@pytest.mark.parametrize("off_at, want", [(None, 0.0), ((5, 2), 0.5)])
def test_gap_is_the_widest_over_every_axis_of_an_answer(off_at, want):
    """A [N, C] answer off at one point reads that point's gap (the
    minimum over points used to read 0), against the nearest accepted
    answer."""
    accepted = np.zeros((2, 8, 4), np.float32)
    accepted[1] += 3.0                      # a far second candidate
    got = accepted[0].copy()
    if off_at:
        got[off_at] += 0.5
    gaps = harness.request_gaps(got[None], [accepted])
    assert gaps.tolist() == [want]


def test_a_module_that_lacks_a_contract_function_fails_at_load(tmp_path):
    (tmp_path / "partial.json").write_text(json.dumps(
        {"name": "partial", "reference": "testdata/lacks_decision_paths.py"}))
    with pytest.raises(AttributeError,
                       match="'partial'.*lacks decision_paths"):
        harness.load_config("partial", tmp_path)


@pytest.mark.parametrize("file", ["../../src/repro/__init__.py",
                                  "configs/pointmlp-elite.json"])
def test_a_reference_outside_the_benchmark_is_refused(tmp_path, file):
    (tmp_path / "outside.json").write_text(json.dumps(
        {"name": "outside", "reference": file}))
    with pytest.raises(ValueError, match="'outside'.*not a Python file"):
        harness.load_config("outside", tmp_path)


def test_a_stream_cell_needs_an_xyz_pool(monkeypatch):
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    monkeypatch.setattr(harness, "load_config", functools.partial(
        harness.load_config, directory=TESTDATA))
    with pytest.raises(ValueError, match="'toy-partseg' cannot serve stream"):
        harness.make_cell("toy.stream", 1, "toy-partseg",
                          "elite-fp32.stream", bench)
    cell = harness.make_cell("toy.backlog", 1, "toy-partseg",
                             "elite-fp32.backlog", bench)
    assert cell.config["name"] == "toy-partseg"
