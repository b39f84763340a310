"""The trace reduction and the readers built on it, pinned on a small
hand-made trace (``testdata/synthetic.xspace.txt``) whose layout is
that of a TPU v5e profile, and on a short trace recorded on the chip
(``testdata/lite-int8.poisson.xplane.pb.gz``).

Times (ns) in the trace: window ``bench.trace`` [0, 10000]; programs
``jit_fwd`` [1000, 4000], [6000, 9000], [9500, 11500] and
``jit_dynamic_slice`` [4500, 4600]; inside the first ``jit_fwd`` a
``while`` [1000, 4000] holding ``fusion`` [1100, 1600], an int8 kernel
[2000, 2500] and an fp32 kernel [2600, 2800]; inside the second a
``while`` [6000, 9000] holding an int8 kernel [6100, 6600]; host spans
pump [900, 4100], submit [4100, 4400], wait [4400, 6000], pump
[6000, 9000], wait [9000, 9500].
"""
import pathlib

import pytest

import harness
import run
import trace_reduce
import work

HERE = pathlib.Path(__file__).resolve().parent
NS = 1e-9


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    text = (HERE / "testdata" / "synthetic.xspace.txt").read_text()
    return trace_reduce.reduce(ProfileData.from_text_proto(text))


def test_window_and_busy_union(red):
    assert red.window == (0.0, pytest.approx(10000 * NS))
    # [1000,4000] + [4500,4600] + [6000,9000] + [9500,10000] (clipped)
    assert red.busy_s[0] == pytest.approx(6600 * NS)
    assert red.mean_busy_s == pytest.approx(6600 * NS)


def test_programs_inside_the_window(red):
    assert trace_reduce.program_time(red, ("jit_fwd",), 0) == (
        2, pytest.approx(6000 * NS))
    assert trace_reduce.program_time(red, ("jit_dynamic",), 0) == (
        1, pytest.approx(100 * NS))


def test_kernel_time_by_name(red):
    assert red.kernels_s[0] == {
        "int8_matmul_pallas": pytest.approx(1000 * NS),
        "fused_linear_pallas": pytest.approx(200 * NS)}


def test_self_time_of_nested_ops(red):
    ops = dict(red.top_ops)
    assert ops["while.1"] == pytest.approx((3000 - 1200 + 3000 - 500) * NS)
    assert ops["int8_matmul_pallas.3"] == pytest.approx(1000 * NS)
    assert ops["fusion.2"] == pytest.approx(500 * NS)
    assert [name for name, _ in red.top_ops][0] == "while.1"


def test_idle_gaps_labelled_by_host_span(red):
    assert red.gaps[:2] == [("bench.wait", pytest.approx(1400 * NS)),
                            ("none", pytest.approx(1000 * NS))]
    assert sorted(g[0] for g in red.gaps[2:]) == ["bench.submit",
                                                  "bench.wait"]
    assert sum(g[1] for g in red.gaps) == pytest.approx(3400 * NS)


def view(red, config="pointmlp-elite", requests=16):
    c = harness.load_config(config)
    window = harness.Window(0.0, 1.0, [], True, {}, {}, 0)
    return run.RunView(
        cell=None, window=window, setup_s=0.0, gave_up=0.0,
        device_kind="TPU v5 lite", configs={"": c}, device_tenant={0: ""},
        lanes_per_device={"": 8}, trace=red,
        trace_counts={"start": {"": (0, 0)}, "end": {"": (requests, 2)},
                      "stream_start": {"frames": 0, "hits": 0, "misses": 0},
                      "stream_end": {"frames": 0, "hits": 0, "misses": 0}})


def test_readers_on_the_trace(red):
    v = view(red)
    c = v.configs[""]
    assert run.reader("device_idle_share.open")(v) == pytest.approx(34.0)
    assert run.reader("step_mfu.backlog")(v) == pytest.approx(
        100 * 16 * work.cloud_flops(c) / (6600 * NS * 197e12))
    assert run.reader("cbr_roofline.backlog")(v) == pytest.approx(
        100 * 2 * 8 * work.cbr_bound_s(c, "TPU v5 lite") / (1200 * NS))


def test_readers_find_nothing_without_a_trace(red):
    v = view(red)
    v.trace = None
    for name in ("device_idle_share.open", "step_mfu.open",
                 "cbr_roofline.open"):
        assert run.reader(name)(v) is None


@pytest.fixture(scope="module")
def chip():
    """0.1 s of ``lite-int8.poisson`` recorded on a TPU v5e
    (``record_trace.py lite-int8.poisson 5151``)."""
    path = HERE / "testdata" / "lite-int8.poisson.xplane.pb.gz"
    return trace_reduce.reduce(trace_reduce.load(path))


def test_recorded_chip_trace(chip):
    assert chip.window_s == pytest.approx(0.047937945)
    assert chip.busy_s == {0: pytest.approx(0.023181762)}
    assert {k: len(v) for k, v in chip.programs[0].items()} == {
        "jit_copy": 19, "jit_fwd": 3, "jit_dynamic_slice": 16}
    assert trace_reduce.program_time(chip, ("jit_fwd",), 0) == (
        3, pytest.approx(0.023161669))
    assert chip.kernels_s[0]["int8_matmul_pallas"] == pytest.approx(
        0.001617023)
    assert chip.top_ops[0] == ("fusion.267", pytest.approx(0.008730654))
    assert [g[0] for g in chip.gaps] == ["bench.pump"] * 10
    assert chip.gaps[0][1] == pytest.approx(0.001582474)


def test_readers_on_the_chip_trace(chip):
    v = view(chip, config="pointmlp-lite")
    assert run.reader("device_idle_share.open")(v) == pytest.approx(
        51.64214486040222)
    assert run.reader("cbr_roofline.open")(v) == pytest.approx(
        35.14195043888819)
