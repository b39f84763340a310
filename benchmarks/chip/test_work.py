"""Work counts and peaks: the benchmark's own FLOP count per cloud equals
the program's analytic count at the spec widths."""
import pytest

import harness
import work


@pytest.mark.parametrize("config, flops", [("pointmlp-elite", 1_793_789_952),
                                           ("pointmlp-lite", 896_122_880)])
def test_cloud_flops_match_program(config, flops):
    from repro.api import build
    c = harness.load_config(config)
    spec = harness.pipeline_spec(c, {"policy": "fixed"})
    params = harness.init_weights(spec, 0)
    assert work.cloud_flops(c) == flops
    assert build(spec, params).flops() == flops


def test_replayed_frame_skips_knn_distances():
    c = harness.load_config("pointmlp-elite")
    assert (work.cloud_flops(c) - work.cloud_flops(c, replayed=True)
            == 2 * 3 * (512 * 1024 + 256 * 512 + 128 * 256 + 64 * 128))


def test_layer_bytes_by_precision():
    layer = work.Layer("x", m=4, k=8, n=16)
    assert layer.flops == 2 * 4 * 8 * 16
    assert layer.bytes("fp32") == 4 * (4 * 8 + 8 * 16) + 4 * 16 + 4 * 4 * 16
    assert layer.bytes("int8") == (4 * 8 + 8 * 16) + 4 * 16 + 4 * 4 * 16


def test_peaks_by_device_kind():
    assert work.compute_peak("TPU v5 lite", "fp32") == 197e12
    assert work.compute_peak("TPU v5 lite", "int8") == 393e12
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v9000")


def test_cbr_bound_is_positive_and_below_a_millisecond():
    for name in ("pointmlp-elite", "pointmlp-lite"):
        c = harness.load_config(name)
        assert 0 < work.cbr_bound_s(c, "TPU v5 lite") < 1e-3
