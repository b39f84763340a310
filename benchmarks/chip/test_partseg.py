"""The ``pointmlp-partseg`` configuration through the harness as it
stands: a two-array request served by the program at a tiny size on the
CPU (kernels interpreted), checked against its own reference module;
its decision paths flip a decoder 3-NN choice left open by float32; and
its work count at the published widths.
"""
import numpy as np
import pytest

import harness
import work

SEED = 2 ** 31 + 11
#: The published configuration cut to a CPU size: the same stage
#: reduction (256 -> 64, 16, 4, 1), xyz in the grouping and four
#: decoder levels.
TINY = {"n_points": 256, "embed_dim": 8, "k_neighbors": 4,
        "pre_blocks": [1, 1, 1, 1], "pos_blocks": [1, 1, 1, 1],
        "decoder": [[16, 1], [16, 1], [8, 1], [8, 1]],
        "context_dims": [8, 8], "n_classes": 6, "n_categories": 4,
        "backend": "pallas_interpret"}


def tiny_config():
    c = harness.load_config("pointmlp-partseg")
    c.update(TINY)
    return c


def test_served_answers_are_within_the_gap_of_the_reference():
    cell = harness.load_cell("partseg-fp32.backlog")
    cell.config.update(TINY)
    cell.traffic["pool"] = 8
    with harness.matmul_precision(cell.config):
        served = harness.build_served(cell, SEED, 1.0)
        eng = served.engines[""]
        pool = served.pools[""]
        assert [a.shape for a in pool] == [(8, 256, 6), (8,)]
        recs = [harness.Rec(0.0, cloud=i) for i in range(8)]
        futs = [eng.submit(*harness.payload(pool, r.cloud)) for r in recs]
        eng.flush()
    for r, f in zip(recs, futs):
        r.out = f.result()
    want = harness.reference_answers(served, {"": recs}, SEED)[""]
    assert all(a.shape[1:] == (256, 6) for a in want)
    gaps = harness.request_gaps(np.stack([r.out for r in recs]), want)
    assert np.all(gaps <= cell.config["limits"]["request_gap"]), gaps
    assert harness.off_share(gaps, cell.config["limits"]["request_gap"]) == 0


def test_decision_paths_flip_a_planted_3nn_near_tie():
    """Move one point onto the bisector of its 3rd and 4th nearest
    stage-1 samples: the decoder's last level then has an open choice,
    and the paths hold the exact one and the one that takes the 4th."""
    c = tiny_config()
    mod = c[work.MODULE]
    pts = np.asarray(mod.make_pool(
        harness.prng_key(SEED, harness.SALT_CLOUDS), c, 1)[0][0])
    xyz = pts[:, :3].astype(np.float64)
    exact = mod.decision_paths(c, (pts[None],), 0, lambda a: a)[0][0]
    samples = exact[0][0]
    i = next(j for j in range(len(xyz)) if j not in set(samples))
    coarse = xyz[samples]
    order = np.argsort(np.sum((coarse - xyz[i]) ** 2, -1))
    c3, c4 = coarse[order[2]], coarse[order[3]]
    u = (c4 - c3) / np.linalg.norm(c4 - c3)
    gap = np.sum((c4 - xyz[i]) ** 2) - np.sum((c3 - xyz[i]) ** 2)
    xyz[i] += u * gap / (2 * np.linalg.norm(c4 - c3))
    pts = pts.copy()
    pts[i, :3] = xyz[i]

    paths = mod.decision_paths(c, (pts[None],), 0, lambda a: a)[0]
    first = paths[0]
    for a, b in zip(first[0] + first[1], exact[0] + exact[1]):
        np.testing.assert_array_equal(a, b)       # the encoder is as it was
    flips = [p for p in paths[1:]
             if all(np.array_equal(a, b) for a, b in
                    zip(p[0] + p[1] + p[2][:3], first[0] + first[1]
                        + first[2][:3]))]
    rows = [np.nonzero(np.any(p[2][3] != first[2][3], axis=1))[0]
            for p in flips]
    assert any(list(r) == [i] for r in rows), rows
    swapped = {int(samples[order[2]]), int(samples[order[3]])}
    for p, r in zip(flips, rows):
        if list(r) == [i]:
            changed = set(p[2][3][i]) ^ set(first[2][3][i])
            assert {int(samples[j]) for j in changed} == swapped


def test_work_of_one_cloud_at_the_published_widths():
    c = harness.load_config("pointmlp-partseg")
    mod = c[work.MODULE]
    convs = sum(layer.flops for layer in mod.cbr_layers(c))
    assert convs == 12_415_191_040
    assert convs == pytest.approx(12.4e9, rel=0.01)
    assert work.cloud_flops(c) == convs + mod.mapping_flops(c)
    assert mod.mapping_flops(c) == (mod.fps_flops(c) + mod.knn_flops(c)
                                    + mod.nn3_flops(c))
    assert work.cbr_bound_s(c, "TPU v5 lite") > convs / 197e12
