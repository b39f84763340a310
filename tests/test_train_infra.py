"""Training infrastructure: checkpoint/restart, grad compression,
optimizers, straggler monitor, data determinism."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.data import lm_data, pointclouds
from repro.train import checkpoint as C
from repro.train import grad_compress as GC
from repro.train import optimizer as opt_lib
from repro.train.train_loop import StragglerMonitor

KEY = jax.random.PRNGKey(0)


class TestCheckpoint:
    def _tree(self, seed=0):
        k = jax.random.PRNGKey(seed)
        return {"layer": {"w": jax.random.normal(k, (16, 8)),
                          "b": jnp.zeros((8,))},
                "stack": jax.random.normal(k, (4, 3, 3))}

    def test_round_trip(self, tmp_path):
        tree = self._tree()
        C.save(str(tmp_path), 7, tree, extra={"lfsr": [1, 2, 3]})
        assert C.latest_step(str(tmp_path)) == 7
        got, extra = C.restore(str(tmp_path), 7, tree)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert extra["lfsr"] == [1, 2, 3]

    def test_atomic_manifest(self, tmp_path):
        """A checkpoint dir without manifest.json is invisible (crash
        mid-save never yields a corrupt 'latest')."""
        tree = self._tree()
        C.save(str(tmp_path), 3, tree)
        d = tmp_path / "step_00000005"
        d.mkdir()
        (d / "shards_host0.npz").write_bytes(b"garbage")
        assert C.latest_step(str(tmp_path)) == 3    # 5 has no manifest

    def test_elastic_reshard_roundtrip(self, tmp_path):
        """Restore re-places leaves with explicit shardings (mesh may have
        changed between save and restore)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((1,), ("data",))
        tree = self._tree()
        C.save(str(tmp_path), 1, tree)
        sh = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), tree)
        got, _ = C.restore(str(tmp_path), 1, tree, shardings=sh)
        assert got["layer"]["w"].sharding == NamedSharding(mesh, P())

    def test_async_checkpointer_and_gc(self, tmp_path):
        tree = self._tree()
        saver = C.AsyncCheckpointer(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            saver.save(s, tree)
        saver.wait()
        saver._gc()
        assert C.latest_step(str(tmp_path)) == 4
        steps = sorted(p.name for p in tmp_path.iterdir())
        assert len(steps) == 2                     # gc kept last 2

    def test_resume_training_bit_exact(self, tmp_path):
        """Uninterrupted 6 steps == (3 steps, checkpoint, restart, 3 more)."""
        tc = TrainConfig(optimizer="sgd", lr=0.1, steps=6, batch_size=4)
        w0 = jnp.ones((4, 4))

        def data(step):
            return jax.random.normal(jax.random.fold_in(KEY, step), (4, 4))

        def step_fn(w, m, step):
            g = jax.grad(lambda w: jnp.mean((w @ data(step) - 1.0) ** 2))(w)
            return opt_lib.sgd_update(g, m, w, 0.1, tc)

        # uninterrupted
        w, m = w0, opt_lib.sgd_init(w0)
        for s in range(6):
            w, m = step_fn(w, m, s)
        # interrupted at 3
        w2, m2 = w0, opt_lib.sgd_init(w0)
        for s in range(3):
            w2, m2 = step_fn(w2, m2, s)
        C.save(str(tmp_path), 3, {"w": w2, "m": m2})
        st = C.latest_step(str(tmp_path))
        got, _ = C.restore(str(tmp_path), st, {"w": w2, "m": m2})
        w2, m2 = got["w"], got["m"]
        for s in range(st, 6):
            w2, m2 = step_fn(w2, m2, s)
        np.testing.assert_allclose(np.asarray(w), np.asarray(w2), rtol=1e-6)


class TestGradCompress:
    def test_error_feedback_preserves_mean_gradient(self):
        """Over many steps the accumulated EF-compressed gradient tracks
        the true gradient sum (bias -> 0)."""
        mesh = jax.make_mesh((1,), ("data",))
        from jax.sharding import PartitionSpec as P
        psum8 = GC.make_compressed_psum(("data",))
        g = {"w": jax.random.normal(KEY, (64, 64)) * 0.01}
        err = GC.init_error_state(g)
        total_true = jnp.zeros((64, 64))
        total_comp = jnp.zeros((64, 64))

        fn = jax.shard_map(lambda gg, ee, kk: psum8(gg, ee, kk[0]),
                           mesh=mesh, in_specs=(P(), P(), P("data")),
                           out_specs=P(), check_vma=False)
        for s in range(50):
            key = jax.random.fold_in(KEY, s)
            gs = {"w": g["w"] + 0.001 * jax.random.normal(key, (64, 64))}
            red, err = fn(gs, err, jax.random.split(key, 1))
            total_true += gs["w"]
            total_comp += red["w"]
        rel = float(jnp.linalg.norm(total_comp - total_true) /
                    jnp.linalg.norm(total_true))
        assert rel < 0.02, rel

    def test_wire_bytes_4x(self):
        params = {"w": jnp.zeros((1000, 1000))}
        f32, i8 = GC.compression_wire_bytes(params)
        assert f32 == 4 * i8


class TestOptimizers:
    def test_sgd_momentum_matches_reference(self):
        tc = TrainConfig(optimizer="sgd", momentum=0.8, weight_decay=0.0)
        w = jnp.ones((4,))
        g = jnp.full((4,), 0.5)
        st = opt_lib.sgd_init(w)
        w1, st = opt_lib.sgd_update(g, st, w, 0.1, tc)
        np.testing.assert_allclose(np.asarray(w1), 1.0 - 0.1 * 0.5)
        w2, st = opt_lib.sgd_update(g, st, w1, 0.1, tc)
        # m2 = 0.8*0.5 + 0.5 = 0.9
        np.testing.assert_allclose(np.asarray(w2),
                                   np.asarray(w1) - 0.1 * 0.9, rtol=1e-6)

    def test_cosine_schedule_endpoints(self):
        tc = TrainConfig(lr=0.1, lr_min=0.005, steps=100)
        assert float(opt_lib.cosine_lr(jnp.asarray(0), tc)) == \
            pytest.approx(0.1)
        assert float(opt_lib.cosine_lr(jnp.asarray(100), tc)) == \
            pytest.approx(0.005)

    def test_adamw_converges_quadratic(self):
        tc = TrainConfig(optimizer="adamw", weight_decay=0.0)
        w = jnp.full((8,), 5.0)
        st = opt_lib.adamw_init(w)
        for _ in range(200):
            g = 2 * w
            w, st = opt_lib.adamw_update(g, st, w, 0.1, tc)
        assert float(jnp.max(jnp.abs(w))) < 0.1

    def test_clip_by_global_norm(self):
        g = {"a": jnp.full((10,), 10.0)}
        clipped, norm = opt_lib.clip_by_global_norm(g, 1.0)
        assert float(opt_lib.global_norm(clipped)) == pytest.approx(1.0,
                                                                    rel=1e-5)


class TestStragglerMonitor:
    def test_flags_slow_steps(self):
        m = StragglerMonitor(window=50, factor=2.0)
        for s in range(20):
            m.record(s, 0.1)
        assert m.record(20, 0.5)          # 5x median -> straggler
        assert not m.record(21, 0.11)
        assert len(m.flagged) == 1


class TestData:
    def test_lm_data_deterministic_and_resumable(self):
        b1 = lm_data.synth_batch(0, step=5, batch=2, seq_len=16, vocab=100)
        b2 = lm_data.synth_batch(0, step=5, batch=2, seq_len=16, vocab=100)
        np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                      np.asarray(b2["tokens"]))
        it = lm_data.stream(0, 2, 16, 100, start_step=5)
        b3 = next(it)
        np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                      np.asarray(b3["tokens"]))

    def test_labels_are_shifted_tokens(self):
        b = lm_data.synth_batch(0, 0, 2, 16, 100)
        np.testing.assert_array_equal(np.asarray(b["tokens"][:, 1:]),
                                      np.asarray(b["labels"][:, :-1]))

    def test_pointcloud_batch(self):
        pts, cls = pointclouds.make_batch(KEY, 128, 8)
        assert pts.shape == (8, 128, 3)
        assert bool(jnp.all(jnp.isfinite(pts)))
        norms = jnp.linalg.norm(np.asarray(pts), axis=-1)
        assert float(norms.max()) <= 1.001       # unit-sphere normalized
        assert 0 <= int(cls.min()) and int(cls.max()) < pointclouds.N_CLASSES

    def test_pointcloud_classes_distinguishable(self):
        """Different classes produce geometrically different clouds."""
        import numpy as onp
        k = jax.random.PRNGKey(1)
        pts, cls = pointclouds.make_batch(k, 256, 64)
        pts, cls = onp.asarray(pts), onp.asarray(cls)
        # mean |z| differs between disk (flat) and sphere
        feats = onp.abs(pts[:, :, 2]).mean(1)
        if (cls == 6).any() and (cls == 0).any():
            assert feats[cls == 6].mean() < feats[cls == 0].mean()
