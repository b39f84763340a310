"""``repro.launch.profile``: launch recipes and the compile cache.

Explicit environment wins over a profile, ``apply()`` is idempotent,
unknown keys raise, and the persistent compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says or at a fixed checkout path.
"""
import os
import pathlib

import jax
import pytest


class TestLaunchProfiles:
    def test_explicit_env_wins(self):
        from repro.launch.profile import PROFILES
        prof = PROFILES["cpu-ci"]
        out = prof.launch_env(base={"JAX_PLATFORMS": "tpu",
                                    "XLA_FLAGS": "--mine"})
        assert "JAX_PLATFORMS" not in out and "XLA_FLAGS" not in out
        fresh = prof.launch_env(base={})
        assert fresh["JAX_PLATFORMS"] == "cpu"
        assert "--xla_force_host_platform_device_count=1" \
            in fresh["XLA_FLAGS"]

    def test_apply_is_idempotent_and_undoable(self):
        from repro.launch.profile import PROFILES
        prof = PROFILES["cpu-ci"]
        first = prof.apply()
        try:
            assert prof.apply() == {}        # everything now set
        finally:
            for k in first:
                os.environ.pop(k, None)

    def test_shell_prefix_renders_recipe(self):
        from repro.launch.profile import PROFILES
        prefix = PROFILES["cpu-ci"].shell_prefix()
        assert "JAX_PLATFORMS=cpu" in prefix
        assert "XLA_FLAGS=" in prefix

    def test_tpu_profile_skips_missing_tcmalloc(self):
        from repro.launch.profile import PROFILES, TCMALLOC
        env = PROFILES["tpu"].launch_env(base={})
        if not os.path.exists(TCMALLOC):
            assert "LD_PRELOAD" not in env
        else:                                # pragma: no cover
            assert env["LD_PRELOAD"] == TCMALLOC

    def test_compile_cache_env_var_wins(self, monkeypatch):
        """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and
        the helper sets no other directory."""
        from repro.launch import profile
        before = jax.config.jax_compilation_cache_dir
        min_s = jax.config.jax_persistent_cache_min_compile_time_secs
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        try:
            assert profile.configure_compile_cache() == "/elsewhere/cache"
            assert jax.config.jax_compilation_cache_dir == before
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        finally:
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              min_s)

    def test_compile_cache_defaults_to_fixed_checkout_path(self,
                                                           monkeypatch):
        from repro.launch import profile
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        min_s = jax.config.jax_persistent_cache_min_compile_time_secs
        try:
            first = profile.configure_compile_cache()
            assert jax.config.jax_compilation_cache_dir == first
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
            assert profile.configure_compile_cache() == first
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              min_s)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert pathlib.Path(first) == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text()

    def test_resolution_and_unknown_key(self):
        from repro.launch.profile import launch_profile
        assert launch_profile().name in ("cpu-ci", "gpu", "tpu")
        assert launch_profile("gpu").name == "gpu"
        with pytest.raises(KeyError, match="cpu-ci"):
            launch_profile("fpga")
