"""Reproducible launch recipes: the env/XLA flags a benchmark ran under.

A perf number is only comparable to another perf number launched the
same way — the allocator, the XLA scheduler flags, and the forced
device count all move the measured samples/sec.  This module freezes
each supported platform's launch recipe as a :class:`LaunchProfile`
so a ``BENCH_<rev>.json`` row can record (and a rerun can reproduce)
exactly how the process was brought up::

    from repro.launch.profile import PROFILES, launch_profile

    prof = launch_profile()            # resolved for this host
    prof.apply()                       # os.environ, idempotent —
                                       # BEFORE importing jax
    print(prof.shell_prefix())         # "LD_PRELOAD=... python ..."

Profiles only *add* settings the environment doesn't already pin —
an explicit ``XLA_FLAGS`` from the caller always wins — and
``apply()`` records what it changed so tests can undo it.

:func:`configure_compile_cache` places JAX's persistent compilation
cache; entry points call it before their first compile, never at
import.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
from typing import Dict, Optional, Tuple

#: tcmalloc soname the TPU-host recipe preloads (the standard Ubuntu
#: path; skipped by ``apply()`` when the library is absent).
TCMALLOC = "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4"


@dataclasses.dataclass(frozen=True)
class LaunchProfile:
    """One platform's frozen launch recipe.

    ``env`` entries are plain environment variables; ``xla_flags`` are
    merged (appended) into ``XLA_FLAGS`` unless the variable is already
    set by the caller — explicit wins over profile.
    """
    name: str
    env: Tuple[Tuple[str, str], ...] = ()
    xla_flags: Tuple[str, ...] = ()

    def launch_env(self, base: Optional[Dict[str, str]] = None
                   ) -> Dict[str, str]:
        """The env-var dict this profile resolves to on top of ``base``
        (``os.environ`` when None) — what a launcher should export.
        Does not mutate anything."""
        cur = dict(os.environ if base is None else base)
        out: Dict[str, str] = {}
        for k, v in self.env:
            if k not in cur:
                if k == "LD_PRELOAD" and not os.path.exists(v):
                    continue           # no tcmalloc on this image
                out[k] = v
        if self.xla_flags and "XLA_FLAGS" not in cur:
            out["XLA_FLAGS"] = " ".join(self.xla_flags)
        return out

    def apply(self) -> Dict[str, str]:
        """Export :meth:`launch_env` into ``os.environ`` (idempotent:
        already-set variables are never overwritten).  Returns what was
        set, so a test can pop the keys back off.  Call *before* the
        first jax import — XLA reads these at backend init."""
        changes = self.launch_env()
        os.environ.update(changes)
        return changes

    def shell_prefix(self) -> str:
        """The recipe as a ``VAR=... VAR=...`` shell prefix — what the
        CI workflow / run.sh puts in front of ``python``."""
        parts = [f"{k}={v}" for k, v in self.launch_env(base={}).items()]
        return " ".join(parts)


#: The supported recipes, keyed by platform.  ``cpu-ci`` is this
#: container / the GitHub runner: a forced single host device (the
#: engines' device math must see the same topology every run) and
#: quiet logs.  ``gpu`` is the olmax-style latency-hiding scheduler
#: set; ``tpu`` is the tcmalloc + quiet-logs TPU-VM recipe.
PROFILES: Dict[str, LaunchProfile] = {
    "cpu-ci": LaunchProfile(
        name="cpu-ci",
        env=(("TF_CPP_MIN_LOG_LEVEL", "4"),
             ("JAX_PLATFORMS", "cpu")),
        xla_flags=("--xla_force_host_platform_device_count=1",)),
    "gpu": LaunchProfile(
        name="gpu",
        env=(("TF_CPP_MIN_LOG_LEVEL", "4"),),
        xla_flags=("--xla_gpu_enable_latency_hiding_scheduler=true",
                   "--xla_gpu_enable_triton_softmax_fusion=true",
                   "--xla_gpu_triton_gemm_any=True",
                   "--xla_gpu_enable_highest_priority_async_stream=true")),
    "tpu": LaunchProfile(
        name="tpu",
        env=(("LD_PRELOAD", TCMALLOC),
             ("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD", "60000000000"),
             ("TF_CPP_MIN_LOG_LEVEL", "4"))),
}


#: The compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed directory of the checkout (listed in ``.gitignore``).  The
#: path is part of the cache key, so it is never built from a temporary
#: name, a pid or the time.
DEFAULT_COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable
    itself and this sets no directory.  Otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE`.  Either way every program is cached,
    not only those that took a second or more to compile, so a warm run
    compiles nothing.  Call before the first compile."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def launch_profile(platform: Optional[str] = None) -> LaunchProfile:
    """Resolve a :class:`LaunchProfile` for ``platform`` (a PROFILES
    key), or for this host when None: the jax default backend when jax
    is already imported, else ``cpu-ci``.  Unknown keys raise with the
    known names (registry idiom)."""
    if platform is None:
        import sys
        if "jax" in sys.modules:
            import jax
            backend = jax.default_backend()
            platform = {"tpu": "tpu", "gpu": "gpu"}.get(backend, "cpu-ci")
        else:
            platform = "cpu-ci"
    try:
        return PROFILES[platform]
    except KeyError:
        raise KeyError(f"unknown launch profile {platform!r}; known: "
                       f"{', '.join(sorted(PROFILES))}") from None
