"""Per-kernel tile-size configuration — the kernel tuning axis.

HLS4PC's throughput comes from *parametrizable* fixed-point kernels whose
tiling/unroll factors are tuned per layer shape (§4); a single default tile
schedule leaves the MXU/VMEM half-used at most of the ladder's shapes.  This
module makes tiles a first-class lowering axis instead of buried kwarg
defaults: a frozen :class:`KernelTuning` rides on
:class:`repro.api.spec.PipelineSpec`, ``lower()`` binds the tile sizes onto
each op's backend callable, and ``repro.tune.kernels`` sweeps the grid at the
plan's actual shapes to pick them.

Every tile choice is observationally invisible modulo float accumulation
order: integer kernels (kNN/FPS indices, int8 matmul's int32 accumulator)
are bit-identical across the whole grid, f32 kernels reassociate only when
the reduction tile (``tk``) changes.  ``tests/test_kernel_tuning.py`` pins
both.

Nothing here imports jax at module scope on purpose: the config must stay
importable (and hashable / asdict-serializable for ``spec_fingerprint`` and
``build_pool`` keying) without touching the accelerator runtime.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


def _check_tile(name: str, v, n: int) -> None:
    vs = v if isinstance(v, tuple) else (v,)
    if isinstance(v, tuple) and len(v) != n:
        raise ValueError(f"KernelTuning.{name} wants {n} tile dims, got {v!r}")
    for t in vs:
        if not isinstance(t, int) or isinstance(t, bool) or t <= 0:
            raise ValueError(
                f"KernelTuning.{name} tiles must be positive ints, got {v!r}")


@dataclasses.dataclass(frozen=True)
class KernelTuning:
    """Frozen per-kernel tile sizes (the defaults reproduce the kernels'
    historical hardcoded values, so ``DEFAULT_TUNING`` is a no-op).

    Fields mirror the kernel signatures:
      * ``fused_linear``: (tm, tk, tn) for the fused CBR matmul.
      * ``grouped_transfer``: tile_s — sample-rows per grid step of the
        fused gather+normalize+affine+transfer kernel.
      * ``int8_matmul``: (tm, tk, tn) for the int8 MXU matmul.
      * ``fps``: tile_n — points per distance-update tile.
      * ``knn``: tile_s — query rows per grid step.
      * ``flash_attention``: (tq, tk) — query/key tile lengths.
    """
    fused_linear: Tuple[int, int, int] = (128, 128, 128)
    grouped_transfer: int = 64
    int8_matmul: Tuple[int, int, int] = (128, 128, 128)
    fps: int = 512
    knn: int = 128
    flash_attention: Tuple[int, int] = (128, 128)

    def __post_init__(self):
        for name, n in (("fused_linear", 3), ("int8_matmul", 3),
                        ("flash_attention", 2)):
            v = getattr(self, name)
            if isinstance(v, list):
                object.__setattr__(self, name, tuple(v))
            _check_tile(name, getattr(self, name), n)
        for name in ("grouped_transfer", "fps", "knn"):
            _check_tile(name, getattr(self, name), 1)

    def replace(self, **kw) -> "KernelTuning":
        return dataclasses.replace(self, **kw)


DEFAULT_TUNING = KernelTuning()


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU — the one platform probe
    behind interpret resolution and the ``tpu-platform`` lowering pass
    (tests steer both by patching this function)."""
    import jax
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve a kernel's ``interpret`` argument against the platform.

    ``None`` means "compile on real Pallas hardware, interpret elsewhere"
    — the lowering layer passes an explicit bool per backend key
    (``pallas_interpret`` forces True), so only direct kernel calls hit
    this default.  ``True`` on a TPU raises (RPA017): a kernel never
    runs interpreted on the chip without the caller hearing about it.
    """
    tpu = on_tpu()
    if interpret is None:
        return not tpu
    if interpret and tpu:
        # Deferred: repro.analysis imports repro.api, which imports
        # this module.
        from repro.analysis import enforce, finding
        enforce([finding(
            "RPA017", "interpret",
            "interpret=True on a TPU: Pallas kernels compile on the "
            "chip; pass interpret=None (or False) instead")])
    return bool(interpret)
