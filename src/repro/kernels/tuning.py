"""The platform probe every Pallas kernel call resolves against.

Each kernel owns its tile sizes as keyword defaults; what is left to
decide per call is whether the kernel compiles to Mosaic or runs in the
Pallas interpreter.  :func:`on_tpu` is the one probe behind that choice,
behind the ``fps`` sampler's route and behind the ``tpu-platform``
lowering pass (tests steer all of them by patching it);
:func:`resolve_interpret` turns a kernel's ``interpret`` argument into
a bool against it.

Nothing here imports jax at module scope: the analysis passes import
this module without touching the accelerator runtime.
"""
from __future__ import annotations

from typing import Optional


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
