"""Operations and bytes of the work a configuration asks for, and the
peaks of the chips the benchmark knows.

Counted from the configuration's shapes alone, never from the kernels
that happen to run a layer, so the yardstick stays put when a kernel is
fused or replaced.  One conv (CBR) layer of one input is a matmul
[M, K] @ [K, N]: 2*M*K*N operations, and it reads its input, weight and
bias and writes its output once.  Which layers an input runs, and what
mapping work besides, the configuration's reference module says
(``cbr_layers``, ``mapping_flops``); the sums and prices here are the
same for every configuration.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

#: Key under which ``harness.load_config`` keeps a configuration's
#: reference module, the one its ``reference`` file names.
MODULE = "_module"

#: Published peaks per chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM at 819 GB/s).  No float32 peak is
#: published; float32 work is priced at the bf16 peak.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown chip is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def compute_peak(device_kind: str, precision: str) -> float:
    """Operations per second of the chip at a configuration's precision
    (float32 at the bf16 peak)."""
    p = peaks(device_kind)
    return p["int8_ops"] if precision == "int8" else p["bf16_flops"]


@dataclasses.dataclass(frozen=True)
class Layer:
    """One CBR layer call for one cloud: [m, k] @ [k, n]."""
    name: str
    m: int
    k: int
    n: int

    @property
    def flops(self) -> int:
        return 2 * self.m * self.k * self.n

    def bytes(self, precision: str) -> int:
        """Input and weight at the layer's operand width, f32 bias and
        f32 output (the int8 layer's per-channel scales count as its
        bias)."""
        operand = 1 if precision == "int8" else 4
        return (self.m * self.k + self.k * self.n) * operand \
            + 4 * self.n + 4 * self.m * self.n


def cloud_flops(c: Dict, replayed: bool = False) -> int:
    """Operations of one input's forward: every CBR layer plus, unless
    its decisions are replayed, the mapping work (for PointMLP the kNN
    distances)."""
    cbr = sum(layer.flops for layer in c[MODULE].cbr_layers(c))
    return cbr + (0 if replayed else c[MODULE].mapping_flops(c))


def cbr_bound_s(c: Dict, device_kind: str) -> float:
    """Least time the chip could spend on one input's CBR layers: per
    layer the larger of operations over peak and bytes over HBM
    bandwidth, summed."""
    peak = compute_peak(device_kind, c["precision"])
    bw = peaks(device_kind)["hbm_bytes_per_s"]
    return sum(max(layer.flops / peak, layer.bytes(c["precision"]) / bw)
               for layer in c[MODULE].cbr_layers(c))
