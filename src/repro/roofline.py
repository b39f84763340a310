"""Roofline analysis from compiled dry-run artifacts (§Roofline).

Hardware model: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI.  ``cost_analysis`` / the optimized HLO are produced
from the SPMD-*partitioned* module, so FLOPs / bytes / collective shapes
are already per-chip; the three terms therefore divide by per-chip peaks
directly (equivalent to the global-quantity / (chips × peak) form).

Collective bytes use the standard ring-model wire cost per chip:
  all-reduce        2·(n−1)/n · size
  all-gather        (n−1)/n · result
  reduce-scatter    (n−1)/n · operand  (= (n−1) · result)
  all-to-all        (n−1)/n · size
  collective-permute  size
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

PEAK_FLOPS = 197e12          # bf16 / chip
PEAK_INT8_OPS = 394e12       # int8 MACs*2 / chip (2x bf16)
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|([a-z0-9_]+)\[([0-9,]*)\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.M)
_SHAPE_RE = re.compile(r"([a-z0-9_]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class CollectiveStats:
    by_type: Dict[str, float]
    wire_bytes: float           # modeled per-chip wire traffic

    @property
    def total_bytes(self) -> float:
        return sum(self.by_type.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    by_type: Dict[str, float] = {}
    wire = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        tuple_body, dtype, dims, op = m.groups()
        if tuple_body is not None:
            size = sum(_shape_bytes(dt, dm)
                       for dt, dm in _SHAPE_RE.findall(tuple_body))
        else:
            size = _shape_bytes(dtype, dims)
        # group size for the ring model
        n = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            n = len([x for x in gm.group(1).split(",") if x.strip()])
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            if gi:
                n = int(gi.group(2))
        n = max(n, 2)
        frac = (n - 1) / n
        if op == "all-reduce":
            w = 2.0 * frac * size
        elif op == "all-gather":
            w = frac * size                   # size = result (gathered)
        elif op == "reduce-scatter":
            w = frac * size                   # size = operand in HLO? result*n
            w = (n - 1) * size                # result-sized shards from n-1 peers
        elif op == "all-to-all":
            w = frac * size
        else:                                 # collective-permute
            w = float(size)
        by_type[op] = by_type.get(op, 0.0) + float(size)
        wire += w
    return CollectiveStats(by_type=by_type, wire_bytes=wire)


@dataclasses.dataclass
class Roofline:
    flops: float                 # per-chip HLO flops
    hbm_bytes: float             # per-chip bytes accessed
    coll_bytes: float            # per-chip summed collective operand bytes
    coll_wire_bytes: float
    coll_by_type: Dict[str, float]
    model_flops: Optional[float] = None   # 6·N·D (or 2·N·D fwd-only), global

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_wire_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        ts = {"compute": self.t_compute, "memory": self.t_memory,
              "collective": self.t_collective}
        return max(ts, key=ts.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def useful_flops_ratio(self, n_chips: int) -> Optional[float]:
        if not self.model_flops:
            return None
        return self.model_flops / (self.flops * n_chips)

    def roofline_fraction(self, n_chips: int) -> Optional[float]:
        """MODEL_FLOPS-achievable fraction: useful work at peak vs the
        modeled bound time."""
        if not self.model_flops or self.t_bound == 0:
            return None
        t_useful = self.model_flops / n_chips / PEAK_FLOPS
        return t_useful / self.t_bound

    def to_dict(self) -> Dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes,
            "coll_wire_bytes": self.coll_wire_bytes,
            "coll_by_type": self.coll_by_type,
            "model_flops": self.model_flops,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
        }


def from_compiled(compiled, hlo_text: str,
                  model_flops: Optional[float] = None) -> Roofline:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    coll = parse_collectives(hlo_text)
    return Roofline(flops=flops, hbm_bytes=hbm,
                    coll_bytes=coll.total_bytes,
                    coll_wire_bytes=coll.wire_bytes,
                    coll_by_type=coll.by_type,
                    model_flops=model_flops)


def model_flops_estimate(n_active_params: int, tokens: int,
                         kind: str) -> float:
    """6·N·D for training, 2·N·D for forward-only (prefill/decode)."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active_params * tokens


# ------------------------------------------- static plan estimation -----
# The autotuner path: score a compiled StagePlan from its analytic
# cost_breakdown (per-op FLOPs / weight-bytes / activation-bytes)
# against a HardwareModel — no compiled HLO, no device, no dry-run
# artifacts — so the search can rank the whole spec space statically
# and spend measurement time only on the promising candidates.

@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """Peak rates the per-op roofline terms divide by.

    ``peak_int8_ops`` prices ops whose owning region resolved to int8
    (2x the fp peak on TPU-class hardware); ``dispatch_overhead_s`` is
    a fixed per-dispatch floor (kernel launch / host sync) so tiny
    plans don't estimate to implausibly-free.
    """
    name: str
    peak_flops: float            # fp FLOP/s per chip
    peak_int8_ops: float         # int8 OP/s per chip
    hbm_bw: float                # bytes/s per chip
    dispatch_overhead_s: float = 0.0


TPU_V5E = HardwareModel("tpu_v5e", PEAK_FLOPS, PEAK_INT8_OPS, HBM_BW)
#: Rough single-socket CPU-host model (the CI runner): the absolute
#: times are not to be trusted — only the *ranking* of candidates is
#: consumed — but the overhead term keeps 128-point quick specs from
#: estimating as pure bandwidth.
CPU_HOST = HardwareModel("cpu_host", peak_flops=5e10, peak_int8_ops=1e11,
                         hbm_bw=2e10, dispatch_overhead_s=2e-4)


@dataclasses.dataclass(frozen=True)
class PlanEstimate:
    """Static roofline estimate of one compiled plan (per sample)."""
    rows: tuple                  # per-op dicts: op/precision/flops/bytes/t_*
    hw: HardwareModel
    data_shards: int = 1

    @property
    def t_compute(self) -> float:
        return sum(r["t_compute"] for r in self.rows)

    @property
    def t_memory(self) -> float:
        return sum(r["t_memory"] for r in self.rows)

    @property
    def total_s(self) -> float:
        """Estimated seconds/sample: per-op bound times (each op is
        compute- or memory-bound on its own), split over the data
        shards, plus the fixed dispatch overhead."""
        t = sum(r["t_bound"] for r in self.rows)
        return t / max(self.data_shards, 1) + self.hw.dispatch_overhead_s

    @property
    def sps(self) -> float:
        return 1.0 / self.total_s

    @property
    def bottleneck(self) -> str:
        return ("compute" if self.t_compute >= self.t_memory else "memory")

    def to_rows(self):
        """JSON-ready per-stage rows for the BENCH artifact."""
        return [dict(r) for r in self.rows]


def _op_precision(plan, op: str) -> str:
    """The precision an op-name row of ``cost_breakdown`` runs under."""
    if op.startswith("stage"):
        s = int(op.split(".")[0][len("stage"):]) - 1
        return plan.stage_precision[s]
    return plan.precision            # embed / head


def estimate_plan(plan, cfg, hw: HardwareModel = TPU_V5E,
                  *, data_shards: int = 1) -> PlanEstimate:
    """Score a compiled :class:`repro.api.plan.StagePlan` statically.

    Consumes ``plan.cost_breakdown(cfg)`` directly (no compiled HLO):
    each row's FLOPs divide by the peak its precision buys, its
    weight+activation bytes by HBM bandwidth, and the op's bound time
    is the max of the two — the classic roofline, per op, summed.
    Precision overrides therefore shrink both terms (int8 peak is
    higher *and* int8 weights are smaller) and a fused group->transfer
    stage drops the grouped tensor's traffic, so the estimate ranks
    the autotuner's search space the way the paper's DSE does.
    """
    rows = []
    for row in plan.cost_breakdown(cfg):
        prec = _op_precision(plan, row["op"])
        peak = hw.peak_int8_ops if prec == "int8" else hw.peak_flops
        nbytes = row["w_bytes"] + row["act_bytes"]
        t_c = row["flops"] / peak
        t_m = nbytes / hw.hbm_bw
        rows.append({"op": row["op"], "precision": prec,
                     "flops": row["flops"], "w_bytes": row["w_bytes"],
                     "act_bytes": row["act_bytes"],
                     "t_compute": t_c, "t_memory": t_m,
                     "t_bound": max(t_c, t_m)})
    return PlanEstimate(rows=tuple(rows), hw=hw,
                        data_shards=max(int(data_shards), 1))
