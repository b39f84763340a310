"""Stage-plan IR: compile a PipelineSpec into an explicit per-stage op plan.

HLS4PC's claim is that the compression ladder is a *configuration
sweep*; PointAcc's is that the mapping ops (sample / group / normalize)
deserve first-class dataflow treatment next to the NN layers.  Both
arguments land in the same place: the forward walk should be **data**,
not code.  This module is that data — a small op IR

    EmbedOp, SampleOp, GroupOp, FusedGroupTransferOp,
    CBROp, ResBlockOp, PoolOp, HeadOp

and ``lower(spec, cfg) -> StagePlan``, the one-shot compiler from a
declarative :class:`~repro.api.spec.PipelineSpec` to the op sequence
the model interpreter (``repro.models.pointmlp._forward``) executes.
``repro.api.build`` lowers once per pipeline; every remaining ROADMAP
component (a new grouper, a new backend, a fused mapping path) is a
lowering rule, not a model edit.

Per-stage overrides
-------------------
``PipelineSpec.stage_precision`` / ``stage_backend`` are 4-tuples (one
entry per stage) resolved here, per :class:`CBROp`, at lowering time:
``stage_precision=("int8", "int8", "int8", "fp32")`` quantizes stages
1-3 and keeps stage 4 (and the embed/head, which follow the spec-level
``precision``) in fp32 — the paper's per-layer quantization exploration
as a spec field.  Lowering diagnostics route through the
``repro.analysis`` pass framework: soft misconfigurations warn with a
stable ``RPAxxx``-coded message (escalated to an error in-tree by the
pytest ``filterwarnings`` gate, keyed on the code prefix); hard errors
(bad tuple length, unknown key, unfusable combination) raise
``ValueError``/``KeyError`` with the same coded messages.

Fused group->normalize->transfer
--------------------------------
With ``spec.fused_group="grouped_transfer"`` the ``GroupOp`` +
transfer-``CBROp`` pair of each stage lowers to one
:class:`FusedGroupTransferOp` executing a single fused gather +
geometric-affine-normalize + matmul+bias+ReLU kernel
(``repro.kernels.grouped_transfer``), so the ``[B, S, k, 2C]`` grouped
tensor never round-trips through HBM between normalize and transfer —
the dataflow the FPGA pipeline implies.  Fused entries live in the
:data:`~repro.api.registry.FUSED_OPS` registry.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import itertools
import json
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from repro.api import registry
from repro.api.spec import N_STAGES as _N_STAGES
from repro.core.quant import QuantConfig, is_quantizable_leaf_path

_PALLAS_BACKENDS = ("pallas_interpret", "pallas")


# ------------------------------------------------------------- op IR ----

@dataclasses.dataclass(frozen=True)
class CBROp:
    """One Conv(+folded BN)(+ReLU) layer, fully resolved.

    ``path`` addresses the layer's param dict inside the model tree
    (``("embed",)``, ``("stages", 2, "transfer")``, ...); ``fn`` is the
    resolved backend callable from ``repro.api.registry.BACKENDS`` and
    ``quant`` the exact :class:`QuantConfig` handed to it at runtime
    (None = fp32).  ``precision`` / ``backend`` keep the registry keys
    for introspection; they never re-resolve.
    """
    path: Tuple[Any, ...]
    stage: Optional[int]            # owning stage, None for embed/head
    act: bool
    precision: str
    backend: str
    quant: Optional[QuantConfig] = dataclasses.field(compare=False,
                                                     default=None)
    fn: Optional[Callable] = dataclasses.field(repr=False, compare=False,
                                               default=None)


@dataclasses.dataclass(frozen=True)
class EmbedOp:
    """Pointwise embedding conv: xyz [B,N,3] -> features [B,N,E]."""
    cbr: CBROp


@dataclasses.dataclass(frozen=True)
class SampleOp:
    """Pick stage centroids with the resolved sampler (FPS / URS / ...).

    ``cached=True`` (stream lowering) lets the interpreter replay the
    stage's sampled indices from a stream cache — but only for samplers
    that do not advance the LFSR state (``advances_state=False``);
    state-advancing samplers still run so the state walk stays exactly
    the cold path's.  Either way the op *collects* its indices into the
    cache on the collect pass.
    """
    stage: int
    n_samples: int
    cached: bool = False


@dataclasses.dataclass(frozen=True)
class GroupOp:
    """Build normalized local neighborhoods with the resolved grouper:
    (xyz, feats, idx) -> (new_xyz, center feats, grouped [B,S,k,2C]).

    ``cached=True`` (stream lowering) splits the grouper into its
    mapping half (``neighbor_index`` — replayed from the stream cache)
    and its arithmetic half (``group_with_idx`` — always recomputed).
    """
    stage: int
    k: int
    cached: bool = False


@dataclasses.dataclass(frozen=True)
class FusedGroupTransferOp:
    """A ``GroupOp`` + transfer-``CBROp`` pair lowered to one fused
    gather + geometric-affine-normalize + matmul+bias+ReLU kernel
    (``repro.api.registry.FUSED_OPS[kernel]``); the grouped
    ``[B, S, k, 2C]`` tensor never leaves the kernel."""
    stage: int
    k: int
    cbr: CBROp                      # the transfer layer it absorbs
    kernel: str                     # FUSED_OPS registry key
    fn: Optional[Callable] = dataclasses.field(repr=False, compare=False,
                                               default=None)


@dataclasses.dataclass(frozen=True)
class ResBlockOp:
    """Bottleneck residual block: relu(net2(net1(x)) + x)."""
    stage: int
    branch: str                     # "pre" ([B,S,k,C]) | "pos" ([B,S,C])
    index: int
    net1: CBROp
    net2: CBROp                     # act=False; the ReLU runs post-add


@dataclasses.dataclass(frozen=True)
class PoolOp:
    """Max-pool: axis=2 pools neighbors ([B,S,k,C] -> [B,S,C]), axis=1
    is the global pool before the head ([B,S,C] -> [B,C])."""
    stage: Optional[int]
    axis: int


@dataclasses.dataclass(frozen=True)
class HeadOp:
    """3-layer MLP classifier; fc3 is a plain linear (no activation)."""
    fc1: CBROp
    fc2: CBROp
    fc3_path: Tuple[Any, ...]
    fc3_quant: Optional[QuantConfig] = dataclasses.field(compare=False,
                                                         default=None)


@dataclasses.dataclass(frozen=True)
class SegHeadOp:
    """Per-point segmentation head (``spec.head="seg"`` lowering rule).

    Replaces the global ``PoolOp(axis=1)`` + :class:`HeadOp` pair: the
    global descriptor is max-pooled *inside* the op, the final stage's
    features are upsampled back to the input resolution by 1-NN
    interpolation (the mapping op the stream cache can replay), the
    skip path concatenates ``[embed_feats, upsampled, global]``, and a
    3-layer per-point classifier emits ``[B, n_points, n_classes]``.
    ``cached=True`` lets the interpreter replay the upsample index.
    """
    fc1: CBROp
    fc2: CBROp
    fc3_path: Tuple[Any, ...]
    fc3_quant: Optional[QuantConfig] = dataclasses.field(compare=False,
                                                         default=None)
    cached: bool = False


@dataclasses.dataclass(frozen=True)
class PropagateOp:
    """One feature-propagation level of the part-segmentation decoder
    (``spec.head="partseg"`` lowering rule), coarse to fine.

    Each point of the finer encoder level takes its 3 nearest points
    of the current (coarser) level, fewer where that level has fewer,
    picked by the kNN selection the groupers use; weights them by
    inverse squared distance; concatenates the finer level's skip
    features with the interpolation; then runs ``fuse`` and the
    residual ``blocks`` (``(net1, net2)`` pairs, ReLU after the add).
    """
    level: int                      # 1..N_STAGES, coarse to fine
    fuse: CBROp
    blocks: Tuple[Tuple[CBROp, CBROp], ...]


@dataclasses.dataclass(frozen=True)
class PartSegHeadOp:
    """The published PointMLP part-segmentation head: per encoder level
    (coarse first) a ``context`` CBR max-pooled over its points, their
    concat through ``context_out``; the request's one-hot category
    through the ``category`` CBRs; per point, the decoder's features
    with both broadcast, through ``classifier`` (BN folded, no ReLU)
    and ``out``, then a log-softmax over the parts."""
    context: Tuple[CBROp, ...]
    context_out: CBROp
    category: Tuple[CBROp, ...]
    classifier: CBROp
    out: CBROp


StageOp = Any   # union of the op dataclasses above


# ---------------------------------------------------------- StagePlan ---

@dataclasses.dataclass(frozen=True)
class StagePlan:
    """A compiled per-stage op plan — the executable rendering of one
    :class:`~repro.api.spec.PipelineSpec` (or of a legacy config).

    ``ops`` is the flat op sequence the interpreter walks; the
    ``stage_*`` tuples record the resolved per-stage policy for
    introspection, quantization and cost reporting.
    """
    name: str
    ops: Tuple[StageOp, ...]
    stage_precision: Tuple[str, ...]
    stage_backend: Tuple[str, ...]
    precision: str                  # embed + head precision
    backend: str                    # embed + head backend key
    fused_group: str = "none"
    head: str = "cls"               # "cls" | "seg" | "partseg"
    stream: bool = False            # cache-aware mapping-op variants

    # ------------------------------------------------- introspection ----

    def cbr_ops(self) -> List[CBROp]:
        """Every CBR layer in execution order (fused transfers included)."""
        out: List[CBROp] = []
        for op in self.ops:
            if isinstance(op, EmbedOp):
                out.append(op.cbr)
            elif isinstance(op, CBROp):
                out.append(op)
            elif isinstance(op, FusedGroupTransferOp):
                out.append(op.cbr)
            elif isinstance(op, ResBlockOp):
                out.extend((op.net1, op.net2))
            elif isinstance(op, (HeadOp, SegHeadOp)):
                out.extend((op.fc1, op.fc2))
            elif isinstance(op, PropagateOp):
                out.append(op.fuse)
                for pair in op.blocks:
                    out.extend(pair)
            elif isinstance(op, PartSegHeadOp):
                out.extend(op.context + (op.context_out,) + op.category
                           + (op.classifier, op.out))
        return out

    @property
    def mixed_precision(self) -> bool:
        precs = set(self.stage_precision) | {self.precision}
        return len(precs) > 1

    @property
    def any_int8(self) -> bool:
        return "int8" in self.stage_precision or self.precision == "int8"

    def quant_predicate(self) -> Callable[[tuple, Any], bool]:
        """Predicate for :func:`repro.core.quant.quantize_tree` selecting
        exactly the weight leaves whose owning region (stage / embed /
        head) resolved to int8.  For a uniform-int8 plan this selects
        the same leaves as the default predicate — the pre-plan export
        — bit for bit."""
        def pred(path: tuple, leaf: Any) -> bool:
            if not (is_quantizable_leaf_path(path)
                    and getattr(leaf, "ndim", 0) >= 2):
                return False
            s = _path_stage(path)
            prec = self.precision if s is None else self.stage_precision[s]
            return prec == "int8"
        return pred

    def describe(self) -> str:
        """Compact per-stage rendering for ``FrozenPipeline.describe``."""
        rows = []
        fused = {op.stage for op in self.ops
                 if isinstance(op, FusedGroupTransferOp)}
        for s in range(_N_STAGES):
            row = (f"stage {s + 1}: {self.stage_precision[s]}/"
                   f"{self.stage_backend[s]}")
            if s in fused:
                row += f" [group->transfer fused: {self.fused_group}]"
            if self.stream:
                row += " [stream-cached mapping]"
            rows.append(row)
        rows.append(f"head: {self.head}/{self.precision}/{self.backend}")
        return "; ".join(rows)

    # ------------------------------------------------ cost breakdown ----

    def cost_breakdown(self, cfg) -> List[Dict[str, Any]]:
        """Analytic per-stage-op FLOPs / weight-bytes / activation-bytes.

        The FLOP column is taken verbatim from
        :func:`repro.models.pointmlp.pointmlp_flops_breakdown` (one
        source of truth — the rows sum to exactly ``pointmlp_flops``);
        the bytes columns are derived from the plan, so precision
        overrides shrink weight bytes and a fused group->transfer
        stage zeroes the grouped tensor's HBM round-trip.
        """
        # Deferred import: this package sits below the models in the
        # import graph (mirrors the spec<->model-config bridge).
        from repro.models.pointmlp import pointmlp_flops_breakdown
        flops = pointmlp_flops_breakdown(cfg)
        rows: List[Dict[str, Any]] = []

        def wbytes(c_in: int, c_out: int, precision: str) -> int:
            if precision == "int8":
                return c_in * c_out + 4 * c_out      # int8 q + f32 scales
            return 4 * c_in * c_out

        def row(op: str, w_bytes: int, act_bytes: int) -> None:
            rows.append({"op": op, "flops": flops[op],
                         "w_bytes": w_bytes, "act_bytes": act_bytes})

        n, e = cfg.n_points, cfg.embed_dim
        row("embed", wbytes(cfg.in_channels, e, self.precision), 4 * n * e)
        c_prev = e
        fused = {op.stage for op in self.ops
                 if isinstance(op, FusedGroupTransferOp)}
        for s in range(_N_STAGES):
            smp, c = cfg.stage_samples[s], cfg.stage_dims[s]
            k = cfg.k_neighbors
            prec = self.stage_precision[s]
            # The [S,k,2C] grouped tensor never materializes when the
            # stage lowers fused, but the fused path's sigma stats pass
            # still reads a [S,k,C] gather (all modes except "center"),
            # so fusion halves — not zeroes — the group op's traffic.
            if s not in fused:
                group_bytes = 4 * smp * k * cfg.transfer_in(c_prev)
            elif cfg.affine_mode == "center":
                group_bytes = 0
            else:
                group_bytes = 4 * smp * k * c_prev
            row(f"stage{s + 1}.group", 0, group_bytes)
            row(f"stage{s + 1}.transfer",
                wbytes(cfg.transfer_in(c_prev), c, prec), 4 * smp * k * c)
            mid = max(1, int(c * cfg.res_expansion))
            blk = wbytes(c, mid, prec) + wbytes(mid, c, prec)
            row(f"stage{s + 1}.pre", cfg.pre_blocks[s] * blk,
                4 * smp * k * c)
            row(f"stage{s + 1}.pos", cfg.pos_blocks[s] * blk, 4 * smp * c)
            c_prev = c
        if self.head == "partseg":
            self._partseg_cost(cfg, row, wbytes)
        elif self.head == "seg":
            # Per-point head: fc1 consumes the [N, E + 2*C4] skip concat
            # and every activation is n_points wide.
            c_in = cfg.embed_dim + 2 * c_prev
            row("head", wbytes(c_in, 512, self.precision)
                + wbytes(512, 256, self.precision)
                + wbytes(256, cfg.n_classes, self.precision),
                4 * n * (512 + 256 + cfg.n_classes))
        else:
            row("head", wbytes(c_prev, 512, self.precision)
                + wbytes(512, 256, self.precision)
                + wbytes(256, cfg.n_classes, self.precision),
                4 * (512 + 256 + cfg.n_classes))
        return rows

    def _partseg_cost(self, cfg, row, wbytes) -> None:
        """Decoder and head rows: the 3-NN reads both levels' xyz and
        the coarse features; every conv writes its activations once."""
        from repro.models.pointmlp import partseg_levels
        p = self.precision
        for j, (nf, nc, skip, c_in, w, blocks) in enumerate(
                partseg_levels(cfg), start=1):
            mid = max(1, int(w * cfg.res_expansion))
            row(f"fp{j}.interp", 0, 4 * (3 * (nf + nc) + nc * c_in))
            row(f"fp{j}", wbytes(c_in + skip, w, p)
                + blocks * (wbytes(w, mid, p) + wbytes(mid, w, p)),
                4 * nf * w * (1 + 2 * blocks))
        ctx, cat = cfg.context_dims
        enc = (cfg.embed_dim,) + cfg.stage_dims
        c_last = cfg.decoder[-1][0]
        w_bytes = (sum(wbytes(c, ctx, p) for c in enc)
                   + wbytes(len(enc) * ctx, ctx, p)
                   + wbytes(cfg.n_categories, cat, p) + wbytes(cat, cat, p)
                   + wbytes(c_last + ctx + cat, 128, p)
                   + wbytes(128, cfg.n_classes, p))
        row("partseg", w_bytes, 4 * cfg.n_points * (128 + cfg.n_classes))


def _path_stage(path: tuple) -> Optional[int]:
    """Stage index owning a param-tree key path (None = embed/head).

    Accepts both jax key-path entries (DictKey/SequenceKey) and the
    plain str/int paths the op IR stores.
    """
    first = getattr(path[0], "key", path[0])
    if first == "stages" and len(path) > 1:
        idx = getattr(path[1], "idx", path[1])
        return int(idx) if isinstance(idx, int) else None
    return None


def param_at(params: Dict, path: Tuple[Any, ...]):
    """Fetch the param subtree an op's ``path`` addresses."""
    node = params
    for p in path:
        node = node[p]
    return node


# ----------------------------------------------------------- lowering ---

def resolve_stage_fields(spec) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """Resolve ``spec.stage_precision`` / ``stage_backend`` to full
    4-tuples (inheriting the spec-level fields where unset).  Spec
    ``__post_init__`` already checked shapes; semantic validation
    (unknown keys, fused-path preconditions) lives in the
    ``repro.analysis`` lowering passes :func:`lower` enforces."""
    prec = spec.stage_precision or (spec.precision,) * _N_STAGES
    back = spec.stage_backend or (spec.backend,) * _N_STAGES
    return tuple(prec), tuple(back)


def _quant_for(spec, precision: str,
               backend: str = "ref") -> Optional[QuantConfig]:
    """The deployment QuantConfig one CBR op runs under (None = fp32).

    The int8 x pallas lowering rule lives here: an int8 op on a pallas
    backend runs the int8 Pallas matmul kernel (int32 MXU accumulation,
    epilogue dequant) — the former RPA101 warn-and-fall-back to the
    reference int8 matmul is retired.  ``pallas_interpret`` pins
    interpret mode (the CPU correctness canary); ``pallas`` compiles.
    """
    if precision != "int8":
        return None
    if backend in _PALLAS_BACKENDS:
        return QuantConfig(w_bits=min(spec.w_bits, 8), a_bits=spec.a_bits,
                           per_channel=spec.per_channel,
                           symmetric=spec.symmetric, backend="int8_pallas",
                           interpret=(backend == "pallas_interpret"))
    return QuantConfig(w_bits=min(spec.w_bits, 8), a_bits=spec.a_bits,
                       per_channel=spec.per_channel,
                       symmetric=spec.symmetric, backend="int8_ref")


def _build_ops(cfg, make_cbr: Callable, head_quant: Optional[QuantConfig],
               fused_key: Optional[str] = None,
               fused_fn: Optional[Callable] = None,
               head: str = "cls",
               stream: bool = False) -> Tuple[StageOp, ...]:
    """The one op-sequence skeleton both lowerings share.

    ``make_cbr(path, stage, act)`` is the only thing that differs
    between the spec lowering (per-stage precision/backend resolution)
    and the legacy config lowering (one uniform backend) — the
    topology walk itself exists exactly once.  ``head="seg"`` swaps the
    global pool + :class:`HeadOp` tail for a :class:`SegHeadOp`;
    ``stream=True`` marks every mapping op ``cached`` so the
    interpreter can replay a stream cache.
    """
    ops: List[StageOp] = [EmbedOp(make_cbr(("embed",), None, True))]
    for s in range(_N_STAGES):
        ops.append(SampleOp(stage=s, n_samples=cfg.stage_samples[s],
                            cached=stream))
        transfer = make_cbr(("stages", s, "transfer"), s, True)
        if fused_fn is not None:
            ops.append(FusedGroupTransferOp(
                stage=s, k=cfg.k_neighbors, cbr=transfer,
                kernel=fused_key, fn=fused_fn))
        else:
            ops.append(GroupOp(stage=s, k=cfg.k_neighbors, cached=stream))
            ops.append(transfer)
        for branch, count in (("pre", cfg.pre_blocks[s]),
                              ("pos", cfg.pos_blocks[s])):
            for i in range(count):
                base = ("stages", s, branch, i)
                ops.append(ResBlockOp(
                    stage=s, branch=branch, index=i,
                    net1=make_cbr(base + ("net1",), s, True),
                    net2=make_cbr(base + ("net2",), s, False)))
            if branch == "pre":
                ops.append(PoolOp(stage=s, axis=2))
    if head == "partseg":
        return tuple(ops) + _partseg_ops(cfg, make_cbr)
    head_cls = HeadOp
    if head == "seg":
        head_cls = functools.partial(SegHeadOp, cached=stream)
    else:
        ops.append(PoolOp(stage=None, axis=1))
    ops.append(head_cls(fc1=make_cbr(("head", "fc1"), None, True),
                        fc2=make_cbr(("head", "fc2"), None, True),
                        fc3_path=("head", "fc3"), fc3_quant=head_quant))
    return tuple(ops)


def _partseg_ops(cfg, make_cbr: Callable) -> Tuple[StageOp, ...]:
    """The decoder levels and the part-segmentation head; every conv
    follows the spec-level precision and backend."""
    ops: List[StageOp] = []
    for j, (_, blocks) in enumerate(cfg.decoder):
        base = ("decoder", j)
        ops.append(PropagateOp(
            level=j + 1, fuse=make_cbr(base + ("fuse",), None, True),
            blocks=tuple((make_cbr(base + ("blocks", i, "net1"), None, True),
                          make_cbr(base + ("blocks", i, "net2"), None, False))
                         for i in range(blocks))))
    head = ("head",)
    ops.append(PartSegHeadOp(
        context=tuple(make_cbr(head + ("context", i), None, True)
                      for i in range(_N_STAGES + 1)),
        context_out=make_cbr(head + ("context_out",), None, True),
        category=tuple(make_cbr(head + ("category", i), None, True)
                       for i in range(2)),
        classifier=make_cbr(head + ("classifier",), None, False),
        out=make_cbr(head + ("out",), None, False)))
    return tuple(ops)


def lower(spec, cfg) -> StagePlan:
    """Compile a spec + model config into the executable op plan.

    ``cfg`` supplies the topology (stage samples/dims, block counts);
    ``spec`` supplies the policy (per-stage precision/backend overrides,
    the fused group->transfer path).  Called once per pipeline by
    ``repro.api.build``.  Validation routes through the
    ``repro.analysis`` lowering passes: error findings raise
    ``ValueError``/``KeyError`` with their ``RPAxxx``-coded message,
    warning findings warn — escalated in-tree by the pytest gate.
    Tile sizes are not a lowering decision: each Pallas kernel owns its
    own.
    """
    # Deferred import: repro.analysis.passes imports this module.
    from repro.analysis.passes import enforce_spec
    enforce_spec(spec, scopes=("lowering",))
    stage_prec, stage_back = resolve_stage_fields(spec)
    fused_key = getattr(spec, "fused_group", "none") or "none"
    fused_fn = (registry.FUSED_OPS.get(fused_key)
                if fused_key != "none" else None)
    head = getattr(spec, "head", "cls") or "cls"
    stream = bool(getattr(spec, "stream", False))

    def make_cbr(path, stage, act) -> CBROp:
        precision = spec.precision if stage is None else stage_prec[stage]
        backend = spec.backend if stage is None else stage_back[stage]
        return CBROp(path=tuple(path), stage=stage, act=act,
                     precision=precision, backend=backend,
                     quant=_quant_for(spec, precision, backend),
                     fn=registry.BACKENDS.get(backend))

    ops = _build_ops(cfg, make_cbr,
                     _quant_for(spec, spec.precision, spec.backend),
                     fused_key=fused_key if fused_fn is not None else None,
                     fused_fn=fused_fn, head=head, stream=stream)
    return StagePlan(name=spec.name, ops=ops,
                     stage_precision=stage_prec, stage_backend=stage_back,
                     precision=spec.precision, backend=spec.backend,
                     fused_group=fused_key, head=head, stream=stream)


def lower_config(cfg, backend_fn: Callable,
                 backend_key: str = "<resolved>") -> StagePlan:
    """Lower a legacy :class:`PointMLPConfig` + one resolved backend
    callable into a uniform plan — the pre-spec entry points
    (``pointmlp_infer`` / ``pointmlp_apply``) route through this, so
    the interpreter is the single forward implementation.

    Every CBR op gets ``backend_fn`` and the config's own quant policy
    (enabled QAT configs keep fake-quant inference semantics exactly as
    the monolithic walk did).
    """
    quant = cfg.quant if cfg.quant.enabled else None
    precision = "int8" if quant is not None else "fp32"

    def make_cbr(path, stage, act) -> CBROp:
        return CBROp(path=tuple(path), stage=stage, act=act,
                     precision=precision, backend=backend_key,
                     quant=quant, fn=backend_fn)

    head = getattr(cfg, "head", "cls") or "cls"
    return StagePlan(name=cfg.name,
                     ops=_build_ops(cfg, make_cbr, quant, head=head),
                     stage_precision=(precision,) * _N_STAGES,
                     stage_backend=(backend_key,) * _N_STAGES,
                     precision=precision, backend=backend_key, head=head)


def plan_shapes(spec) -> Dict[str, tuple]:
    """The shapes each Pallas kernel of the point pipeline runs at under
    ``spec``, per serving lane, as :func:`lower`'s ops see them.

    The matmul kernels (``fused_linear``, ``int8_matmul``) at the
    FLOP-heaviest stage transfer, ``(S * k, cfg.transfer_in(C_prev), C)``;
    the mapping kernels at stage 1: ``fps`` ``(N, S)``, ``knn``
    ``(S, N, k)`` and ``grouped_transfer`` ``(N, S, k, C_embed)``.
    """
    cfg = spec.to_model_config()
    k = cfg.k_neighbors
    c_prev = (cfg.embed_dim,) + cfg.stage_dims[:-1]
    transfers = [(cfg.stage_samples[s] * k, cfg.transfer_in(c_prev[s]),
                  cfg.stage_dims[s]) for s in range(_N_STAGES)]
    mm = max(transfers, key=lambda t: t[0] * t[1] * t[2])
    n, s0 = cfg.n_points, cfg.stage_samples[0]
    return {"fused_linear": mm, "int8_matmul": mm,
            "grouped_transfer": (n, s0, k, cfg.embed_dim),
            "fps": (n, s0), "knn": (s0, n, k)}


# ------------------------------------------- fingerprint / search space -

def spec_fingerprint(spec) -> str:
    """Deterministic 12-hex-char fingerprint of a spec's field values.

    The identity key of one point in the design space: two specs share
    a fingerprint iff they lower the same (field order is canonicalized,
    tuples/lists normalize to the same JSON, and an unset
    ``stage_precision``/``stage_backend`` hashes as the full inherited
    tuple — so the all-fp32 anchor and its explicit-tuple twin are one
    point).  Used by ``repro.tune`` to name, dedupe and diff
    ``BENCH_<rev>.json`` rows across revisions — stable as long as the
    spec itself is.
    """
    d = dataclasses.asdict(spec)
    prec, back = _inherited_stage_fields(spec)
    d["stage_precision"], d["stage_backend"] = list(prec), list(back)
    blob = json.dumps(d, sort_keys=True, default=str,
                      separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def _inherited_stage_fields(spec) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """Stage tuples with spec-level inheritance applied — the shape
    :func:`resolve_stage_fields` resolves to, without its registry
    checks or warnings (fingerprinting/labeling must stay pure)."""
    prec = tuple(spec.stage_precision or (spec.precision,) * _N_STAGES)
    back = tuple(spec.stage_backend or (spec.backend,) * _N_STAGES)
    return prec, back


def spec_label(spec) -> str:
    """Compact human-readable rendering of the *searched* axes of a spec
    (the tuner's row name — stable across revisions for the CI diff)."""
    prec, back = _inherited_stage_fields(spec)
    return (f"{spec.sampler}/{spec.grouper}"
            f"/prec={'.'.join(prec)}+{spec.precision}"
            f"/be={back[0] if len(set(back)) == 1 else '.'.join(back)}"
            f"/fg={getattr(spec, 'fused_group', 'none')}"
            f"/ds={spec.data_shards}")


#: Default per-stage precision ladder searched by the autotuner: the
#: two uniform endpoints plus the paper-style tail-in-fp32 mixes.
DEFAULT_STAGE_PRECISIONS: Tuple[Tuple[str, ...], ...] = (
    ("fp32",) * _N_STAGES,
    ("int8",) * _N_STAGES,
    ("int8", "int8", "int8", "fp32"),
    ("int8", "int8", "fp32", "fp32"),
)


def enumerate_plan_space(base,
                         *,
                         stage_precisions: Iterable = DEFAULT_STAGE_PRECISIONS,
                         stage_backends: Iterable = (("ref",) * _N_STAGES,),
                         fused_groups: Iterable = ("none",),
                         data_shards: Iterable = (1,),
                         samplers: Optional[Iterable] = None,
                         groupers: Optional[Iterable] = None) -> List:
    """Enumerate the valid spec search space around ``base``.

    The cross product ``stage_precision`` x ``stage_backend`` x
    ``fused_group`` x ``data_shards`` x sampler x grouper, filtered by
    the ``repro.analysis`` lowering passes: any candidate with an error
    finding (fused group->transfer with an int8 stage or non-knn
    grouper, unknown registry keys, a broken stream contract) *or* a
    warning finding leaves the space.  int8 stages on pallas backends
    are *valid* points (they lower to the int8 Pallas matmul — the
    former RPA101 fallback warning is retired).  Deterministic order —
    the cross product in argument order — so the autotuner's candidate
    ranking is reproducible.
    """
    # Deferred import: repro.analysis.passes imports this module.
    from repro.analysis.passes import analyze_spec
    samplers = tuple(samplers) if samplers is not None else (base.sampler,)
    groupers = tuple(groupers) if groupers is not None else (base.grouper,)
    out = []
    for sp, sb, fg, ds, sam, grp in itertools.product(
            tuple(tuple(p) for p in stage_precisions),
            tuple(tuple(b) for b in stage_backends),
            tuple(fused_groups), tuple(data_shards),
            samplers, groupers):
        spec = base.replace(stage_precision=sp, stage_backend=sb,
                            fused_group=fg, data_shards=ds,
                            sampler=sam, grouper=grp)
        if analyze_spec(spec, scopes=("lowering",)):
            continue
        out.append(spec)
    return out
