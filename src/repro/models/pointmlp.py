"""PointMLP-Elite / PointMLP-Lite (HLS4PC §3; Ma et al. 2022).

Topology: conv1d embedding -> 4 stages of (local grouper [FPS|URS sample,
KNN group, geometric-affine normalize], transfer ConvBNReLU, pre-extraction
residual blocks on [B,S,k,C], max-pool over neighbors, pos-extraction
residual blocks on [B,S,C]) -> global max-pool -> 3-layer MLP classifier.

The compression ladder of Table 1 is expressed purely through
:class:`PointMLPConfig` (input points, sampler, affine mode, BN fusion,
quantization) — ``pointmlp_lite_config()`` is the paper's M-2 + 8/8 QAT.

All convs are pointwise (1x1), i.e. matmuls — on the FPGA they are
streaming MAC arrays; on TPU they hit the MXU, and the fused
conv+BN+ReLU path uses ``repro.kernels.fused_linear``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.api import plan as stage_plan
from repro.api import registry as api_registry
from repro.core import knn as knn_core
from repro.core.quant import QuantConfig
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class PointMLPConfig:
    name: str = "pointmlp-elite"
    n_points: int = 1024                  # N_input (Table 1 ladder)
    n_classes: int = 40
    embed_dim: int = 32
    k_neighbors: int = 16                 # paper HW uses k=16
    stage_expansion: Tuple[int, ...] = (2, 2, 2, 2)
    pre_blocks: Tuple[int, ...] = (1, 1, 2, 1)
    pos_blocks: Tuple[int, ...] = (1, 1, 2, 1)
    res_expansion: float = 0.25           # Elite's slim residual bottleneck
    sampler: str = "fps"                  # fps | urs
    affine_mode: str = "affine"           # affine | norm (alpha/beta pruned)
    head: str = "cls"                     # cls | seg (per-point logits)
    use_bn: bool = True                   # False after fuse_tree()
    quant: QuantConfig = QuantConfig(w_bits=32, a_bits=32)
    bn_momentum: float = 0.9

    @property
    def stage_samples(self) -> Tuple[int, ...]:
        # numSamp halves per stage: 1024 -> (512,256,128,64);
        # 512 -> (256,128,64,32) exactly as §2.1.
        return tuple(self.n_points // (2 ** (i + 1)) for i in range(4))

    @property
    def stage_dims(self) -> Tuple[int, ...]:
        dims, d = [], self.embed_dim
        for e in self.stage_expansion:
            d *= e
            dims.append(d)
        return tuple(dims)

    def replace(self, **kw) -> "PointMLPConfig":
        return dataclasses.replace(self, **kw)


def pointmlp_elite_config(n_classes: int = 40) -> PointMLPConfig:
    return PointMLPConfig(name="pointmlp-elite", n_classes=n_classes)


def pointmlp_m2_config(n_classes: int = 40) -> PointMLPConfig:
    """M-2 of Table 1: 512 points, URS, alpha/beta pruned, BN fused."""
    return PointMLPConfig(name="pointmlp-m2", n_points=512, sampler="urs",
                          affine_mode="norm", n_classes=n_classes)


def pointmlp_lite_config(n_classes: int = 40) -> PointMLPConfig:
    """PointMLP-Lite: M-2 + 8/8-bit QAT (Fig. 4 Pareto point)."""
    return pointmlp_m2_config(n_classes).replace(
        name="pointmlp-lite", quant=QuantConfig(w_bits=8, a_bits=8))


# ------------------------------------------------------------- init -----

def _cbr_init(key, c_in, c_out, cfg) -> Dict:
    return L.conv1d_init(key, c_in, c_out, ksize=1, bias=True,
                         bn=cfg.use_bn)


def _res_block_init(key, c, cfg) -> Dict:
    mid = max(1, int(c * cfg.res_expansion))
    k1, k2 = jax.random.split(key)
    return {"net1": _cbr_init(k1, c, mid, cfg),
            "net2": _cbr_init(k2, mid, c, cfg)}


def pointmlp_init(key, cfg: PointMLPConfig) -> Dict:
    keys = jax.random.split(key, 64)
    ki = iter(range(64))
    params: Dict = {"embed": _cbr_init(keys[next(ki)], 3, cfg.embed_dim, cfg)}
    c_prev = cfg.embed_dim
    stages = []
    for s in range(4):
        c_out = cfg.stage_dims[s]
        st: Dict = {}
        if cfg.affine_mode == "affine":
            st["affine"] = knn_core.geometric_affine_init(c_prev)
        st["transfer"] = _cbr_init(keys[next(ki)], 2 * c_prev, c_out, cfg)
        st["pre"] = [_res_block_init(keys[next(ki)], c_out, cfg)
                     for _ in range(cfg.pre_blocks[s])]
        st["pos"] = [_res_block_init(keys[next(ki)], c_out, cfg)
                     for _ in range(cfg.pos_blocks[s])]
        stages.append(st)
        c_prev = c_out
    params["stages"] = stages
    k1, k2, k3 = (keys[next(ki)] for _ in range(3))
    # Seg head fc1 consumes the per-point skip concat
    # [embed_feats (E), upsampled final feats (C4), global max (C4)].
    fc1_in = (cfg.embed_dim + 2 * c_prev if cfg.head == "seg" else c_prev)
    params["head"] = {
        "fc1": _cbr_init(k1, fc1_in, 512, cfg),
        "fc2": _cbr_init(k2, 512, 256, cfg),
        "fc3": L.conv1d_init(k3, 256, cfg.n_classes, ksize=1, bias=True,
                             bn=False),
    }
    return params


def count_conv_layers(cfg: PointMLPConfig) -> int:
    return 1 + sum(1 + 2 * cfg.pre_blocks[s] + 2 * cfg.pos_blocks[s]
                   for s in range(4))


# ------------------------------------------------------------ apply -----

def _cbr_apply(p: Dict, x: jnp.ndarray, cfg: PointMLPConfig, train: bool,
               act: bool = True) -> Tuple[jnp.ndarray, Dict]:
    """Conv(+BN)(+ReLU); in train mode BN uses batch stats and returns a
    params dict with refreshed running stats (functional BN)."""
    quant = cfg.quant if cfg.quant.enabled else None
    y = L._matmul(x, p["w"], quant)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    p_new = p
    if "bn" in p:
        bn = p["bn"]
        if train:
            red = tuple(range(y.ndim - 1))
            mu = jnp.mean(y, axis=red)
            var = jnp.var(y, axis=red)
            m = cfg.bn_momentum
            p_new = dict(p)
            p_new["bn"] = {"gamma": bn["gamma"], "beta": bn["beta"],
                           "mean": m * bn["mean"] + (1 - m) * mu,
                           "var": m * bn["var"] + (1 - m) * var}
        else:
            mu, var = bn["mean"], bn["var"]
        y = (y - mu) * jax.lax.rsqrt(var + 1e-5) * bn["gamma"] + bn["beta"]
    if act:
        y = jax.nn.relu(y)
    return y, p_new


def _forward_reference(params: Dict, cfg: PointMLPConfig, xyz: jnp.ndarray,
                       lfsr_state: Optional[jnp.ndarray], train: bool, *,
                       sampler, grouper, backend,
                       shared_urs: bool = False,
                       per_sample_norm: bool = False
                       ) -> Tuple[jnp.ndarray, Dict, Optional[jnp.ndarray]]:
    """The pre-IR monolithic topology walk — retained as the golden
    oracle for the stage-plan interpreter.

    This is the hand-written op sequence :func:`_forward` used to be
    before the plan refactor; ``tests/test_stage_plan.py`` asserts the
    interpreter is *bit-identical* to it for every spec, so the IR
    refactor stays observationally invisible until a per-stage override
    or the fused grouped-transfer path is opted into.  Production code
    never calls this; do not add features here — add lowering rules in
    ``repro.api.plan`` instead.
    """
    quant = cfg.quant if cfg.quant.enabled else None
    if train:
        def cbr(p, x, act=True):
            return _cbr_apply(p, x, cfg, True, act)
    else:
        def cbr(p, x, act=True):
            return backend(p, x, quant, act), p

    def res(p, x):
        h, n1 = cbr(p["net1"], x)
        h, n2 = cbr(p["net2"], h, act=False)
        return jax.nn.relu(h + x), {"net1": n1, "net2": n2}

    new_params = {k: v for k, v in params.items()}
    feats, new_params["embed"] = cbr(params["embed"], xyz)      # [B,N,E]

    cur_xyz, cur = xyz, feats
    new_stages = []
    for s, st in enumerate(params["stages"]):
        n_samp = cfg.stage_samples[s]
        idx, lfsr_state = sampler(cur_xyz, n_samp, lfsr_state, shared_urs)
        affine = st.get("affine")
        cur_xyz, _, grouped = grouper(
            cur_xyz, cur, idx, cfg.k_neighbors, affine, cfg.affine_mode,
            per_sample_norm)
        st_new = dict(st)
        h, st_new["transfer"] = cbr(st["transfer"], grouped)    # [B,S,k,C]
        pre_new = []
        for blk in st["pre"]:
            h, b_new = res(blk, h)
            pre_new.append(b_new)
        st_new["pre"] = pre_new
        h = jnp.max(h, axis=2)                                  # pool over k
        pos_new = []
        for blk in st["pos"]:
            h, b_new = res(blk, h)
            pos_new.append(b_new)
        st_new["pos"] = pos_new
        new_stages.append(st_new)
        cur = h
    new_params["stages"] = new_stages

    g = jnp.max(cur, axis=1)                                    # [B, C]
    head = params["head"]
    h, f1 = cbr(head["fc1"], g)
    h, f2 = cbr(head["fc2"], h)
    logits = L.conv1d_apply(head["fc3"], h, quant=quant)
    new_params["head"] = {"fc1": f1, "fc2": f2, "fc3": head["fc3"]}
    return logits, new_params, lfsr_state


def _forward(params: Dict, cfg: PointMLPConfig, xyz: jnp.ndarray,
             lfsr_state: Optional[jnp.ndarray], train: bool, *,
             sampler, grouper, backend,
             shared_urs: bool = False, per_sample_norm: bool = False,
             plan=None
             ) -> Tuple[jnp.ndarray, Dict, Optional[jnp.ndarray]]:
    """Thin interpreter over a compiled :class:`repro.api.plan.StagePlan`.

    The forward walk is *data*: ``repro.api.build`` lowers a
    PipelineSpec once (per-stage precision/backend overrides, fused
    group->transfer path) and passes the plan in; the legacy entry
    points pass ``plan=None`` and a uniform plan is lowered on the fly
    from ``cfg`` + the one resolved ``backend`` callable — bit-identical
    to the pre-IR monolithic walk (:func:`_forward_reference`, retained
    as the golden oracle).

    ``sampler`` / ``grouper`` are environment-level callables (resolved
    once by the caller); each CBR op carries its own resolved backend
    ``fn`` and deployment QuantConfig.  ``train`` preserves BN-stat
    threading: every CBR runs the stat-refreshing reference lowering
    (``_cbr_apply``) and the per-op backends are bypassed, exactly as
    before — the interpreter is written once for train and infer.
    """
    logits, new_params, lfsr_state, _ = _forward_impl(
        params, cfg, xyz, lfsr_state, train,
        sampler=sampler, grouper=grouper, backend=backend,
        shared_urs=shared_urs, per_sample_norm=per_sample_norm, plan=plan)
    return logits, new_params, lfsr_state


#: Kind of each plan op in its device-trace scope (:func:`op_scope`).
_SCOPE_KIND = {
    stage_plan.EmbedOp: "embed", stage_plan.SampleOp: "sample",
    stage_plan.GroupOp: "group", stage_plan.FusedGroupTransferOp: "group",
    stage_plan.CBROp: "transfer", stage_plan.ResBlockOp: "res",
    stage_plan.PoolOp: "pool", stage_plan.HeadOp: "head",
    stage_plan.SegHeadOp: "seghead",
}


def op_scope(op) -> str:
    """The ``jax.named_scope`` a plan op runs under: its kind, then its
    stage where it has one (``sample1``, ``group1``, ``res1``, ``head``).
    Scopes are HLO metadata (``op_name``) only, so a device trace can
    tell the plan ops apart; the group op's neighbour search runs under
    a nested ``knn`` scope (``core/knn.py``)."""
    stage = getattr(op, "stage", None)
    return (_SCOPE_KIND.get(type(op), "op")
            + ("" if stage is None else str(stage)))


def _forward_impl(params: Dict, cfg: PointMLPConfig, xyz: jnp.ndarray,
                  lfsr_state: Optional[jnp.ndarray], train: bool, *,
                  sampler, grouper, backend,
                  shared_urs: bool = False, per_sample_norm: bool = False,
                  plan=None, mapping_cache: Optional[Dict] = None,
                  collect_cache: bool = False
                  ) -> Tuple[jnp.ndarray, Dict, Optional[jnp.ndarray],
                             Optional[Dict]]:
    """:func:`_forward` plus the stream-cache plumbing.

    ``mapping_cache`` replays cached mapping results for the ops the
    plan marked ``cached``: sampled indices (stateless samplers only —
    state-advancing ones still run so the LFSR walk stays exactly the
    cold path's), kNN/ball neighbor lists, and the seg head's 1-NN
    upsample index.  ``collect_cache=True`` additionally returns the
    cache pytree ``{"sample": (idx, ...), "nbr": (nbr, ...)[, "up":
    idx]}`` (all leaves batch-leading) computed by this pass, so a
    :class:`repro.serve.streaming.StreamSession` can key future frames
    off it.  With both unset this is exactly the pre-stream walk.
    """
    if plan is None:
        plan = stage_plan.lower_config(cfg, backend)

    def run_cbr(op, p, x):
        if train:
            return _cbr_apply(p, x, cfg, True, op.act)
        return op.fn(p, x, op.quant, op.act), p

    collected_sample, collected_nbr, collected_up = [], [], None
    new_params = {k: v for k, v in params.items()}
    new_stages = [dict(st) for st in params["stages"]]
    for st in new_stages:
        st["pre"], st["pos"] = [], []
    cur_xyz, cur, idx = xyz, None, None
    embed_feats = None
    logits = None
    for op in plan.ops:
        with jax.named_scope(op_scope(op)):
            if isinstance(op, stage_plan.EmbedOp):
                cur, new_params["embed"] = run_cbr(op.cbr, params["embed"], xyz)
                embed_feats = cur
            elif isinstance(op, stage_plan.SampleOp):
                replay = (op.cached and mapping_cache is not None
                          and not getattr(sampler, "advances_state", True))
                if replay:
                    idx = mapping_cache["sample"][op.stage]
                else:
                    idx, lfsr_state = sampler(cur_xyz, op.n_samples, lfsr_state,
                                              shared_urs)
                if collect_cache:
                    collected_sample.append(idx)
            elif isinstance(op, stage_plan.GroupOp):
                affine = params["stages"][op.stage].get("affine")
                if op.cached and (mapping_cache is not None or collect_cache):
                    # Split lowering: the mapping half (neighbor_index) is
                    # replayed or collected; the arithmetic half always
                    # recomputes on the frame's features.  group_points ==
                    # group_with_idx(neighbor_index(..)) bit-for-bit.
                    new_xyz = jnp.take_along_axis(cur_xyz, idx[..., None],
                                                  axis=1)
                    if mapping_cache is not None:
                        nbr = mapping_cache["nbr"][op.stage]
                    else:
                        nbr = grouper.neighbor_index(new_xyz, cur_xyz, op.k)
                    if collect_cache:
                        collected_nbr.append(nbr)
                    cur_xyz, _, cur = grouper.group_with_idx(
                        cur_xyz, cur, idx, nbr, affine, cfg.affine_mode,
                        per_sample_norm)
                else:
                    cur_xyz, _, cur = grouper(cur_xyz, cur, idx, op.k, affine,
                                              cfg.affine_mode, per_sample_norm)
            elif isinstance(op, stage_plan.CBROp):
                # Bare CBR ops are stage transfers (embed/head CBRs ride
                # inside their wrapper ops).
                p = stage_plan.param_at(params, op.path)
                cur, new_stages[op.stage]["transfer"] = run_cbr(op, p, cur)
            elif isinstance(op, stage_plan.FusedGroupTransferOp):
                if train:
                    raise ValueError(
                        "fused group->transfer ops are inference-only; "
                        "train with fused_group='none'")
                affine = params["stages"][op.stage].get("affine")
                p = stage_plan.param_at(params, op.cbr.path)
                cur_xyz, _, cur = op.fn(p, cur_xyz, cur, idx, op.k, affine,
                                        cfg.affine_mode, per_sample_norm,
                                        act=op.cbr.act)
            elif isinstance(op, stage_plan.ResBlockOp):
                blk = params["stages"][op.stage][op.branch][op.index]
                h, n1 = run_cbr(op.net1, blk["net1"], cur)
                h, n2 = run_cbr(op.net2, blk["net2"], h)
                cur = jax.nn.relu(h + cur)
                new_stages[op.stage][op.branch].append({"net1": n1, "net2": n2})
            elif isinstance(op, stage_plan.PoolOp):
                cur = jnp.max(cur, axis=op.axis)
            elif isinstance(op, stage_plan.HeadOp):
                head = params["head"]
                h, f1 = run_cbr(op.fc1, head["fc1"], cur)
                h, f2 = run_cbr(op.fc2, head["fc2"], h)
                fc3_quant = ((cfg.quant if cfg.quant.enabled else None)
                             if train else op.fc3_quant)
                logits = L.conv1d_apply(head["fc3"], h, quant=fc3_quant)
                new_params["head"] = {"fc1": f1, "fc2": f2, "fc3": head["fc3"]}
            elif isinstance(op, stage_plan.SegHeadOp):
                # Per-point segmentation head: global descriptor pooled
                # here (no standalone global PoolOp in seg plans), final
                # stage features upsampled back to input resolution by
                # 1-NN (the cacheable mapping op), skip concat, 3-layer
                # per-point classifier -> [B, n_points, n_classes].
                g = jnp.max(cur, axis=1)                           # [B, C4]
                replay = op.cached and mapping_cache is not None
                if replay:
                    up_idx = mapping_cache["up"]
                else:
                    with jax.named_scope("knn"):
                        up_idx = knn_core.knn_batched(xyz, cur_xyz, 1)
                if collect_cache:
                    collected_up = up_idx
                upsampled = knn_core.gather_neighbors(cur, up_idx)[:, :, 0, :]
                g_b = jnp.broadcast_to(g[:, None, :],
                                       upsampled.shape[:2] + (g.shape[-1],))
                h = jnp.concatenate([embed_feats, upsampled, g_b], axis=-1)
                head = params["head"]
                h, f1 = run_cbr(op.fc1, head["fc1"], h)
                h, f2 = run_cbr(op.fc2, head["fc2"], h)
                fc3_quant = ((cfg.quant if cfg.quant.enabled else None)
                             if train else op.fc3_quant)
                logits = L.conv1d_apply(head["fc3"], h, quant=fc3_quant)
                new_params["head"] = {"fc1": f1, "fc2": f2, "fc3": head["fc3"]}
            else:
                raise TypeError(f"unknown stage-plan op {type(op).__name__}")
    new_params["stages"] = new_stages
    cache = None
    if collect_cache:
        cache = {"sample": tuple(collected_sample),
                 "nbr": tuple(collected_nbr)}
        if collected_up is not None:
            cache["up"] = collected_up
    return logits, new_params, lfsr_state, cache


def pointmlp_infer_with(params: Dict, cfg: PointMLPConfig,
                        xyz: jnp.ndarray,
                        lfsr_state: Optional[jnp.ndarray] = None, *,
                        sampler, grouper, backend,
                        shared_urs: bool = False,
                        per_sample_norm: bool = False,
                        plan=None, mapping_cache: Optional[Dict] = None,
                        collect_cache: bool = False):
    """Inference forward over resolved pipeline components.

    The spec-era hot path: ``repro.api.build`` resolves a
    :class:`~repro.api.spec.PipelineSpec`'s registry keys, lowers the
    stage plan once (``plan``; None lowers a uniform plan from ``cfg``)
    and jits this entry.  No BN-stat threading and no new-params return
    — with fused params every CBR is a single matmul+bias+ReLU lowered
    by its op's backend.

    Under full serving semantics (``shared_urs`` *and*
    ``per_sample_norm``) lanes are mathematically independent — one
    index sequence serves the batch, every cloud normalizes with its
    own statistics — and the walk is lowered as a ``lax.map`` over
    lanes: each lane runs a single-cloud executable traced once at a
    fixed shape, so a lane's logits are *bitwise* independent of the
    dispatch batch size.  That is the serving engines' dispatch-
    invariance contract made shape-independent, and what makes a
    ``data_shards``-split dispatch (``repro.serve.sharding``) golden-
    equivalent to the single-device one: XLA's gemm reduction blocking
    is batch-shape-dependent, so the batched lowering is bit-identical
    only within one dispatch shape.  FLOPs are unchanged (the batch
    dim only ever widens gemm M; every per-lane gemm keeps its full
    S*k spatial extent); the scan serializes lanes on one device for a
    ~10% dispatch-time cost at batch 8 on CPU — recovered many times
    over once ``data_shards`` spreads the lanes across devices.

    Stream-cache kwargs: ``mapping_cache`` (a batch-leading cache
    pytree from a prior ``collect_cache`` pass) replays cached mapping
    indices on the ops the plan marked ``cached``; ``collect_cache``
    appends the computed cache pytree to the return tuple —
    ``(logits, state, cache)`` instead of ``(logits, state)``.

    Returns: (logits, advanced lfsr state[, collected cache]) —
    logits [B, n_classes] for the cls head, [B, n_points, n_classes]
    for the seg head.
    """
    if plan is None:
        plan = stage_plan.lower_config(cfg, backend)
    if shared_urs and per_sample_norm:
        def lane(args):
            cloud, mc = args
            mc = (None if mc is None else
                  jax.tree_util.tree_map(lambda a: a[None], mc))
            logits, _, state, cache = _forward_impl(
                params, cfg, cloud[None], lfsr_state, train=False,
                sampler=sampler, grouper=grouper, backend=backend,
                shared_urs=True, per_sample_norm=True, plan=plan,
                mapping_cache=mc, collect_cache=collect_cache)
            if collect_cache:
                cache = jax.tree_util.tree_map(lambda a: a[0], cache)
            return logits[0], state, cache

        logits, states, caches = jax.lax.map(lane, (xyz, mapping_cache))
        state_out = (None if lfsr_state is None else
                     # Every lane advances the shared state identically;
                     # return one.
                     jax.tree_util.tree_map(lambda s: s[0], states))
        if collect_cache:
            return logits, state_out, caches
        return logits, state_out
    logits, _, lfsr_state, cache = _forward_impl(
        params, cfg, xyz, lfsr_state, train=False, sampler=sampler,
        grouper=grouper, backend=backend, shared_urs=shared_urs,
        per_sample_norm=per_sample_norm, plan=plan,
        mapping_cache=mapping_cache, collect_cache=collect_cache)
    if collect_cache:
        return logits, lfsr_state, cache
    return logits, lfsr_state


def pointmlp_infer(params: Dict, cfg: PointMLPConfig, xyz: jnp.ndarray,
                   lfsr_state: Optional[jnp.ndarray] = None,
                   use_pallas: bool = False, shared_urs: bool = False,
                   per_sample_norm: bool = False
                   ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Pure inference forward — legacy kwarg surface.

    Thin resolver over :func:`pointmlp_infer_with`: ``cfg.sampler`` and
    ``use_pallas`` are mapped to registry entries (``use_pallas`` names
    the interpret-mode fused kernel — the CPU correctness canary).  New
    code should build a :class:`~repro.api.spec.PipelineSpec` and use
    ``repro.api.build`` instead.

    Args:
      xyz: [B, N, 3] point coordinates (N == cfg.n_points).
      lfsr_state: uint32 [>=B] LFSR streams (URS sampler only).
      use_pallas: route fused fp32 CBR layers through
        ``repro.kernels.fused_linear`` (interpret mode on CPU).
      shared_urs: one URS index sequence shared across the batch
        (slot-invariant results; used by the serving engine).
      per_sample_norm: per-cloud geometric-affine sigma (streaming
        deployment semantics — co-batched requests fully decoupled).

    Returns: (logits [B, n_classes], advanced lfsr state).
    """
    sampler, grouper, backend = api_registry.resolve(
        cfg.sampler, "knn", "pallas_interpret" if use_pallas else "ref")
    return pointmlp_infer_with(params, cfg, xyz, lfsr_state,
                               sampler=sampler, grouper=grouper,
                               backend=backend, shared_urs=shared_urs,
                               per_sample_norm=per_sample_norm)


def pointmlp_apply(params: Dict, cfg: PointMLPConfig, xyz: jnp.ndarray,
                   lfsr_state: Optional[jnp.ndarray] = None,
                   train: bool = False
                   ) -> Tuple[jnp.ndarray, Dict, Optional[jnp.ndarray]]:
    """Training-facing forward (thin wrapper over the shared walk).

    Args:
      xyz: [B, N, 3] point coordinates (N == cfg.n_points).
      lfsr_state: uint32 [>=B] LFSR streams (URS sampler only).

    Returns: (logits [B, n_classes], updated params (BN stats), lfsr state).
    In eval mode the params pass through unchanged (pure inference path).
    """
    if not train:
        logits, lfsr_state = pointmlp_infer(params, cfg, xyz, lfsr_state)
        return logits, params, lfsr_state
    sampler, grouper, backend = api_registry.resolve(cfg.sampler, "knn",
                                                     "ref")
    return _forward(params, cfg, xyz, lfsr_state, train=True,
                    sampler=sampler, grouper=grouper, backend=backend)


def pointmlp_flops_breakdown(cfg: PointMLPConfig) -> Dict[str, int]:
    """Analytic MAC*2 count per sample, itemized per stage op.

    Keys follow the stage-plan op naming (``embed``,
    ``stage<i>.{group,transfer,pre,pos}``, ``head``); the values sum to
    exactly :func:`pointmlp_flops` — same arithmetic, one accumulator
    per op instead of one total.
    """
    fl: Dict[str, int] = {}
    n = cfg.n_points
    fl["embed"] = 2 * n * 3 * cfg.embed_dim
    c_prev = cfg.embed_dim
    for s in range(4):
        smp, c = cfg.stage_samples[s], cfg.stage_dims[s]
        k = cfg.k_neighbors
        # knn distances: S x N x C MACs
        fl[f"stage{s + 1}.group"] = 2 * smp * n * 3
        fl[f"stage{s + 1}.transfer"] = 2 * smp * k * (2 * c_prev) * c
        mid = max(1, int(c * cfg.res_expansion))
        fl[f"stage{s + 1}.pre"] = (cfg.pre_blocks[s] * 2 * smp * k
                                   * (c * mid + mid * c))
        fl[f"stage{s + 1}.pos"] = (cfg.pos_blocks[s] * 2 * smp
                                   * (c * mid + mid * c))
        n, c_prev = smp, c
    if cfg.head == "seg":
        # 1-NN upsample distances (n_points x S4 x 3 MACs) + the
        # per-point classifier over the [E + 2*C4] skip concat.
        n0 = cfg.n_points
        fl["head"] = (2 * n0 * n * 3
                      + 2 * n0 * ((cfg.embed_dim + 2 * c_prev) * 512
                                  + 512 * 256 + 256 * cfg.n_classes))
    else:
        fl["head"] = 2 * (c_prev * 512 + 512 * 256 + 256 * cfg.n_classes)
    return {op: int(v) for op, v in fl.items()}


def pointmlp_flops(cfg: PointMLPConfig) -> int:
    """Analytic MAC*2 count per sample (for GOPS derivations, Table 2/3)."""
    return sum(pointmlp_flops_breakdown(cfg).values())
