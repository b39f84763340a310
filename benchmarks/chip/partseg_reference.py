"""Plain PointMLP part-segmentation reference: the forward pass the
``pointmlp-partseg`` configuration's answers are judged against.

Straightforward ``jax.numpy`` in float32, one cloud at a time and
``vmap``-ed over a block of clouds, written from the published model
(Ma et al., ICLR 2022, arXiv:2202.07123; ``ma-xu/pointMLP-pytorch``,
``part_segmentation/model/pointMLP.py``, factory ``pointMLP()``) with BN
folded into the convs.  It imports nothing of the program under test:
the weights are drawn from the same seed by a copy of the program's
init, key for key, and folded here.  With ``N`` points of xyz and
normals and a category per cloud:

- embedding: ``f0 = CBR(6 -> E)`` per point;
- 4 encoder stages: ``S = N / r^i`` FPS samples, their ``k`` nearest
  points, neighbours ``[f, xyz]`` less the anchor's, divided by one
  RMS per cloud (``sigma + 1e-5``), times alpha plus beta; the anchor's
  features appended (``2d + 3`` channels); a transfer CBR, residual
  blocks, max over the neighbours, residual blocks;
- 4 decoder levels, coarse to fine: each finer point's 3 nearest coarser
  points (fewer where the coarser level has fewer), weighted by
  ``1 / (d + 1e-8)`` normalized, ``d`` the squared distance by direct
  differences; ``CBR([skip, interpolation])`` and residual blocks;
- context: per encoder level (coarse first) a CBR max-pooled over its
  points, their concat through one more CBR; category: its one-hot
  through two CBRs;
- per point ``[decoder, context, category]`` through the classifier
  (conv and BN, no ReLU; dropout is the identity at inference) and the
  output conv, then a log-softmax over the parts: answers ``[N, parts]``.

Each departure from the published code is under ``assumed`` in
``configs/pointmlp-partseg.json``.  ``mode`` names the arithmetic of
the float32 matmuls, as in ``reference.py``.  It provides the contract
``harness.CONTRACT`` names (``reference.py`` documents it): a request
is ``(points [N, 6], category)``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import partseg_decisions
from traffic import clouds as cloud_traffic
from work import Layer

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS = 1e-5
NORM_EPS = 1e-5
INTERP_EPS = 1e-8
#: Neighbours (the point included) whose spread gives a point's normal.
NORMAL_K = 16
#: The program's init splits its key this many ways.
N_KEYS = 64
N_STAGES = partseg_decisions.N_STAGES
NN = partseg_decisions.NN
stage_samples = partseg_decisions.stage_samples


# ------------------------------------------------------------ shapes ----

def stage_dims(c: Dict) -> List[int]:
    dims, d = [], c["embed_dim"]
    for e in c["stage_expansion"]:
        d *= e
        dims.append(d)
    return dims


def level_points(c: Dict) -> List[int]:
    """Points of each encoder level, finest first."""
    return [c["n_points"]] + stage_samples(c)


def level_dims(c: Dict) -> List[int]:
    """Feature channels of each encoder level, finest first."""
    return [c["embed_dim"]] + stage_dims(c)


def res_mid(c: Dict, ch: int) -> int:
    return max(1, int(ch * c["res_expansion"]))


# -------------------------------------------------------------- init ----

def _conv(key, c_in: int, c_out: int, bn: bool = True) -> Dict:
    p = {"w": jax.random.normal(key, (c_in, c_out)) * (1.0 / math.sqrt(c_in)),
         "b": jnp.zeros((c_out,), jnp.float32)}
    if bn:
        p["bn"] = {"gamma": jnp.ones((c_out,)), "beta": jnp.zeros((c_out,)),
                   "mean": jnp.zeros((c_out,)), "var": jnp.ones((c_out,))}
    return p


def _block(key, c: Dict, ch: int) -> Dict:
    k1, k2 = jax.random.split(key)
    mid = res_mid(c, ch)
    return {"net1": _conv(k1, ch, mid), "net2": _conv(k2, mid, ch)}


def init_params(key, c: Dict) -> Dict:
    """The model's random init (conv weights N(0, 1/c_in), zero bias,
    identity BN, alpha=1 / beta=0), key for key as the program draws
    it: embedding, each stage's transfer and blocks, each decoder
    level's fuse and blocks, the context maps (coarse first) and their
    merge, the category maps, the classifier and the output conv."""
    keys = iter(jax.random.split(key, N_KEYS))
    params = {"embed": _conv(next(keys), c["in_channels"], c["embed_dim"])}
    stages, c_prev = [], c["embed_dim"]
    for s, ch in enumerate(stage_dims(c)):
        wide = c_prev + 3
        st = {"affine": {"alpha": jnp.ones((wide,)),
                         "beta": jnp.zeros((wide,))},
              "transfer": _conv(next(keys), c_prev + wide, ch)}
        for branch in ("pre", "pos"):
            st[branch] = [_block(next(keys), c, ch)
                          for _ in range(c[f"{branch}_blocks"][s])]
        stages.append(st)
        c_prev = ch
    params["stages"] = stages
    enc, decoder = level_dims(c), []
    for j, (width, blocks) in enumerate(c["decoder"]):
        decoder.append({"fuse": _conv(next(keys), c_prev + enc[-2 - j],
                                      width),
                        "blocks": [_block(next(keys), c, width)
                                   for _ in range(blocks)]})
        c_prev = width
    params["decoder"] = decoder
    ctx, cat = c["context_dims"]
    head = {"context": [_conv(next(keys), ch, ctx) for ch in reversed(enc)]}
    head["context_out"] = _conv(next(keys), len(enc) * ctx, ctx)
    head["category"] = [_conv(next(keys), c["n_categories"], cat),
                        _conv(next(keys), cat, cat)]
    head["classifier"] = _conv(next(keys), c_prev + ctx + cat, 128)
    head["out"] = _conv(next(keys), 128, c["n_classes"], bn=False)
    params["head"] = head
    return params


def fold_bn(p):
    """Fold every conv's BN into its (w, b)."""
    if isinstance(p, list):
        return [fold_bn(v) for v in p]
    if not isinstance(p, dict):
        return p
    if "bn" in p:
        bn = p["bn"]
        g = bn["gamma"] * jax.lax.rsqrt(bn["var"] + BN_EPS)
        return {"w": p["w"] * g, "b": (p["b"] - bn["mean"]) * g + bn["beta"]}
    return {k: fold_bn(v) for k, v in p.items()}


#: Configuration keys the weights and the forward depend on.
SHAPE_KEYS = ("n_points", "n_classes", "n_categories", "in_channels",
              "embed_dim", "k_neighbors", "stage_reduction",
              "stage_expansion", "pre_blocks", "pos_blocks", "res_expansion",
              "decoder", "context_dims")


def _freeze(c: Dict) -> tuple:
    def hashable(v):
        return tuple(map(hashable, v)) if isinstance(v, (list, tuple)) else v
    return tuple(sorted((k, hashable(c[k])) for k in SHAPE_KEYS))


@functools.partial(jax.jit, static_argnums=1)
def _init(key, cfg_items):
    return init_params(key, dict(cfg_items))


def deploy_params(key, c: Dict, bits: Optional[int] = None) -> Dict:
    """Init (in one jitted call, as the served weights are made) and
    fold BN.  Only the published grouping is written here."""
    if not (c["use_xyz"] and c["affine_mode"] == "affine"
            and c["precision"] == "fp32"):
        raise ValueError("partseg_reference writes the published model: "
                         "use_xyz, affine grouping, float32")
    return fold_bn(_init(key, _freeze(c)))


# ---------------------------------------------------------- arithmetic ----

def fdot(x, w, mode: str):
    """float32 [..., K] @ [K, N] in the named arithmetic."""
    if mode == "highest":
        return jnp.matmul(x, w, precision=HIGHEST)
    if mode == "default":
        return jnp.matmul(x, w, precision=jax.lax.Precision.DEFAULT)
    if mode == "high":
        def split(a):
            # reduce_precision, not a cast pair, which XLA may elide
            hi = jax.lax.reduce_precision(a, exponent_bits=8,
                                          mantissa_bits=7)
            lo = jax.lax.reduce_precision(a - hi, exponent_bits=8,
                                          mantissa_bits=7)
            return hi, lo
        xh, xl = split(x)
        wh, wl = split(w)
        mm = functools.partial(jnp.matmul, precision=HIGHEST)
        return mm(xh, wh) + (mm(xh, wl) + mm(xl, wh))
    raise ValueError(f"unknown matmul mode {mode!r}")


# ------------------------------------------------------------ mapping ----

def fps(xyz, n_samples: int):
    """Farthest point sampling from index 0: [N, 3] -> [S] int32."""
    n = xyz.shape[0]

    def body(i, carry):
        dist, idx = carry
        d = jnp.sum((xyz - xyz[idx[i - 1]]) ** 2, axis=-1)
        dist = jnp.minimum(dist, d)
        return dist, idx.at[i].set(jnp.argmax(dist).astype(jnp.int32))

    init = (jnp.full((n,), jnp.inf, jnp.float32),
            jnp.zeros((n_samples,), jnp.int32))
    return jax.lax.fori_loop(1, n_samples, body, init)[1]


def nearest(centres, xyz, k: int, mode: str):
    """[S, 3], [N, 3] -> [S, k]: the k nearest points of each centre by
    squared distance (|s|^2 - 2 s.p + |p|^2)."""
    d = (jnp.sum(centres * centres, -1)[:, None]
         - 2.0 * fdot(centres, xyz.T, mode)
         + jnp.sum(xyz * xyz, -1)[None, :])
    return jax.lax.top_k(-d, k)[1].astype(jnp.int32)


def group(xyz, feats, idx, nbr, affine):
    """Neighbours' features and xyz less the anchor's, over the cloud's
    RMS, times alpha plus beta; the anchor's features appended:
    -> ([S, 3], [S, k, 2C + 3])."""
    new_xyz, centre_f = xyz[idx], feats[idx]
    anchor = jnp.concatenate([centre_f, new_xyz], -1)
    off = jnp.concatenate([feats[nbr], xyz[nbr]], -1) - anchor[:, None, :]
    sigma = jnp.sqrt(jnp.mean(off * off) + NORM_EPS)
    g = off / (sigma + NORM_EPS) * affine["alpha"] + affine["beta"]
    centre_b = jnp.broadcast_to(centre_f[:, None, :],
                                g.shape[:2] + centre_f.shape[-1:])
    return new_xyz, jnp.concatenate([g, centre_b], axis=-1)


def interpolate(fine_xyz, coarse_xyz, coarse_f, up):
    """Inverse squared distance weights of each fine point's chosen
    coarse sources ``up`` [F, m], distances by direct differences."""
    d = jnp.sum((coarse_xyz[up] - fine_xyz[:, None, :]) ** 2, axis=-1)
    w = 1.0 / (d + INTERP_EPS)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.sum(coarse_f[up] * w[..., None], axis=1)


# ------------------------------------------------------------ forward ----

def forward_one(params: Dict, c: Dict, points, category, cache, *,
                mode: str):
    """One cloud ``points`` [N, 6] of category ``category`` ->
    (log-probabilities [N, parts], (FPS idx, kNN nbr, 3-NN sources) per
    stage and level).  ``cache`` replays those decisions."""
    def cbr(p, x, act=True):
        y = fdot(x, p["w"], mode) + p["b"]
        return jax.nn.relu(y) if act else y

    def res(p, x):
        return jax.nn.relu(cbr(p["net2"], cbr(p["net1"], x), False) + x)

    xyz = points[:, :3]
    feats = cbr(params["embed"], points)
    levels = [(xyz, feats)]
    idx_t, nbr_t, up_t = [], [], []
    for s, st in enumerate(params["stages"]):
        cur_xyz = levels[-1][0]
        if cache is not None:
            idx, nbr = cache[0][s], cache[1][s]
        else:
            idx = fps(cur_xyz, stage_samples(c)[s])
            nbr = nearest(cur_xyz[idx], cur_xyz, c["k_neighbors"], mode)
        idx_t.append(idx)
        nbr_t.append(nbr)
        new_xyz, h = group(cur_xyz, feats, idx, nbr, st["affine"])
        h = cbr(st["transfer"], h)
        for blk in st["pre"]:
            h = res(blk, h)
        h = jnp.max(h, axis=1)
        for blk in st["pos"]:
            h = res(blk, h)
        feats = h
        levels.append((new_xyz, feats))
    x = feats
    for j, lvl in enumerate(params["decoder"]):
        (fine_xyz, skip), (coarse_xyz, _) = levels[-2 - j], levels[-1 - j]
        if cache is not None:
            up = cache[2][j]
        else:
            up = nearest(fine_xyz, coarse_xyz, min(NN, len(coarse_xyz)),
                         mode)
        up_t.append(up)
        x = cbr(lvl["fuse"], jnp.concatenate(
            [skip, interpolate(fine_xyz, coarse_xyz, x, up)], -1))
        for blk in lvl["blocks"]:
            x = res(blk, x)
    hd = params["head"]
    pooled = [jnp.max(cbr(p, f), axis=0)
              for p, (_, f) in zip(hd["context"], reversed(levels))]
    ctx = cbr(hd["context_out"], jnp.concatenate(pooled))
    cat = jax.nn.one_hot(category, c["n_categories"], dtype=jnp.float32)
    for p in hd["category"]:
        cat = cbr(p, cat)
    n = x.shape[0]
    h = jnp.concatenate([x, jnp.broadcast_to(ctx, (n,) + ctx.shape),
                         jnp.broadcast_to(cat, (n,) + cat.shape)], -1)
    logits = cbr(hd["out"], cbr(hd["classifier"], h, False), False)
    return (jax.nn.log_softmax(logits, axis=-1),
            (tuple(idx_t), tuple(nbr_t), tuple(up_t)))


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _forward_block(params, points, category, cache, cfg_items, mode):
    c = dict(cfg_items)
    return jax.vmap(lambda p, k, ch: forward_one(params, c, p, k, ch,
                                                 mode=mode),
                    in_axes=(0, 0, None if cache is None else 0))(
                        points, category, cache)


def forward(params: Dict, c: Dict, inputs: Tuple[np.ndarray, np.ndarray], *,
            lfsr_seed: int, mode: str = "highest",
            bits: Optional[int] = None, cache=None, block: int = 16):
    """Answers of the requests ``inputs = (points [R, N, 6], category
    [R])`` (and the decisions they used), in blocks of ``block`` clouds
    so that any R fits."""
    points, category = inputs
    items = _freeze(c)

    def rows(a, i):
        """Rows [i, i + block) of ``a``, the last block padded with its
        own first row, so that every block has one shape."""
        a = np.asarray(a[i:i + block])
        return np.concatenate([a, np.repeat(a[:1], block - len(a), 0)])

    answers, caches = [], []
    for i in range(0, len(points), block):
        n = min(block, len(points) - i)
        sub = None if cache is None else jax.tree_util.tree_map(
            lambda a: rows(a, i), cache)
        out, ch = _forward_block(params, jnp.asarray(rows(points, i)),
                                 jnp.asarray(rows(category, i)), sub, items,
                                 mode)
        answers.append(np.asarray(out)[:n])
        caches.append(jax.tree_util.tree_map(lambda a: np.asarray(a)[:n], ch))
    cat = jax.tree_util.tree_map(lambda *a: np.concatenate(a), *caches)
    return np.concatenate(answers), cat


# ----------------------------------------------- rest of the contract ----

def _normals(xyz):
    """Unit normal of each point of one cloud [N, 3]: the direction of
    least spread of its ``NORMAL_K`` nearest points (sign arbitrary)."""
    d = (jnp.sum(xyz * xyz, -1)[:, None]
         - 2.0 * jnp.matmul(xyz, xyz.T, precision=HIGHEST)
         + jnp.sum(xyz * xyz, -1)[None, :])
    nb = xyz[jax.lax.top_k(-d, NORMAL_K)[1]]                 # [N, K, 3]
    off = nb - jnp.mean(nb, axis=1, keepdims=True)
    cov = jnp.einsum("nki,nkj->nij", off, off, precision=HIGHEST)
    return jnp.linalg.eigh(cov)[1][..., 0]


@jax.jit
def pca_normals(xyz):
    """[P, N, 3] -> [P, N, 3], one cloud at a time."""
    return jax.lax.map(_normals, xyz)


def make_pool(key, c: Dict, size: int) -> Tuple[np.ndarray, np.ndarray]:
    """``size`` requests: synthetic clouds with PCA normals
    ``[size, N, 6]``, and categories uniform over ``n_categories``."""
    xyz = cloud_traffic.make_batch(key, c["n_points"], size)
    points = jnp.concatenate([xyz, pca_normals(xyz)], -1)
    category = jax.random.randint(jax.random.fold_in(key, 1), (size,), 0,
                                  c["n_categories"], jnp.int32)
    return points, category


def decision_paths(c: Dict, deciders: Tuple[np.ndarray, np.ndarray],
                   lfsr_seed: int, rnd) -> List[List[partseg_decisions.Path]]:
    """Per request of ``deciders``, the (FPS, kNN, 3-NN) choices of every
    decision path :func:`partseg_decisions.paths` lists for its xyz."""
    return [partseg_decisions.paths(c, p[:, :3], rnd) for p in deciders[0]]


def cbr_layers(c: Dict) -> List[Layer]:
    """Every conv layer of one cloud's forward, in order."""
    k, pts, enc = c["k_neighbors"], level_points(c), level_dims(c)
    out = [Layer("embed", c["n_points"], c["in_channels"], c["embed_dim"])]

    def blocks(name, rows, ch, count):
        mid = res_mid(c, ch)
        for i in range(count):
            out.append(Layer(f"{name}{i}.net1", rows, ch, mid))
            out.append(Layer(f"{name}{i}.net2", rows, mid, ch))

    for s in range(N_STAGES):
        smp, ch, c_prev = pts[s + 1], enc[s + 1], enc[s]
        out.append(Layer(f"stage{s + 1}.transfer", smp * k, 2 * c_prev + 3,
                         ch))
        blocks(f"stage{s + 1}.pre", smp * k, ch, c["pre_blocks"][s])
        blocks(f"stage{s + 1}.pos", smp, ch, c["pos_blocks"][s])
    c_prev = enc[-1]
    for j, (width, count) in enumerate(c["decoder"]):
        fine = pts[-2 - j]
        out.append(Layer(f"fp{j + 1}.fuse", fine, c_prev + enc[-2 - j], width))
        blocks(f"fp{j + 1}.block", fine, width, count)
        c_prev = width
    ctx, cat = c["context_dims"]
    for lvl, (n, ch) in enumerate(zip(reversed(pts), reversed(enc))):
        out.append(Layer(f"context{lvl}", n, ch, ctx))
    out += [Layer("context_out", 1, len(enc) * ctx, ctx),
            Layer("category0", 1, c["n_categories"], cat),
            Layer("category1", 1, cat, cat),
            Layer("classifier", c["n_points"], c_prev + ctx + cat, 128),
            Layer("out", c["n_points"], 128, c["n_classes"])]
    return out


def fps_flops(c: Dict) -> int:
    """FPS distance updates: each step, one squared distance (3
    multiply-adds) per point of the level."""
    pts = level_points(c)
    return sum(2 * 3 * n * m for n, m in zip(pts, pts[1:]))


def knn_flops(c: Dict) -> int:
    """The kNN distance matmuls: 2*S*N*3 per stage."""
    pts = level_points(c)
    return sum(2 * m * n * 3 for n, m in zip(pts, pts[1:]))


def nn3_flops(c: Dict) -> int:
    """The decoder's 3-NN distance matmuls: 2*F*S*3 per level."""
    pts = level_points(c)
    return sum(2 * fine * coarse * 3 for fine, coarse in zip(pts, pts[1:]))


def mapping_flops(c: Dict) -> int:
    """FPS, kNN and 3-NN distance work of one cloud."""
    return fps_flops(c) + knn_flops(c) + nn3_flops(c)
