"""Plain PointMLP reference: the forward pass the benchmark's outputs are
judged against.

Straightforward ``jax.numpy`` written from the model's description
(PointMLP, Ma et al. 2022, as cut down by HLS4PC: BN folded into the
convs, optional W8A8), one cloud at a time and ``vmap``-ed over a
block of clouds.  It imports nothing of the program under test: the
weights are drawn from the same seed by a copy of the model's init,
folded and quantized here, and the samplers, kNN and grouping are
written out again.  The serving semantics it follows are the
deployment's: one LFSR sequence shared by every cloud, normalization
statistics per cloud, and, for a stream frame that replays its key
frame, the key frame's sample and neighbour indices.

It is the ``reference`` module of both PointMLP classification
configurations, and so provides the contract through which the harness
reaches everything model-specific (``harness.CONTRACT``):

- ``make_pool(key, c, size)``: the traffic's inputs, a tuple of arrays
  with a leading request axis; here ``(xyz [size, N, 3],)``.  A request
  is sent as ``submit(*payload)``, one row of each array.
- ``deploy_params(key, c, bits)``: the reference's weights from a key.
- ``forward(params, c, inputs, *, lfsr_seed, mode, bits, cache)``: the
  answers ``[B, ...]`` of the stacked payload ``inputs`` and the cache
  of decisions they used (replayed where ``cache`` is given).
- ``decision_paths(c, deciders, lfsr_seed, rnd)``: per input of the
  stacked payload ``deciders``, the caches of every decision path that
  float32 rounding leaves open (``decisions.py``).
- ``cbr_layers(c)``, ``mapping_flops(c)``: the work of one input, from
  the configuration's shapes (``work.py`` sums and prices them).

``mode`` names the arithmetic of the fp32 matmuls: ``"highest"`` is
full float32; ``"high"`` is the three-pass bfloat16 product
(hi*hi + hi*lo + lo*hi), written out so that it means the same on
every platform; ``"default"`` is the platform's own default matmul
precision, for a configuration that states no precision (on a TPU one
bfloat16 pass, on the CPU float32).  ``bits`` is the width of the quantized layers of an
int8 configuration (8 as served; 4 for the control).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import decisions
from traffic import clouds as cloud_traffic
from work import Layer

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS = 1e-5
NORM_EPS = 1e-5
LFSR_TAPS16 = 0xB400


# ------------------------------------------------------------ shapes ----

def stage_samples(c: Dict) -> List[int]:
    return [c["n_points"] // 2 ** (i + 1) for i in range(4)]


def stage_dims(c: Dict) -> List[int]:
    dims, d = [], c["embed_dim"]
    for e in c["stage_expansion"]:
        d *= e
        dims.append(d)
    return dims


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


def init_params(key, c: Dict) -> Dict:
    """The model's random init (conv weights N(0, 1/c_in), zero bias,
    identity BN, alpha=1 / beta=0), key for key as the model draws it."""
    keys = jax.random.split(key, 64)
    ki = iter(range(64))
    params = {"embed": _conv(keys[next(ki)], 3, c["embed_dim"])}
    c_prev, stages = c["embed_dim"], []
    for s in range(4):
        c_out = stage_dims(c)[s]
        st = {}
        if c["affine_mode"] == "affine":
            st["affine"] = {"alpha": jnp.ones((c_prev,)),
                            "beta": jnp.zeros((c_prev,))}
        st["transfer"] = _conv(keys[next(ki)], 2 * c_prev, c_out)
        for branch in ("pre", "pos"):
            blocks = []
            for _ in range(c[f"{branch}_blocks"][s]):
                k1, k2 = jax.random.split(keys[next(ki)])
                mid = res_mid(c, c_out)
                blocks.append({"net1": _conv(k1, c_out, mid),
                               "net2": _conv(k2, mid, c_out)})
            st[branch] = blocks
        stages.append(st)
        c_prev = c_out
    params["stages"] = stages
    k1, k2, k3 = (keys[next(ki)] for _ in range(3))
    params["head"] = {"fc1": _conv(k1, c_prev, 512),
                      "fc2": _conv(k2, 512, 256),
                      "fc3": _conv(k3, 256, c["n_classes"], bn=False)}
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


def quantize_weights(p, bits: int):
    """Every conv weight to symmetric per-output-channel integers."""
    if isinstance(p, list):
        return [quantize_weights(v, bits) for v in p]
    if not isinstance(p, dict):
        return p
    if "w" in p and "b" in p:
        qmax = 2 ** (bits - 1) - 1
        w = p["w"]
        scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8) / qmax
        q = jnp.clip(jnp.round(w / scale), -qmax - 1, qmax)
        return {"wq": q.astype(jnp.int32), "scale": scale, "b": p["b"]}
    return {k: quantize_weights(v, bits) for k, v in p.items()}


@functools.partial(jax.jit, static_argnums=1)
def _init(key, cfg_items):
    return init_params(key, dict(cfg_items))


def deploy_params(key, c: Dict, bits: Optional[int] = None) -> Dict:
    """Init (in one jitted call, as the served weights are made), fold BN
    and, for an int8 configuration, quantize."""
    keys = ("n_points", "n_classes", "embed_dim", "k_neighbors",
            "stage_expansion", "pre_blocks", "pos_blocks", "res_expansion",
            "affine_mode")
    p = fold_bn(_init(key, _freeze({k: c[k] for k in keys})))
    if c["precision"] == "int8":
        p = quantize_weights(p, bits or c["w_bits"])
    return p


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


def conv(p: Dict, x, act: bool, mode: str, bits: Optional[int]):
    """One pointwise conv (+ReLU).  Quantized layers quantize their
    input per tensor (one cloud's tensor) and accumulate in int32."""
    if "wq" in p:
        qmax = 2 ** (bits - 1) - 1
        a_scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / qmax
        xq = jnp.clip(jnp.round(x / a_scale), -qmax - 1, qmax)
        acc = jax.lax.dot_general(
            xq.astype(jnp.int32), p["wq"],
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        y = acc.astype(jnp.float32) * (a_scale * p["scale"])
    else:
        y = fdot(x, p["w"], mode)
    y = y + p["b"]
    return jax.nn.relu(y) if act else y


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


def lfsr_indices(seed: int, sizes: List[Tuple[int, int]]) -> List[np.ndarray]:
    """The shared URS index sequence: a 16-bit Galois LFSR seeded from
    ``seed`` (stream 0), its successive words mod the stage's point
    count.  ``sizes`` is [(n_points, n_samples)] per stage."""
    s = ((seed & 0xFFFFFFFF) * 2654435761) & 0xFFFFFFFF
    s = (s >> 4) & 0xFFFF
    s = s or 1
    out = []
    for n, m in sizes:
        idx = np.empty(m, np.int32)
        for j in range(m):
            s = (s >> 1) ^ LFSR_TAPS16 if s & 1 else s >> 1
            idx[j] = s % n
        out.append(idx)
    return out


def knn(centres, xyz, k: int, mode: str):
    """[S, 3], [N, 3] -> [S, k]: the k nearest points of each centre by
    squared distance (|s|^2 - 2 s.p + |p|^2)."""
    d = (jnp.sum(centres * centres, -1)[:, None]
         - 2.0 * fdot(centres, xyz.T, mode)
         + jnp.sum(xyz * xyz, -1)[None, :])
    return jax.lax.top_k(-d, k)[1].astype(jnp.int32)


def group(xyz, feats, idx, nbr, affine, affine_mode: str):
    """Gather, normalize by the cloud's RMS offset (then alpha/beta),
    concatenate the centre feature: -> ([S, 3], [S, k, 2C])."""
    centre_f = feats[idx]
    off = feats[nbr] - centre_f[:, None, :]
    sigma = jnp.sqrt(jnp.mean(off * off) + NORM_EPS)
    g = off / (sigma + NORM_EPS)
    if affine_mode == "affine":
        g = g * affine["alpha"] + affine["beta"]
    centre_b = jnp.broadcast_to(centre_f[:, None, :], g.shape)
    return xyz[idx], jnp.concatenate([g, centre_b], axis=-1)


# ------------------------------------------------------------ forward ----

def forward_one(params: Dict, c: Dict, xyz, urs_idx, cache, *, mode: str,
                bits: Optional[int]):
    """One cloud [N, 3] -> (logits [n_classes], (sample idx, nbr idx)
    per stage).  ``cache`` replays a key frame's indices (FPS configs);
    ``urs_idx`` are the shared URS indices (URS configs)."""
    def cbr(p, x, act=True):
        return conv(p, x, act, mode, bits)

    feats = cbr(params["embed"], xyz)
    cur_xyz, used = xyz, []
    for s, st in enumerate(params["stages"]):
        if cache is not None:
            idx, nbr = cache[0][s], cache[1][s]
        else:
            if c["sampler"] == "fps":
                idx = fps(cur_xyz, stage_samples(c)[s])
            else:
                idx = urs_idx[s]
            nbr = knn(cur_xyz[idx], cur_xyz, c["k_neighbors"], mode)
        used.append((idx, nbr))
        cur_xyz, h = group(cur_xyz, feats, idx, nbr, st.get("affine"),
                           c["affine_mode"])
        h = cbr(st["transfer"], h)
        for blk in st["pre"]:
            h = jax.nn.relu(cbr(blk["net2"], cbr(blk["net1"], h), False) + h)
        h = jnp.max(h, axis=1)
        for blk in st["pos"]:
            h = jax.nn.relu(cbr(blk["net2"], cbr(blk["net1"], h), False) + h)
        feats = h
    g = jnp.max(feats, axis=0)
    hd = params["head"]
    logits = cbr(hd["fc3"], cbr(hd["fc2"], cbr(hd["fc1"], g)), False)
    idx_t = tuple(u[0] for u in used)
    nbr_t = tuple(u[1] for u in used)
    return logits, (idx_t, nbr_t)


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode", "bits"))
def _forward_block(params, xyz, urs_idx, cache, cfg_items, mode, bits):
    c = dict(cfg_items)
    return jax.vmap(lambda x, ch: forward_one(params, c, x, urs_idx, ch,
                                              mode=mode, bits=bits),
                    in_axes=(0, None if cache is None else 0))(xyz, cache)


def _freeze(c: Dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in c.items()))


def urs_indices(c: Dict, lfsr_seed: int) -> Optional[List[np.ndarray]]:
    """The shared URS indices of every stage (None for FPS)."""
    if c["sampler"] != "urs":
        return None
    sizes, n = [], c["n_points"]
    for m in stage_samples(c):
        sizes.append((n, m))
        n = m
    return lfsr_indices(lfsr_seed, sizes)


def forward(params: Dict, c: Dict, inputs: Tuple[np.ndarray], *,
            lfsr_seed: int, mode: str = "highest",
            bits: Optional[int] = None, cache=None, block: int = 16):
    """Logits of the clouds ``inputs = (xyz [R, N, 3],)`` (and the
    indices they used), in blocks of ``block`` clouds so that any R
    fits."""
    clouds, = inputs
    urs_idx = urs_indices(c, lfsr_seed)
    if urs_idx is not None:
        urs_idx = tuple(jnp.asarray(a) for a in urs_idx)
    shape_keys = ("n_points", "n_classes", "embed_dim", "k_neighbors",
                  "stage_expansion", "pre_blocks", "pos_blocks",
                  "res_expansion", "affine_mode", "sampler")
    items = _freeze({k: c[k] for k in shape_keys})
    def rows(a, i):
        """Rows [i, i + block) of ``a``, the last block padded with its
        own first row, so that every block has one shape."""
        a = np.asarray(a[i:i + block])
        return np.concatenate([a, np.repeat(a[:1], block - len(a), 0)])

    logits, caches = [], []
    for i in range(0, len(clouds), block):
        n = min(block, len(clouds) - i)
        sub = None if cache is None else jax.tree_util.tree_map(
            lambda a: rows(a, i), cache)
        lg, ch = _forward_block(params, jnp.asarray(rows(clouds, i)),
                                urs_idx, sub, items, mode, bits)
        logits.append(np.asarray(lg)[:n])
        caches.append(jax.tree_util.tree_map(lambda a: np.asarray(a)[:n], ch))
    cat = jax.tree_util.tree_map(lambda *a: np.concatenate(a), *caches)
    return np.concatenate(logits), cat


# ----------------------------------------------- rest of the contract ----

def make_pool(key, c: Dict, size: int) -> Tuple[np.ndarray]:
    """``size`` synthetic clouds of the configuration's point count."""
    return (cloud_traffic.make_batch(key, c["n_points"], size),)


def decision_paths(c: Dict, deciders: Tuple[np.ndarray], lfsr_seed: int,
                   rnd) -> List[List[decisions.Path]]:
    """Per cloud of ``deciders``, the (sample, neighbour) indices of
    every decision path :func:`decisions.paths` lists for it."""
    urs = urs_indices(c, lfsr_seed)
    return [decisions.paths(c, cloud, urs, rnd) for cloud in deciders[0]]


def cbr_layers(c: Dict) -> List[Layer]:
    """Every CBR layer of one cloud's forward, in order."""
    k = c["k_neighbors"]
    out = [Layer("embed", c["n_points"], 3, c["embed_dim"])]
    c_prev = c["embed_dim"]
    for s, (smp, ch) in enumerate(zip(stage_samples(c), stage_dims(c))):
        mid = res_mid(c, ch)
        out.append(Layer(f"stage{s + 1}.transfer", smp * k, 2 * c_prev, ch))
        for branch, rows in (("pre", smp * k), ("pos", smp)):
            for i in range(c[f"{branch}_blocks"][s]):
                out.append(Layer(f"stage{s + 1}.{branch}{i}.net1", rows, ch,
                                 mid))
                out.append(Layer(f"stage{s + 1}.{branch}{i}.net2", rows, mid,
                                 ch))
        c_prev = ch
    out += [Layer("head.fc1", 1, c_prev, 512), Layer("head.fc2", 1, 512, 256),
            Layer("head.fc3", 1, 256, c["n_classes"])]
    return out


def mapping_flops(c: Dict) -> int:
    """The kNN distance matmuls of one cloud (2*S*N*3 per stage); a
    stream frame that replays its key frame's neighbours skips them."""
    total, n = 0, c["n_points"]
    for smp in stage_samples(c):
        total += 2 * smp * n * 3
        n = smp
    return total
