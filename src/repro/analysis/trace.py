"""Jaxpr-level abstract analysis of stage callables.

``lower(spec, cfg)`` resolves every CBR layer (and optionally a fused
group->transfer op) to a backend callable; this module traces each
distinct one with :func:`jax.make_jaxpr` on synthetic
``ShapeDtypeStruct`` inputs shaped from the real topology — no FLOP is
spent — and walks the (nested) jaxpr for statically-decidable
violations of the framework's contracts:

RPA201  any ``float64`` value: the deployment arithmetic is fp32/int8;
        a stray f64 (an un-cast numpy scalar, a python float promoted
        under x64) doubles bandwidth and silently changes bit patterns.
RPA202  a *silent* int8->float upcast: the only legal way int8 export
        weights reach float math is the dequant idiom
        ``q.astype(f) * scale`` — a convert whose result feeds anything
        but that scale multiply (e.g. ``x @ q.astype(f)``) is serving
        the raw quantized integers as if they were the weights.
RPA203  host-callback / nondeterministic primitives
        (``pure_callback``, ``io_callback``, ``debug_callback``, live
        RNG) inside a region dispatched under ``shard_map``: callbacks
        break lane-mapped determinism and deadlock under SPMD.
RPA204  a cross-shard collective naming the ``"data"`` mesh axis: the
        serving contract is that lanes are independent (that is what
        makes ``data_shards`` bit-invisible); any ``psum``/
        ``all_gather`` over ``P("data")`` couples them.

Entry points: :func:`scan_jaxpr` (one traced jaxpr),
:func:`trace_callable` (trace + scan), :func:`analyze_plan_trace`
(every distinct CBR/fused op of a lowered spec).
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, Iterable, List, Tuple

import jax
import jax.extend.core
import jax.numpy as jnp

from repro.analysis.findings import Finding, dedupe, finding

#: Primitives that escape to the host (or read host state) — forbidden
#: inside a shard_map-dispatched region (RPA203).
HOST_CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback_call", "outside_call", "infeed", "outfeed",
})

#: Live-RNG primitives — nondeterministic w.r.t. the framework's
#: explicit-LFSR contract when they appear inside a sharded region.
NONDETERMINISTIC_PRIMITIVES = frozenset({
    "rng_bit_generator", "random_seed", "random_bits",
})

#: Cross-device collectives; flagged (RPA204) when they name the
#: ``"data"`` mesh axis of the serving dispatch.
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "ppermute", "pbroadcast", "reduce_scatter", "pgather",
})

#: Primitives that move a tainted (silently-upcast) value around
#: without consuming it arithmetically — taint flows through.
_TAINT_PASSTHROUGH = frozenset({
    "reshape", "broadcast_in_dim", "transpose", "squeeze",
    "expand_dims", "copy", "convert_element_type", "slice",
    "dynamic_slice", "rev",
})

_INT_NARROW = (jnp.int8, jnp.uint8, jnp.int4 if hasattr(jnp, "int4")
               else jnp.int8)


def _subjaxprs(eqn) -> Iterable:
    """Every nested jaxpr hanging off one equation's params."""
    for val in eqn.params.values():
        # ClosedJaxpr proxies .eqns, so unwrap via .jaxpr *first*.
        if hasattr(val, "jaxpr"):            # ClosedJaxpr
            yield val.jaxpr
        elif hasattr(val, "eqns"):
            yield val
        elif isinstance(val, (tuple, list)):
            for item in val:
                if hasattr(item, "jaxpr"):
                    yield item.jaxpr
                elif hasattr(item, "eqns"):
                    yield item


def _named_axes(eqn) -> Tuple[str, ...]:
    """The mesh axis names a collective equation operates over."""
    names: List[str] = []
    for key in ("axes", "axis_name", "axis_names"):
        val = eqn.params.get(key)
        if isinstance(val, str):
            names.append(val)
        elif isinstance(val, (tuple, list)):
            names.extend(v for v in val if isinstance(v, str))
    return tuple(names)


def _is_f64(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and dtype == jnp.float64


def _scan_one(jaxpr, where: str, in_shard_region: bool,
              out: List[Finding]) -> None:
    """Scan one jaxpr level: dtype discipline + forbidden primitives,
    with an intra-level int8->float taint walk, recursing into nested
    jaxprs (a ``shard_map`` equation marks its body sharded)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)   # tolerate ClosedJaxpr
    tainted: set = set()
    for var in jaxpr.invars + jaxpr.constvars:
        if _is_f64(var.aval):
            out.append(finding("RPA201", where,
                               f"float64 input/const in traced jaxpr "
                               f"(var {var})"))
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        for var in eqn.outvars:
            if _is_f64(var.aval):
                out.append(finding(
                    "RPA201", where,
                    f"primitive {prim!r} produces float64 "
                    f"{getattr(var.aval, 'shape', ())}"))
        # --- int8->float taint: seed, consume, propagate -------------
        in_tainted = any(not isinstance(v, jax.extend.core.Literal)
                         and v in tainted for v in eqn.invars)
        if prim == "convert_element_type":
            src = eqn.invars[0]
            src_dtype = getattr(src.aval, "dtype", None)
            dst_dtype = getattr(eqn.outvars[0].aval, "dtype", None)
            if (src_dtype is not None and dst_dtype is not None
                    and any(src_dtype == t for t in _INT_NARROW)
                    and jnp.issubdtype(dst_dtype, jnp.floating)):
                tainted.add(eqn.outvars[0])
            elif in_tainted:
                tainted.update(eqn.outvars)
        elif prim == "mul":
            # The dequant idiom: q.astype(f) * scale sanctifies the
            # upcast — taint stops here.
            pass
        elif prim in _TAINT_PASSTHROUGH:
            if in_tainted:
                tainted.update(eqn.outvars)
        elif in_tainted:
            out.append(finding(
                "RPA202", where,
                f"int8->float converted value reaches {prim!r} without "
                f"the dequant scale multiply — the raw quantized "
                f"integers are being used as float weights"))
        # --- forbidden primitives in sharded regions ------------------
        if in_shard_region:
            if prim in HOST_CALLBACK_PRIMITIVES:
                out.append(finding(
                    "RPA203", where,
                    f"host-callback primitive {prim!r} inside a "
                    f"shard_map-dispatched region (breaks lane-mapped "
                    f"determinism; deadlocks under SPMD)"))
            elif prim in NONDETERMINISTIC_PRIMITIVES:
                out.append(finding(
                    "RPA203", where,
                    f"nondeterministic primitive {prim!r} inside a "
                    f"shard_map-dispatched region (the framework's "
                    f"randomness contract is the explicit LFSR state)"))
            if prim in COLLECTIVE_PRIMITIVES:
                axes = _named_axes(eqn)
                if "data" in axes:
                    out.append(finding(
                        "RPA204", where,
                        f"collective {prim!r} over mesh axes {axes} "
                        f"couples lanes across the P('data') split — "
                        f"sharding would no longer be bit-invisible"))
        sharded_body = in_shard_region or prim == "shard_map"
        for sub in _subjaxprs(eqn):
            _scan_one(sub, where, sharded_body, out)


def scan_jaxpr(closed_jaxpr, where: str = "<jaxpr>",
               in_shard_region: bool = False) -> List[Finding]:
    """All trace findings of one (closed) jaxpr, deduped by (code,
    site).  ``in_shard_region=True`` treats the whole jaxpr as
    shard_map-dispatched (the stage callables of a ``data_shards > 1``
    spec); nested ``shard_map`` equations are detected either way."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    out: List[Finding] = []
    _scan_one(jaxpr, where, in_shard_region, out)
    return dedupe(out)


def trace_callable(fn, *args, where: str = "<callable>",
                   in_shard_region: bool = False) -> List[Finding]:
    """``jax.make_jaxpr`` a callable on ShapeDtypeStruct args and scan
    it; a callable that fails to trace is itself a finding (RPA209)."""
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001 — any trace failure is the finding
        return [finding("RPA209", where,
                        f"failed to trace: {type(e).__name__}: {e}")]
    return scan_jaxpr(closed, where=where,
                      in_shard_region=in_shard_region)


# --------------------------------------------- plan-wide tracing --------

def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _cbr_params(c_in: int, c_out: int, int8_export: bool) -> Dict:
    """Synthetic frozen-layer param structure (matches what
    ``repro.api.build._freeze`` exports: fused (w, b), int8 stages as
    ``{"q", "scale"}`` dicts)."""
    if int8_export:
        w = {"q": _sds((c_in, c_out), jnp.int8),
             "scale": _sds((1, c_out), jnp.float32)}
    else:
        w = _sds((c_in, c_out))
    return {"w": w, "b": _sds((c_out,))}


def _cbr_shape_walk(plan, cfg) -> List[Tuple[Any, int, int]]:
    """(op, c_in, c_out) for every CBR in the plan, mirroring the
    topology walk ``cost_breakdown`` uses (one source of truth for
    channel dims)."""
    from repro.api import plan as plan_mod
    from repro.models.pointmlp import partseg_levels
    out: List[Tuple[Any, int, int]] = []
    c_prev = cfg.embed_dim
    for op in plan.ops:
        if isinstance(op, plan_mod.EmbedOp):
            out.append((op.cbr, cfg.in_channels, cfg.embed_dim))
        elif isinstance(op, plan_mod.FusedGroupTransferOp):
            c = cfg.stage_dims[op.stage]
            out.append((op.cbr, 2 * c_prev, c))
            c_prev = c
        elif isinstance(op, plan_mod.CBROp):          # stage transfer
            c = cfg.stage_dims[op.stage]
            out.append((op, cfg.transfer_in(c_prev), c))
            c_prev = c
        elif isinstance(op, plan_mod.ResBlockOp):
            c = cfg.stage_dims[op.stage]
            mid = max(1, int(c * cfg.res_expansion))
            out.append((op.net1, c, mid))
            out.append((op.net2, mid, c))
        elif isinstance(op, (plan_mod.HeadOp, plan_mod.SegHeadOp)):
            c_head = (cfg.embed_dim + 2 * c_prev
                      if isinstance(op, plan_mod.SegHeadOp) else c_prev)
            out.append((op.fc1, c_head, 512))
            out.append((op.fc2, 512, 256))
        elif isinstance(op, plan_mod.PropagateOp):
            _, _, skip, c_in, w, _ = partseg_levels(cfg)[op.level - 1]
            mid = max(1, int(w * cfg.res_expansion))
            out.append((op.fuse, c_in + skip, w))
            for net1, net2 in op.blocks:
                out += [(net1, w, mid), (net2, mid, w)]
            c_prev = w
        elif isinstance(op, plan_mod.PartSegHeadOp):
            ctx, cat = cfg.context_dims
            enc = (cfg.embed_dim,) + cfg.stage_dims
            out += [(o, c, ctx) for o, c in zip(op.context, reversed(enc))]
            out.append((op.context_out, len(enc) * ctx, ctx))
            out += [(op.category[0], cfg.n_categories, cat),
                    (op.category[1], cat, cat),
                    (op.classifier, c_prev + ctx + cat, 128),
                    (op.out, 128, cfg.n_classes)]
    return out


def analyze_plan_trace(spec, cfg=None, plan=None) -> List[Finding]:
    """Trace every *distinct* resolved CBR callable of a lowered spec
    (plus the fused group->transfer op, when lowered) and scan the
    jaxprs.  Distinctness is (c_in, c_out, precision, backend, act,
    exported) — a plan traces a handful of jaxprs, not hundreds.

    The spec must pass the ``lowering`` analysis scope (this function
    lowers it); ``data_shards > 1`` scans every stage callable as a
    shard_map-dispatched region (RPA203/204 armed).
    """
    from repro.api import plan as plan_mod
    if cfg is None:
        cfg = spec.to_model_config()
    if plan is None:
        with warnings.catch_warnings():
            # Warning findings are the lowering scope's report;
            # re-warning them from the trace entry point would
            # double-count.
            warnings.simplefilter("ignore")
            plan = plan_mod.lower(spec, cfg)
    in_shard = spec.data_shards > 1
    out: List[Finding] = []
    seen: set = set()
    for cbr, c_in, c_out in _cbr_shape_walk(plan, cfg):
        exported = cbr.precision == "int8"
        key = (c_in, c_out, cbr.precision, cbr.backend, cbr.act, exported)
        if key in seen or cbr.fn is None:
            continue
        seen.add(key)
        where = ".".join(str(p) for p in cbr.path)
        params = _cbr_params(c_in, c_out, exported)
        out += trace_callable(
            lambda p, x, _fn=cbr.fn, _q=cbr.quant, _a=cbr.act:
                _fn(p, x, _q, _a),
            params, _sds((4, c_in)),
            where=f"{where}[{cbr.precision}/{cbr.backend}]",
            in_shard_region=in_shard)
    out += _trace_fused_ops(plan, cfg, in_shard)
    return dedupe(out)


def _trace_fused_ops(plan, cfg, in_shard: bool) -> List[Finding]:
    """Trace each fused group->transfer op on real-topology shapes (the
    kernel has tile-size expectations synthetic dims could violate)."""
    from repro.api import plan as plan_mod
    out: List[Finding] = []
    n_prev, c_prev = cfg.n_points, cfg.embed_dim
    for op in plan.ops:
        if isinstance(op, plan_mod.SampleOp):
            continue
        if not isinstance(op, plan_mod.FusedGroupTransferOp):
            if isinstance(op, plan_mod.CBROp):
                n_prev = cfg.stage_samples[op.stage]
                c_prev = cfg.stage_dims[op.stage]
            continue
        s = op.stage
        n_in = cfg.n_points if s == 0 else cfg.stage_samples[s - 1]
        c_in = cfg.embed_dim if s == 0 else cfg.stage_dims[s - 1]
        c = cfg.stage_dims[s]
        affine = ({"alpha": _sds((c_in,)), "beta": _sds((c_in,))}
                  if cfg.affine_mode == "affine" else None)
        args = [{"w": _sds((2 * c_in, c)), "b": _sds((c,))},
                _sds((1, n_in, 3)), _sds((1, n_in, c_in)),
                _sds((1, cfg.stage_samples[s]), jnp.int32)]
        if affine is not None:
            args.append(affine)

        def fused(p, xyz, feats, idx, aff=None, _op=op):
            return _op.fn(p, xyz, feats, idx, _op.k, aff,
                          cfg.affine_mode, True, act=True)

        out += trace_callable(
            fused, *args, where=f"stages.{s}.fused[{op.kernel}]",
            in_shard_region=in_shard)
        n_prev, c_prev = cfg.stage_samples[s], c
    del n_prev, c_prev
    return out


def analyze_sharded_callable(fn, *args, where: str = "<dispatch>",
                             ) -> List[Finding]:
    """Scan a full (possibly jitted / shard_map-wrapped) dispatch
    callable on concrete or ShapeDtypeStruct args — the deep check for
    a built pipeline's forward.  ``shard_map`` bodies are detected from
    the jaxpr itself."""
    return trace_callable(fn, *args, where=where, in_shard_region=False)
