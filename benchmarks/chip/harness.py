"""The benchmark's machinery: cells from data, set-up, the measured window,
and the check of what the window produced.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a
configuration file (``configs/<config>.json``) under a traffic file
(``traffic/<traffic>.json``).  Nothing here names a cell; a new cell is
new data files and a new entry.

Nothing here names a model either.  A configuration's ``reference`` key
names a module under this directory that provides what is particular
to its model (:data:`CONTRACT`; ``reference.py`` documents it): the
traffic's inputs, the plain reference's weights and forward, the
decision paths that float32 rounding leaves open, and the work of one
input.  A new architecture is a new configuration file, reference
module and traffic file.

The window drives the served path itself: ``AsyncPointCloudEngine``
``submit``/``pump`` (and its stream sessions) or ``PipelineFleet``
``submit``/``pump``.  Every request carries the time it was due; its
latency runs from then until its answer is on the host.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import jax  # noqa: E402

import work  # noqa: E402
from traffic import clouds, schedule  # noqa: E402

#: What a configuration's reference module provides.
CONTRACT = ("make_pool", "deploy_params", "forward", "decision_paths",
            "cbr_layers", "mapping_flops")

#: Clouds checked against the reference in each run (per tenant).
CHECK_SAMPLE = 64
#: Of those, at least this many stream frames that missed the cache.
CHECK_MISSES = 16
#: How long requests due in the window may take to resolve after it.
DRAIN_S = 60.0
#: Length of the traced part of a ``--trace 1`` window (its end).
TRACE_S = 1.0
#: Poll interval of the open-loop driver while it has nothing to do.
IDLE_POLL_S = 0.0005


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


# ------------------------------------------------------------- cells ----

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def reference_module(name: str, file: str):
    """The module at ``file`` (relative to this directory) that
    configuration ``name`` names as its reference, loaded once per file
    and checked against :data:`CONTRACT`."""
    path = (HERE / file).resolve()
    if HERE not in path.parents or path.suffix != ".py":
        raise ValueError(f"configuration {name!r}: reference {file!r} is "
                         f"not a Python file under {HERE}")
    key = f"reference_module:{path.relative_to(HERE)}"
    mod = sys.modules.get(key)
    if mod is None:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    missing = [f for f in CONTRACT if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"configuration {name!r}: its reference "
                             f"{file!r} lacks {', '.join(missing)}")
    return mod


def load_config(name: str, directory: pathlib.Path = HERE / "configs"
                ) -> Dict:
    """The configuration ``<directory>/<name>.json``, its reference
    module kept under ``work.MODULE``; a fleet's tiers each keep their
    own."""
    c = load_json(directory / f"{name}.json")
    if "tiers" in c:
        for tier, sub in c["tiers"].items():
            c["tiers"][tier] = load_config(sub, directory)
        return c
    if "reference" not in c:
        raise KeyError(f"configuration {name!r} names no reference module")
    c[work.MODULE] = reference_module(name, c["reference"])
    return c


def pool_shapes(c: Dict) -> List[Tuple[int, ...]]:
    """The shapes of a one-request pool of ``c``, traced, not computed."""
    key = jax.ShapeDtypeStruct((2,), np.uint32)
    out = jax.eval_shape(lambda k: c[work.MODULE].make_pool(k, c, 1), key)
    return [tuple(a.shape) for a in out]


def load_cell(workload: str, bench: Optional[Dict] = None) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``."""
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    try:
        w = next(w for w in bench["workloads"] if w["name"] == workload)
    except StopIteration:
        raise KeyError(f"no workload {workload!r}; known: "
                       f"{[w['name'] for w in bench['workloads']]}") from None
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    return make_cell(workload, w["chips"], pathlib.Path(conf["file"]).stem,
                     w["traffic"], bench)


def make_cell(name: str, chips: int, config: str, traffic: str,
              bench: Dict) -> Cell:
    """A cell from its configuration and traffic files, with the metrics
    ``bench`` declares for it."""
    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m) and m["moves"] in names]
    c = load_config(config)
    t = load_json(HERE / "traffic" / f"{traffic}.json")
    if t["kind"] == "stream":
        shapes = pool_shapes(c)
        if len(shapes) != 1 or len(shapes[0]) != 3 or shapes[0][2] != 3:
            raise ValueError(
                f"configuration {config!r} cannot serve stream cell "
                f"{name!r}: a stream sends xyz frames [P, N, 3], but its "
                f"reference's make_pool gives arrays of shapes {shapes}")
    return Cell(name, chips, c, t, e2e, per_layer)


def require_chips(chips: int):
    """The cell's devices; raises :class:`NoChip` without enough TPUs."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (platform {devices[0].platform!r}); "
                     f"the benchmark never falls back to the CPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def prng_key(seed: int, salt: int):
    """A key from a seed of any size: low 32 bits, folded with the rest
    and a salt per use."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), salt)


SALT_WEIGHTS, SALT_CLOUDS, SALT_SCHEDULE, SALT_SAMPLE = range(4)


# ------------------------------------------------------------- set-up ----

def spec_fields(c: Dict) -> Dict:
    from repro.api.spec import PipelineSpec
    names = {f.name for f in dataclasses.fields(PipelineSpec)}
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in c.items() if k in names}


def pipeline_spec(c: Dict, serve: Dict, stream: Optional[Dict] = None,
                  data_shards: int = 1):
    from repro.api.spec import PipelineSpec
    spec = PipelineSpec(**spec_fields(c))
    if stream:
        spec = spec.replace(stream=True,
                            stream_drift_threshold=stream["drift_threshold"])
    return spec.serving(policy=serve["policy"],
                        slo_ms=serve.get("slo_ms", 0.0),
                        dispatch_ms=serve.get("dispatch_ms", 0.0),
                        data_shards=data_shards)


def init_weights(spec, seed: int):
    """The served weights from the seed: one jitted call on the device."""
    from repro.models import pointmlp as PM
    init = jax.jit(PM.pointmlp_init, static_argnums=1)
    return init(prng_key(seed, SALT_WEIGHTS), spec.to_model_config())


def matmul_precision(c: Dict):
    prec = c.get("matmul_precision")
    return (jax.default_matmul_precision(prec) if prec
            else contextlib.nullcontext())


@dataclasses.dataclass
class Served:
    """What set-up built: the system under test and its inputs."""
    kind: str                       # "engine" | "fleet"
    target: object                  # AsyncPointCloudEngine | PipelineFleet
    engines: Dict[str, object]      # tenant ("" single) -> engine
    configs: Dict[str, Dict]        # tenant -> model config
    pools: Dict[str, Tuple[np.ndarray, ...]]  # tenant -> payloads [P, ...]
    streams: Optional[np.ndarray]   # [sessions, frames, N, 3]
    lfsr_seed: int
    max_batch: int
    threshold: Optional[float] = None


def make_pool(c: Dict, key, size: int) -> Tuple[np.ndarray, ...]:
    """``size`` requests' payloads, made by the configuration's module."""
    return tuple(np.asarray(a) for a in c[work.MODULE].make_pool(key, c, size))


def payload(pool: Tuple[np.ndarray, ...], which) -> Tuple[np.ndarray, ...]:
    """Request ``which`` of ``pool`` (what ``submit(*payload)`` sends),
    or with an index array those requests stacked."""
    return tuple(a[which] for a in pool)


def build_served(cell: Cell, seed: int, seconds: float) -> Served:
    """Weights, pipelines, engines and traffic inputs for one run; a
    stream cell gets exactly the frames its window sends."""
    from repro.api import build
    from repro.api.spec import FleetSpec, TenantSpec
    from repro.serve.async_engine import AsyncPointCloudEngine
    from repro.serve.fleet import PipelineFleet

    c, t = cell.config, cell.traffic
    lfsr_seed = seed & 0xFFFFFFFF
    ckey = prng_key(seed, SALT_CLOUDS)
    if "tiers" in c:
        tiers = c["tiers"]
        specs, params, configs, pools = [], {}, {}, {}
        for i, (tenant, tc) in enumerate(sorted(tiers.items())):
            spec = pipeline_spec(tc, t["serve"][tenant],
                                 data_shards=c["data_shards"])
            specs.append(spec)
            params[spec.name] = init_weights(spec, seed + i)
            configs[tenant] = tc
            pools[tenant] = make_pool(tc, jax.random.fold_in(ckey, i),
                                      t["pool"])
        fspec = FleetSpec(
            pipelines=tuple(specs),
            tenants=tuple(TenantSpec(tn, tiers[tn]["name"], slo_ms=0.0,
                                     max_inflight=2 ** 31 - 1)
                          for tn in sorted(tiers)),
            replicas=c["replicas"], router=c["router"],
            max_batch=c["max_batch"])
        fleet = PipelineFleet.from_specs(fspec, params, seed=lfsr_seed,
                                         clock=time.perf_counter)
        engines = {}
        for rep in fleet.replicas:
            tenant = next(tn for tn, tc in tiers.items()
                          if tc["name"] == rep.tier)
            engines.setdefault(tenant, rep.engine)
        return Served("fleet", fleet, engines, configs, pools, None,
                      lfsr_seed, c["max_batch"])

    stream = t if t["kind"] == "stream" else None
    spec = pipeline_spec(c, t["serve"], stream=stream)
    pipe = build(spec, init_weights(spec, seed))
    eng = AsyncPointCloudEngine(pipe, max_batch=c["max_batch"],
                                seed=lfsr_seed, clock=time.perf_counter)
    streams, pools = None, {}
    if stream:
        frames = int(seconds * t["hz"]) + 1
        streams = np.asarray(clouds.make_streams(
            ckey, c["n_points"], frames, t["sessions"], t["drift"]))
    else:
        pools[""] = make_pool(c, ckey, t["pool"])
    return Served("engine", eng, {"": eng}, {"": c}, pools, streams,
                  lfsr_seed, c["max_batch"],
                  t["drift_threshold"] if stream else None)


def warm_up(served: Served, cell: Cell) -> None:
    """Compile every shape the window uses: the dispatch programs, and
    each partial dispatch size with its padding and row reads."""
    eng0 = next(iter(served.engines.values()))
    if served.kind == "fleet":
        served.target.warmup()
    else:
        eng0.warmup()
    mb = served.max_batch
    sizes = [mb] if cell.traffic["kind"] == "backlog" else range(1, mb + 1)
    for tenant, eng in served.engines.items():
        pool = served.pools.get(tenant)
        if pool is None:
            continue
        for k in sizes:
            futs = [eng.submit(*payload(pool, i % len(pool[0])))
                    for i in range(k)]
            eng.flush()
            for f in futs:
                np.asarray(f.result())
    if served.streams is not None:
        frames = served.streams[:, 0]
        for k in range(1, mb + 1):
            sess = [eng0.open_stream() for _ in range(k)]
            for kind in ("miss", "hit", "miss"):
                futs = []
                for i, s in enumerate(sess):
                    f = frames[i % len(frames)]
                    futs.append(s.submit(f + 5.0 if kind == "miss" and
                                         s.stats.frames else f))
                eng0.flush()
                for f in futs:
                    np.asarray(f.result())
    for eng in served.engines.values():
        eng.reset_stats()


# ------------------------------------------------------------- window ----

class Rec:
    """One request: when it was due, submitted, dispatched and answered."""
    __slots__ = ("due", "t_submit", "t_dispatch", "t_done", "out", "tenant",
                 "cloud", "session", "frame", "shed")

    def __init__(self, due, tenant="", cloud=-1, session=-1, frame=-1):
        self.due = due
        self.tenant = tenant
        self.cloud = cloud
        self.session = session
        self.frame = frame
        self.t_submit = self.t_dispatch = self.t_done = None
        self.out = None
        self.shed = False


def _on_done(rec: Rec, clock):
    def cb(fut):
        rec.out = np.asarray(fut.result())
        rec.t_done = clock()
    return cb


@dataclasses.dataclass
class Window:
    """What the measured window recorded."""
    t0: float
    seconds: float
    recs: List[Rec]
    open_loop: bool
    engine_stats: Dict[str, Dict]
    stream_stats: Dict[str, int]
    compiles: int
    trace_counts: Optional[Dict] = None
    gc_pauses: List[tuple] = dataclasses.field(default_factory=list)


class GcWatch:
    """Collects the garbage collector's pauses while it is on."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


class CompileCounter:
    """Counts compilations (and persistent-cache loads) between marks."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.n += 1


def _engine_stats(served: Served) -> Dict[str, Dict]:
    return {tn: dataclasses.asdict(e.stats)
            for tn, e in served.engines.items()}


class Tracer:
    """Starts the profiler ``TRACE_S`` before the window closes; the
    window's end stops it, so that writing the trace out lands after
    the window.  No-op when tracing is off."""

    def __init__(self, on: bool, directory: pathlib.Path, served: Served):
        self.on, self.dir, self.served = on, directory, served
        self.state = "off" if not on else "pending"
        self.span = None
        self.t_start = None
        self.counts = None

    def _counts(self):
        st = _engine_stats(self.served)
        return {tn: (s["requests"], s["batches"]) for tn, s in st.items()}

    def poll(self, now: float, t0: float, seconds: float,
             sessions=None) -> None:
        if self.state == "pending" and now >= t0 + max(0.0,
                                                       seconds - TRACE_S):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench.trace")
            self.span.__enter__()
            self.state = "on"
            self.t_start = now
            self.counts = {"start": self._counts(),
                           "stream_start": _stream_counts(sessions)}

    def stop(self, sessions=None) -> None:
        if self.state != "on":
            return
        self.counts["end"] = self._counts()
        self.counts["stream_end"] = _stream_counts(sessions)
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"


def _stream_counts(sessions) -> Dict[str, int]:
    if not sessions:
        return {"frames": 0, "hits": 0, "misses": 0}
    return {k: sum(getattr(s.stats, k) for s in sessions)
            for k in ("frames", "hits", "misses")}


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def run_backlog(served: Served, cell: Cell, seconds: float,
                tracer: Tracer, counter: CompileCounter) -> Window:
    """Closed backlog: the queue always holds ``depth`` clouds."""
    eng, clock = served.engines[""], time.perf_counter
    pool, depth = served.pools[""], cell.traffic["depth"]
    recs: List[Rec] = []

    def top_up():
        while eng.depth < depth:
            r = Rec(clock(), cloud=len(recs) % len(pool[0]))
            r.t_submit = r.due
            eng.submit(*payload(pool, r.cloud)).add_done_callback(
                _on_done(r, clock))
            recs.append(r)

    top_up()
    n_compiles = counter.n
    t0 = clock()
    t_end = t0 + seconds
    with annotate("bench.window"):
        while True:
            now = clock()
            if now >= t_end:
                break
            tracer.poll(now, t0, seconds)
            with annotate("bench.submit"):
                top_up()
            with annotate("bench.pump"):
                eng.pump()
        tracer.stop()
    compiles = counter.n - n_compiles
    stats = _engine_stats(served)
    eng.flush()
    return Window(t0, seconds, recs, False, stats, {}, compiles,
                  trace_counts=tracer.counts)


def run_open(served: Served, cell: Cell, seconds: float, seed: int,
             tracer: Tracer, counter: CompileCounter) -> Window:
    """Open loop: every request is sent when it is due, whatever the
    system's state; stream sessions hold a due frame back only while
    their previous frame is unanswered."""
    t, clock = cell.traffic, time.perf_counter
    rng = np.random.default_rng(np.random.SeedSequence(
        [seed & 0xFFFFFFFF, seed >> 32, SALT_SCHEDULE]))
    if t["kind"] == "stream":
        arrivals = schedule.sensor_frames(seconds, t["sessions"], t["hz"], rng)
    else:
        rates = t.get("tenants") or {"": t["rate_per_s"]}
        arrivals = schedule.poisson(seconds, rates, t["pool"], rng)
    target, engines = served.target, served.engines
    sessions = None
    if served.streams is not None:
        sessions = [engines[""].open_stream() for _ in range(t["sessions"])]
        held = [collections.deque() for _ in sessions]
        last = [None] * len(sessions)
    fifo = {tn: collections.deque() for tn in engines}
    eng_of = {id(e): tn for tn, e in engines.items()}
    recs: List[Rec] = []

    def send(rec: Rec):
        rec.t_submit = clock()
        if sessions is not None:
            s = rec.session
            fut = sessions[s].submit(served.streams[s, rec.frame])
            last[s] = fut
            tn = ""
        elif served.kind == "fleet":
            depths = {tn: e.depth for tn, e in engines.items()}
            from repro.serve.admission import Overloaded
            try:
                fut = target.submit(rec.tenant, *payload(
                    served.pools[rec.tenant], rec.cloud))
            except Overloaded:
                rec.shed = True
                return
            tn = next(n for n, e in engines.items() if e.depth > depths[n])
        else:
            fut = target.submit(*payload(served.pools[""], rec.cloud))
            tn = ""
        fifo[tn].append(rec)
        fut.add_done_callback(_on_done(rec, clock))

    def release_held():
        for s, q in enumerate(held):
            if q and (last[s] is None or last[s].done()):
                send(q.popleft())

    def pump(now: float) -> bool:
        before = {tn: e.stats.requests for tn, e in engines.items()}
        target.pump(block=False)
        moved = False
        for tn, e in engines.items():
            for _ in range(e.stats.requests - before[tn]):
                fifo[tn].popleft().t_dispatch = now
                moved = True
        return moved

    def arrive(i: int, t0: float) -> None:
        a = arrivals[i]
        if sessions is not None:
            r = Rec(t0 + a.due, session=int(a.stream), frame=a.cloud)
            recs.append(r)
            if held[r.session] or (last[r.session] is not None
                                   and not last[r.session].done()):
                held[r.session].append(r)
            else:
                send(r)
        else:
            r = Rec(t0 + a.due, tenant=a.stream, cloud=a.cloud)
            recs.append(r)
            send(r)

    n_compiles = counter.n
    i, n = 0, len(arrivals)
    t0 = clock()
    t_end = t0 + seconds
    with annotate("bench.window"):
        while True:
            now = clock()
            if now >= t_end:
                break
            tracer.poll(now, t0, seconds, sessions)
            busy = False
            with annotate("bench.submit"):
                while i < n and t0 + arrivals[i].due <= now:
                    arrive(i, t0)
                    i += 1
                    busy = True
                if sessions is not None:
                    release_held()
            with annotate("bench.pump"):
                busy |= pump(now)
            if not busy:
                nxt = t0 + arrivals[i].due if i < n else t_end
                wait = min(nxt - clock(), IDLE_POLL_S)
                if wait > 0:
                    with annotate("bench.wait"):
                        time.sleep(wait)
        tracer.stop(sessions)
        while i < n:                    # due inside the window, sent late
            arrive(i, t0)
            i += 1
    compiles = counter.n - n_compiles
    stats = _engine_stats(served)
    stream_stats = _stream_counts(sessions)
    deadline = clock() + DRAIN_S
    while clock() < deadline and (
            any(r.t_done is None and not r.shed for r in recs[-64:])
            or target.pending or (sessions and any(held))):
        if sessions is not None:
            release_held()
        if not pump(clock()):
            time.sleep(IDLE_POLL_S)
    return Window(t0, seconds, recs, True, stats, stream_stats, compiles,
                  trace_counts=tracer.counts)


# -------------------------------------------------------------- check ----

@dataclasses.dataclass
class Check:
    """One compared number beside its limit (and, for a gap check, the
    per-request gaps it was counted from)."""
    name: str
    value: float
    limit: float
    gaps: Optional[np.ndarray] = None

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def sample_recs(recs: List[Rec], seed: int, served: Served
                ) -> Dict[str, List[Rec]]:
    """Answered requests drawn from the seed, up to ``CHECK_SAMPLE`` per
    tenant, as runs of ``max_batch`` requests in the order they were
    sent: each run holds the first lane of at least one dispatch, and
    under full batches every lane of one.  For a stream, further cache
    misses are drawn singly until the sample holds ``CHECK_MISSES``."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [seed & 0xFFFFFFFF, seed >> 32, SALT_SAMPLE]))
    run = served.max_batch
    out = {}
    for tn in served.configs:
        done = sorted((r for r in recs if r.tenant == tn
                       and r.out is not None), key=lambda r: r.t_submit)
        blocks = len(done) // run
        if blocks * run <= CHECK_SAMPLE:
            pick = done
        else:
            starts = rng.choice(blocks, CHECK_SAMPLE // run, replace=False)
            pick = [done[b * run + j] for b in sorted(starts)
                    for j in range(run)]
        if served.streams is not None:
            miss = stream_decisions(served)
            chosen = {id(r) for r in pick}
            rest = [r for r in done if miss[(r.session, r.frame)]
                    and id(r) not in chosen]
            short = CHECK_MISSES - sum(miss[(r.session, r.frame)]
                                       for r in pick)
            if short > 0 and rest:
                pick += [rest[j] for j in rng.choice(
                    len(rest), min(short, len(rest)), replace=False)]
        out[tn] = pick
    return out


def stream_decisions(served: Served) -> Dict:
    """(session, frame) -> True where the frame misses the cache: the
    first frame, and every frame whose largest point displacement from
    the last miss exceeds the threshold.  Computed from the frames
    alone."""
    miss = {}
    for s, seq in enumerate(served.streams):
        key_frame = None
        for f, frame in enumerate(seq):
            m = key_frame is None or float(np.max(np.linalg.norm(
                frame - key_frame, axis=-1))) > served.threshold
            miss[(s, f)] = m
            if m:
                key_frame = frame
    return miss


def key_frames(served: Served, miss: Dict, recs: List[Rec]):
    out = []
    for r in recs:
        f = r.frame
        while not miss[(r.session, f)]:
            f -= 1
        out.append(served.streams[r.session, f])
    return np.stack(out)


def reference_mode(c: Dict) -> str:
    """The matmul arithmetic a configuration states for its float32
    parts: its ``matmul_precision``, else the platform's default."""
    return c.get("matmul_precision") or "default"


def cross_rounding(mode: str):
    """How the reference's matmul arithmetic ``mode`` rounds the
    operands of the kNN cross term: the TPU's default matmul takes them
    to bfloat16 (one pass, exact products); every other mode here keeps
    float32 operands."""
    if mode == "default" and jax.default_backend() == "tpu":
        def rnd(a):
            return np.asarray(a, np.float32).astype(
                jax.numpy.bfloat16).astype(np.float64)
        return rnd
    return lambda a: a


def _params(c: Dict, weight_seed: int, bits: Optional[int]):
    return c[work.MODULE].deploy_params(prng_key(weight_seed, SALT_WEIGHTS),
                                        c, bits)


def reference_forward(c: Dict, weight_seed: int,
                      inputs: Tuple[np.ndarray, ...],
                      keys: Optional[Tuple[np.ndarray, ...]],
                      lfsr_seed: int, mode: Optional[str] = None,
                      bits: Optional[int] = None) -> np.ndarray:
    """The plain reference's answers to the stacked payload ``inputs``,
    with its own float32 decisions: hits replay those of their key
    frames ``keys``; misses and single requests compute theirs.
    ``mode`` and ``bits`` default to what the configuration states."""
    mode = mode or reference_mode(c)
    if bits is None and c["precision"] == "int8":
        bits = c["a_bits"]
    forward = c[work.MODULE].forward
    params = _params(c, weight_seed, bits)
    cache = None
    if keys is not None:
        _, cache = forward(params, c, keys, lfsr_seed=lfsr_seed, mode=mode,
                           bits=bits, cache=None)
    answers, _ = forward(params, c, inputs, lfsr_seed=lfsr_seed, mode=mode,
                         bits=bits, cache=cache)
    return answers


def open_decision_answers(c: Dict, weight_seed: int,
                          inputs: Tuple[np.ndarray, ...],
                          deciders: Tuple[np.ndarray, ...], lfsr_seed: int
                          ) -> List[np.ndarray]:
    """Per input, the reference's answers on every decision path that
    the configuration's ``decision_paths`` lists for its decider (the
    input itself, or for a stream hit its key frame): the exact path
    and those that flip its open decisions."""
    mod = c[work.MODULE]
    mode = reference_mode(c)
    bits = c["a_bits"] if c["precision"] == "int8" else None
    owner, found = [], []
    for r, paths in enumerate(mod.decision_paths(c, deciders, lfsr_seed,
                                                 cross_rounding(mode))):
        owner += [r] * len(paths)
        found += paths
    owner = np.asarray(owner)
    cache = jax.tree_util.tree_map(lambda *a: np.stack(a), *found)
    answers, _ = mod.forward(_params(c, weight_seed, bits), c,
                             payload(inputs, owner), lfsr_seed=lfsr_seed,
                             mode=mode, bits=bits, cache=cache)
    return [answers[owner == r] for r in range(len(inputs[0]))]


def checked_inputs(served: Served, tenant: str, picked: List[Rec]):
    """The payloads the picked requests sent, stacked, and for stream
    hits the key frames whose decisions they replay (None where nothing
    replays)."""
    if served.streams is None:
        rows = np.array([r.cloud for r in picked])
        return payload(served.pools[tenant], rows), None
    inputs = np.stack([served.streams[r.session, r.frame] for r in picked])
    miss = stream_decisions(served)
    is_hit = np.array([not miss[(r.session, r.frame)] for r in picked])
    keys = key_frames(served, miss, picked)
    return (inputs,), ((keys,), is_hit)


def reference_answers(served: Served, picked: Dict[str, List[Rec]],
                      seed: int, mode: Optional[str] = None,
                      bits: Optional[int] = None,
                      open_decisions: bool = True
                      ) -> Dict[str, List[np.ndarray]]:
    """Per tenant and picked request, the answers the reference accepts
    [candidates, ...answer's shape]: first its own float32 forward, then
    (with ``open_decisions``) the forwards on each decision path that
    float32 rounding leaves open."""
    out = {}
    for tn, recs in sorted(picked.items()):
        c = served.configs[tn]
        wseed = seed + sorted(served.configs).index(tn)
        if not recs:
            out[tn] = []
            continue
        inputs, stream = checked_inputs(served, tn, recs)
        if stream is None:
            own = reference_forward(c, wseed, inputs, None,
                                    served.lfsr_seed, mode, bits)
            deciders = inputs
        else:
            keys, is_hit = stream
            own = None
            for rows, replayed in ((~is_hit, None), (is_hit, keys)):
                if not rows.any():
                    continue
                part = reference_forward(
                    c, wseed, payload(inputs, rows),
                    None if replayed is None else payload(replayed, rows),
                    served.lfsr_seed, mode, bits)
                if own is None:
                    own = np.empty((len(recs),) + part.shape[1:], part.dtype)
                own[rows] = part
            deciders = (np.where(is_hit[:, None, None], keys[0], inputs[0]),)
        cands = [o[None] for o in own]
        if open_decisions:
            more = open_decision_answers(c, wseed, inputs, deciders,
                                         served.lfsr_seed)
            cands = [np.concatenate([a, b]) for a, b in zip(cands, more)]
        out[tn] = cands
    return out


def request_gaps(got: np.ndarray, accepted: List[np.ndarray]) -> np.ndarray:
    """Per checked request, the widest |served - accepted| over every
    element of its answer, against the nearest answer the reference
    accepts (inf for an answer of the wrong shape)."""
    if len(got) != len(accepted):
        return np.full(len(accepted), np.inf)
    out = np.empty(len(accepted))
    for i, (g, cands) in enumerate(zip(got, accepted)):
        if np.shape(g) != cands.shape[1:]:
            out[i] = np.inf
            continue
        widest = np.abs(cands - g).reshape(len(cands), -1).max(axis=1)
        out[i] = float(widest.min())
    return out


def off_share(gaps: np.ndarray, request_gap: float) -> float:
    """Percent of checked requests whose widest gap exceeds
    ``request_gap`` (100 where nothing could be checked)."""
    if gaps.size == 0:
        return 100.0
    return 100.0 * float(np.mean(~(gaps <= request_gap)))


def _gap_checks(served: Served, picked: Dict[str, List[Rec]],
                want: Dict[str, List[np.ndarray]],
                got: Dict[str, np.ndarray]) -> List[Check]:
    checks = []
    for tn, recs in sorted(picked.items()):
        lim = served.configs[tn]["limits"]
        gaps = request_gaps(got[tn], want[tn])
        name = f"{tn}.off_share" if tn else "off_share"
        checks.append(Check(name, off_share(gaps, lim["request_gap"]),
                            lim["off_share"], gaps))
    return checks


def check(served: Served, window: Window, seed: int) -> List[Check]:
    """The window's answers against the plain reference: per tenant the
    share of checked requests off by more than the configuration's
    ``request_gap`` from every answer the reference accepts, beside its
    limit; and every request due in the window answered."""
    picked = sample_recs(window.recs, seed, served)
    want = reference_answers(served, picked, seed)
    got = {tn: (np.stack([r.out for r in recs]) if recs
                else np.zeros((0,))) for tn, recs in picked.items()}
    checks = _gap_checks(served, picked, want, got)
    lost = sum(1 for r in window.recs if r.t_done is None and not r.shed)
    checks.append(Check("unanswered", float(lost), 0.0))
    return checks


def control_checks(served: Served, window: Window, seed: int) -> List[Check]:
    """The control: the reference one precision step below the
    configuration's (float32 "high" for "highest"; 4-bit for 8-bit) in
    the program's place, on the same requests, by the same numbers."""
    picked = sample_recs(window.recs, seed, served)
    want = reference_answers(served, picked, seed)
    got = {}
    for tn, recs in sorted(picked.items()):
        c = served.configs[tn]
        low = ({"bits": 4} if c["precision"] == "int8"
               else {"mode": "high"})
        own = reference_answers(served, {tn: recs}, seed,
                                open_decisions=False, **low)[tn]
        got[tn] = np.stack([a[0] for a in own]) if own else np.zeros((0,))
    return _gap_checks(served, picked, want, got)


def free(served: Served) -> None:
    """Drop the program's state before the reference runs."""
    if served.kind == "fleet":
        served.target.close()
    for e in served.engines.values():
        e.close()
    served.target = None
    served.engines = {}
    gc.collect()
    jax.clear_caches()
