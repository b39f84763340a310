"""From a profiler trace to the numbers the benchmark reports.

The JAX profiler writes one ``*.xplane.pb`` per traced process.  Its TPU
planes (``/device:TPU:<id>``) carry an ``XLA Modules`` line, one event
per program execution, and an ``XLA Ops`` line with the operations
inside them (nested: a loop's event covers its body's).  The host plane
carries the benchmark's own ``TraceAnnotation`` spans (``bench.*``) on
the same clock.

:func:`reduce` turns one trace into:

* the traced window (the ``bench.trace`` span) and, per device, the
  union of program intervals inside it (busy time);
* per device, each program's executions ``(start, duration)``;
* per device, the device time of each kernel (custom-call operation),
  by name;
* the operations with the most self time, and the idle gaps with the
  innermost ``bench.*`` span open on the host at their midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import pathlib
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.trace"
SPAN_PREFIX = "bench."
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Reduction:
    window: Tuple[float, float]                 # seconds, trace clock
    busy_s: Dict[int, float]                    # device -> busy seconds
    programs: Dict[int, Dict[str, List[Tuple[float, float]]]]
    kernels_s: Dict[int, Dict[str, float]]      # device -> kernel -> s
    top_ops: List[Tuple[str, float]]            # self time, all devices
    gaps: List[Tuple[str, float]]               # longest idle gaps

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(1, len(self.busy_s))


def op_name(event_name: str) -> str:
    """``%fusion.155 = f32[...] fusion(...)`` -> ``fusion.155``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """``int8_matmul_pallas.216`` -> ``int8_matmul_pallas``;
    ``jit_fwd(1597...)`` -> ``jit_fwd``."""
    name = name.split("(", 1)[0]
    return re.sub(r"\.\d+$", "", name)


def load(path) -> "object":
    """A ``ProfileData`` from an ``.xplane.pb`` (or ``.xplane.pb.gz``)
    file, or from the newest one under a profiler log directory."""
    from jax.profiler import ProfileData
    path = pathlib.Path(path)
    if path.is_dir():
        found = sorted(glob.glob(str(path / "**" / "*.xplane.pb*"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = pathlib.Path(found[-1])
    if path.suffix == ".gz":
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(str(path))


def _union(intervals: List[Tuple[float, float]], lo: float, hi: float
           ) -> Tuple[float, List[Tuple[float, float]]]:
    """Covered length of ``intervals`` clipped to [lo, hi], and the gaps
    between them."""
    covered, gaps, cur = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            covered += e - max(s, cur)
            cur = e
    if hi > cur:
        gaps.append((cur, hi))
    return covered, gaps


def reduce(profile, top: int = 10) -> Reduction:
    """Reduce one trace (a ``ProfileData``) to a :class:`Reduction`."""
    spans: List[Tuple[float, float, str]] = []
    devices: Dict[int, object] = {}
    for plane in profile.planes:
        m = _DEVICE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((s, s + ev.duration_ns * 1e-9, ev.name))
    window = next(((s, e) for s, e, n in spans if n == WINDOW_SPAN), None)

    programs: Dict[int, Dict[str, List[Tuple[float, float]]]] = {}
    kernels: Dict[int, Dict[str, float]] = {}
    ops_self: Dict[str, float] = collections.defaultdict(float)
    lo, hi = float("inf"), float("-inf")
    for dev, plane in sorted(devices.items()):
        progs: Dict[str, List[Tuple[float, float]]] = \
            collections.defaultdict(list)
        kern: Dict[str, float] = collections.defaultdict(float)
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    s, d = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                    progs[base_name(ev.name)].append((s, d))
                    lo, hi = min(lo, s), max(hi, s + d)
            elif line.name == "XLA Ops":
                _ops(line, window, kern, ops_self)
        programs[dev] = dict(progs)
        kernels[dev] = dict(kern)
    if window is None:
        window = (lo, hi)

    busy: Dict[int, float] = {}
    holes: List[Tuple[float, float]] = []
    for dev, progs in programs.items():
        ivals = [(s, s + d) for runs in progs.values() for s, d in runs]
        busy[dev], dev_holes = _union(ivals, *window)
        holes += dev_holes
    holes.sort(key=lambda h: h[0] - h[1])
    host = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    gaps = [(_label(host, (a + b) / 2), b - a) for a, b in holes[:top]]
    top_ops = sorted(ops_self.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(window, busy, programs, kernels, top_ops, gaps)


def _inside(window, s: float, d: float) -> bool:
    return window is None or window[0] <= s + d / 2 < window[1]


def _ops(line, window, kern: Dict[str, float],
         ops_self: Dict[str, float]) -> None:
    """Kernel time and per-op self time of one ``XLA Ops`` line, for the
    events whose midpoint lies in the window (nested events: a parent's
    self time is its span less its children's)."""
    stack: List[List] = []               # [end, name, start, child, in]
    for ev in line.events:
        s, d = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
        text = ev.name
        name = op_name(text)
        inside = _inside(window, s, d)
        if inside and " custom-call(" in text:
            kern[base_name(name)] += d
        while stack and stack[-1][0] <= s:
            _pop(stack, ops_self)
        stack.append([s + d, name, s, 0.0, inside])
    while stack:
        _pop(stack, ops_self)


def _pop(stack: List[List], ops_self: Dict[str, float]) -> None:
    end, name, start, child, inside = stack.pop()
    if inside:
        ops_self[name] += max(0.0, end - start - child)
    if stack:
        stack[-1][3] += end - start


def _label(spans: List[Tuple[float, float, str]], t: float) -> str:
    """The innermost span open at ``t`` (latest start), or ``none``."""
    best: Optional[Tuple[float, float, str]] = None
    for s, e, n in spans:
        if s <= t < e and (best is None or s > best[0]):
            best = (s, e, n)
    return best[2] if best else "none"


def program_time(red: Reduction, prefixes, device: int) -> Tuple[int, float]:
    """Executions and device seconds of the programs whose base name
    starts with one of ``prefixes``, on ``device``, inside the window."""
    n, total = 0, 0.0
    lo, hi = red.window
    for name, runs in red.programs.get(device, {}).items():
        if not name.startswith(tuple(prefixes)):
            continue
        for s, d in runs:
            if lo <= s + d / 2 < hi:
                n += 1
                total += d
    return n, total
