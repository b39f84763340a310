"""Run one benchmark cell once on the chip and print its result.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration under
``configs/`` and a traffic file under ``traffic/``.  Set-up makes the
weights and the traffic from ``--seed``, builds the served path and
warms every shape the window uses, reading compiled programs from the
compile cache inside the checkout.  Then the window drives the served
path for ``--seconds``; afterwards a sample of its answers is compared
with the plain reference, the module the configuration's ``reference``
key names.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces part of the window with the JAX profiler and reports its
per-layer metrics (readers under ``metrics/``).  The last line of
standard output is one JSON object; the last lines of standard error
are the compared numbers beside their limits.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2;
where a metric the cell declares reads nothing (a kernel's roofline
share excepted), it prints no result and exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402
import measures  # noqa: E402
import trace_reduce  # noqa: E402

#: A kernel's roofline share may go silent, when a change takes the
#: kernel off the path; every other metric a cell declares must read.
ROOFLINE = re.compile(r"_roofline(\.|$)")

#: Where a traced run's profile goes (inside the checkout, fixed, and
#: removed once reduced).
TRACE_DIR = harness.ROOT / ".bench_out" / "trace"


@dataclasses.dataclass
class RunView:
    """What a metric reader may read about one run."""
    cell: harness.Cell
    window: harness.Window
    setup_s: float
    gave_up: float
    device_kind: str
    configs: Dict[str, Dict]
    device_tenant: Dict[int, str]
    lanes_per_device: Dict[str, int]
    trace: Optional[trace_reduce.Reduction] = None
    trace_counts: Optional[Dict] = None


def reader(name: str):
    """The metric's reader: ``metrics/<name>.py``, else the file of the
    quantity it splits (``metrics/<name up to the first dot>.py``)."""
    base = harness.HERE / "metrics"
    for stem in (name, name.split(".")[0]):
        path = base / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under {base}")


def device_layout(served: harness.Served):
    """Device id -> tenant, and lanes each device runs per dispatch."""
    tenants, lanes = {}, {}
    for tn, eng in served.engines.items():
        mesh = eng.pipeline.mesh
        ids = ([d.id for d in mesh.devices.flat] if mesh is not None
               else [harness.jax.devices()[0].id])
        for i in ids:
            tenants[i] = tn
        lanes[tn] = served.max_batch // len(ids)
    return tenants, lanes


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float, counter: harness.CompileCounter,
             control: bool = False) -> Dict:
    """Set up, measure, check and reduce one run; returns the result
    object (and, with ``control``, the control's numbers)."""
    with harness.matmul_precision(cell.config):
        served = harness.build_served(cell, seed, seconds)
        harness.warm_up(served, cell)
        setup_s = time.perf_counter() - t_start
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = harness.Tracer(trace, TRACE_DIR, served)
        with harness.GcWatch() as watch:
            if cell.traffic["kind"] == "backlog":
                window = harness.run_backlog(served, cell, seconds, tracer,
                                             counter)
            else:
                window = harness.run_open(served, cell, seconds, seed,
                                          tracer, counter)
        window.gc_pauses = watch.pauses
    gave_up = time.perf_counter()
    peak = memory_peak(devices)
    tenants, lanes = device_layout(served)
    harness.free(served)

    checks = harness.check(served, window, seed)
    result = {"checks": checks}
    if control:
        result["control"] = harness.control_checks(served, window, seed)

    view = RunView(cell, window, setup_s, gave_up, devices[0].device_kind,
                   served.configs, tenants, lanes,
                   trace_counts=window.trace_counts)
    if trace:
        view.trace = trace_reduce.reduce(trace_reduce.load(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics, silent = {}, []
    for m in wanted:
        value = reader(m["name"])(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            silent.append(m["name"])

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": all(c.ok for c in checks),
           "attempted": len(window.recs),
           "failed": measures.failed(window.recs),
           "metrics": metrics, "device": device}
    if trace:
        red = view.trace
        device["busy_s"] = red.mean_busy_s
        device["window_s"] = red.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in red.top_ops],
                            "idle_gaps": [list(x) for x in red.gaps]}
    late = measures.lateness_ms(window.recs)
    result.update(out=out, window=window, lateness_ms=late, silent=silent)
    return result


def diagnostics(result: Dict) -> List[str]:
    w = result["window"]
    late = result["lateness_ms"]
    stats = {tn: (s["requests"], s["batches"], s["padded"])
             for tn, s in w.engine_stats.items()}
    sent = [r for r in w.recs if r.t_submit is not None]
    worst = max(sent, key=lambda r: r.t_submit - r.due, default=None)
    lines = [
        f"generator late ms: median {float(_q(late, 50))!r} "
        f"p99 {float(_q(late, 99))!r} max {float(_q(late, 100))!r} "
        f"over {late.size} requests; {int(np.sum(late > 100.0))} sent "
        f"over 100 ms late; the latest was due "
        f"{(worst.due - w.t0) if worst else float('nan')!r} s into the "
        f"window",
        f"window: {w.seconds}s, requests {len(w.recs)}, "
        f"engine (requests, dispatches, pad lanes) {stats}, "
        f"stream {w.stream_stats}, compiles in window {w.compiles}",
        f"garbage collections in the window and its drain: "
        f"{len(w.gc_pauses)}, of them full "
        f"{sum(1 for g, _ in w.gc_pauses if g == 2)}; longest pause "
        f"{max((d for _, d in w.gc_pauses), default=0.0)!r} s",
    ]
    for c in result["checks"]:
        if c.gaps is not None and c.gaps.size:
            lines.append(f"{c.name} counted from {c.gaps.size} requests: "
                         f"widest gap {float(np.max(c.gaps))!r}, median "
                         f"{float(np.median(c.gaps))!r}")
    return lines


def _q(a, q):
    return measures.percentile(a, q) if a.size else float("nan")


def check_lines(checks) -> List[str]:
    return [f"check {c.name}: {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}" for c in checks]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    from repro.launch.profile import configure_compile_cache
    configure_compile_cache()
    counter = harness.CompileCounter()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, T_START, counter)
    out = result["out"]
    for name in result["silent"]:
        print(f"run.py: metric {name} read nothing in this run",
              file=sys.stderr)
    if any(not ROOFLINE.search(n) for n in result["silent"]):
        return 3
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in result["checks"]}
    for line in diagnostics(result) + check_lines(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
