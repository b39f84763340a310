"""Find an open-loop cell's knee: the same served path at a ladder of
offered loads, set up once, one window per load.

    python3 benchmarks/chip/sweep.py <workload> <seed> <seconds> <load> ...

A load is requests per second (a single-model Poisson cell), sessions
(a stream cell), or ``rt_rate:bulk_rate`` (the fleet).  For each load it
prints the offered and answered rates, p50/p95 latency from the due
time, the median generator lateness and how many requests were still
unanswered when the window closed: a backlog that grows with the load
marks the knee.  The knee found is recorded by hand in the traffic
file; the benchmark's own runs never search for it.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402
import measures  # noqa: E402
from traffic import clouds  # noqa: E402


def set_load(cell, served, load: str, seed: int, seconds: float) -> None:
    t = cell.traffic
    if t["kind"] == "stream":
        t["sessions"] = int(load)
        frames = int(seconds * t["hz"]) + 1
        served.streams = np.asarray(clouds.make_streams(
            harness.prng_key(seed, harness.SALT_CLOUDS),
            cell.config["n_points"], frames, t["sessions"], t["drift"]))
    elif "tenants" in t:
        rt, bulk = (float(x) for x in load.split(":"))
        t["tenants"] = {"rt": rt, "bulk": bulk}
    else:
        t["rate_per_s"] = float(load)


def main(workload: str, seed: int, seconds: float, loads) -> int:
    cell = harness.load_cell(workload)
    devices = harness.require_chips(cell.chips)
    from repro.launch.profile import configure_compile_cache
    configure_compile_cache()
    counter = harness.CompileCounter()
    with harness.matmul_precision(cell.config):
        served = harness.build_served(cell, seed, seconds)
        harness.warm_up(served, cell)
        print(f"setup {time.perf_counter() - T_START:.1f}s on "
              f"{devices[0].device_kind} x{len(devices)}", flush=True)
        for load in loads:
            set_load(cell, served, load, seed, seconds)
            for e in served.engines.values():
                e.reset_stats()
            tracer = harness.Tracer(False, None, served)
            w = harness.run_open(served, cell, seconds, seed, tracer,
                                 counter)
            end = w.t0 + w.seconds
            behind = sum(1 for r in w.recs
                         if r.t_done is None or r.t_done > end)
            lat = measures.latencies_ms(w.recs)
            row = {"load": load, "offered_per_s": len(w.recs) / seconds,
                   "answered_per_s": measures.rate_per_s(w.recs, w.t0,
                                                         seconds),
                   "p50_ms": measures.percentile(lat, 50),
                   "p95_ms": measures.percentile(lat, 95),
                   "late_ms_median": measures.percentile(
                       measures.lateness_ms(w.recs), 50),
                   "unanswered_at_close": behind,
                   "failed": measures.failed(w.recs),
                   "stream": w.stream_stats,
                   "dispatches": {tn: s["batches"]
                                  for tn, s in w.engine_stats.items()},
                   "compiles": w.compiles}
            if "tenants" in cell.traffic:
                row["tenants"] = {
                    tn: {"answered_per_s": measures.rate_per_s(
                        [r for r in w.recs if r.tenant == tn], w.t0, seconds),
                         "p95_ms": measures.percentile(measures.latencies_ms(
                             [r for r in w.recs if r.tenant == tn]), 95)}
                    for tn in cell.traffic["tenants"]}
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]), float(sys.argv[3]),
                  sys.argv[4:]))
