"""Test helpers: the benchmark's modules on the path, and cells cut to a
size the CPU can run in seconds (the chip check taken out)."""
from __future__ import annotations

import pathlib
import sys
import time

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

#: Small widths for CPU runs; kernels run interpreted.
TINY = {"n_points": 64, "embed_dim": 8, "k_neighbors": 4,
        "backend": "pallas_interpret"}
#: Offered load of the tiny open-loop cells.
TINY_TRAFFIC = {"rate_per_s": 40, "sessions": 4,
                "tenants": {"rt": 400, "bulk": 200}}


#: Mixes whose traffic file is kept for later and which ``BENCHMARK.json``
#: does not run yet: (chips, configuration).
KEPT = {"elite-fp32.stream": (1, "pointmlp-elite"),
        "fleet.rt-lite.bulk-elite": (4, "fleet-lite-elite")}


def tiny_cell(workload: str):
    import harness
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    if any(w["name"] == workload for w in bench["workloads"]):
        cell = harness.load_cell(workload, bench)
    else:
        chips, config = KEPT[workload]
        cell = harness.make_cell(workload, chips, config, workload, bench)
    for c in [cell.config] + list(cell.config.get("tiers", {}).values()):
        if "tiers" not in c:
            c.update(TINY)
    for k, v in TINY_TRAFFIC.items():
        if k in cell.traffic:
            cell.traffic[k] = v
    return cell


def run_tiny(cell, seed: int = 2 ** 31 + 11, seconds: float = 2.0,
             control: bool = False):
    """One run of a tiny cell on the CPU devices, as ``run.py`` makes it."""
    import harness
    import run
    devices = harness.jax.devices()[:cell.chips]
    return run.run_cell(cell, seed, seconds, False, devices,
                        time.perf_counter(), harness.CompileCounter(),
                        control=control)


@pytest.fixture
def tiny():
    return tiny_cell
