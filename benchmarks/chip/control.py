"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 benchmarks/chip/control.py <workload> <seconds> <seed> ...

For each seed, one run of the cell as ``run.py`` makes it (short
window), then on the same sampled requests: the program's compared
numbers, and the control's, the plain reference one precision step
below the configuration (float32 "high" for "highest", 4-bit for
8-bit) put in the program's place, each with the per-request gaps it
was counted from.  One process, so the set-up is paid
once per seed and never recompiled.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402
import run  # noqa: E402


def readings(checks):
    """Each compared number, and for a gap check the per-request gaps
    it was counted from, widest first."""
    out = {}
    for c in checks:
        out[c.name] = c.value
        if c.gaps is not None:
            out[c.name + ".gaps"] = sorted(map(float, c.gaps), reverse=True)
    return out


def main(workload: str, seconds: float, seeds) -> int:
    cell = harness.load_cell(workload)
    devices = harness.require_chips(cell.chips)
    from repro.launch.profile import configure_compile_cache
    configure_compile_cache()
    counter = harness.CompileCounter()
    for seed in seeds:
        res = run.run_cell(cell, seed, seconds, False, devices,
                           time.perf_counter(), counter, control=True)
        print(json.dumps({
            "seed": seed, "correct": res["out"]["correct"],
            "program": readings(res["checks"]),
            "control": readings(res["control"]),
            "limits": {c.name: c.limit for c in res["checks"]}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], float(sys.argv[2]),
                  [int(s) for s in sys.argv[3:]]))
