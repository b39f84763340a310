"""Record a short profiler trace of one cell on the chip, for the trace
reduction's test data.

    python3 benchmarks/chip/record_trace.py <workload> <seed> <out.xplane.pb.gz>

Runs the cell as ``run.py --trace 1`` does, with a traced part of 0.1 s,
and keeps the trace, gzipped, at the given path.
"""
import time

T_START = time.perf_counter()

import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402


def main(workload: str, seed: int, out: str) -> int:
    harness.TRACE_S = 0.1
    cell = harness.load_cell(workload)
    devices = harness.require_chips(cell.chips)
    from repro.launch.profile import configure_compile_cache
    configure_compile_cache()
    load = trace_reduce.load

    def keep(path):
        src = sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))[-1]
        pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
        with open(src, "rb") as f, gzip.open(out, "wb") as g:
            shutil.copyfileobj(f, g)
        return load(path)

    trace_reduce.load = keep
    res = run.run_cell(cell, seed, 4.0, True, devices, T_START,
                       harness.CompileCounter())
    print(json.dumps(res["out"]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]), sys.argv[3]))
