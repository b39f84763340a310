"""Faults planted under the timed path must turn ``correct`` false.

Each test takes the chip check out, runs a cell at a size the CPU
holds, and breaks the served path where an answer is produced: one
answer of every dispatch altered, or (on the fleet, whose dispatches
are split over two devices) the rows of the second device never
gathered.
"""
import os
import pathlib
import subprocess
import sys

import pytest

from conftest import run_tiny

HERE = pathlib.Path(__file__).resolve().parent


def alter_lane0(fn):
    def broken(self, *args):
        out = fn(self, *args)
        return (out[0].at[0].add(1.0),) + tuple(out[1:])
    return broken


def drop_second_shard(fn):
    def broken(self, *args):
        out = fn(self, *args)
        if self.mesh is None:
            return out
        half = out[0].shape[0] // 2
        return (out[0].at[half:].set(0.0),) + tuple(out[1:])
    return broken


def plant(monkeypatch, fault):
    from repro.api.build import FrozenPipeline
    for name in ("infer", "infer_collect", "infer_cached"):
        monkeypatch.setattr(FrozenPipeline, name,
                            fault(getattr(FrozenPipeline, name)))


@pytest.mark.parametrize("workload", ["elite-fp32.backlog",
                                      "lite-int8.poisson",
                                      "elite-fp32.stream"])
def test_altered_answer_is_caught(tiny, monkeypatch, workload):
    sound = run_tiny(tiny(workload))
    assert sound["out"]["correct"], [c.__dict__ for c in sound["checks"]]
    plant(monkeypatch, alter_lane0)
    broken = run_tiny(tiny(workload))
    assert not broken["out"]["correct"]


@pytest.mark.parametrize("fault", ["alter_lane0", "drop_second_shard"])
def test_fleet_faults_are_caught(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "fleet_fault_check.py"),
                        fault], env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    assert p.stdout.split()[-2:] == ["sound=True", "broken=False"], p.stdout
