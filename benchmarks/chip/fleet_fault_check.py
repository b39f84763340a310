"""Run the fleet cell at CPU size on four host devices, sound and with
one planted fault; print ``sound=<correct> broken=<correct>``.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python benchmarks/chip/fleet_fault_check.py drop_second_shard
"""
import sys

import pytest

from conftest import run_tiny, tiny_cell
import test_faults


def main(fault: str) -> None:
    cell = "fleet.rt-lite.bulk-elite"
    sound = run_tiny(tiny_cell(cell))["out"]["correct"]
    with pytest.MonkeyPatch.context() as mp:
        test_faults.plant(mp, getattr(test_faults, fault))
        broken = run_tiny(tiny_cell(cell))["out"]["correct"]
    print(f"sound={sound} broken={broken}")


if __name__ == "__main__":
    main(sys.argv[1])
