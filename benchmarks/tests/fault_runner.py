"""Drive one run of a cell with the timed path broken underneath:
``python fault_runner.py <fault> <run.py arguments>``.

The fault is looked up under the driver of the cell that ``--workload``
names (``faults/<driver>.py``; ``none`` breaks nothing). With
``--cpu-rehearsal --rows N`` among the arguments it runs here at a tiny
size; without them, on the chip at the cell's own size.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for _p in (os.path.dirname(BENCH), BENCH, HERE):
    sys.path.insert(0, _p)


def plant(fault: str, workload: str) -> None:
    import faults
    import harness

    if fault == "none":
        return
    driver = harness.traffic_of(harness.find_cell(harness.load_manifest(), workload))["driver"]
    declared = faults.of(driver)
    if fault not in declared:
        raise SystemExit(f"driver {driver!r} declares no fault {fault!r}: {sorted(declared)}")
    declared[fault].plant()


if __name__ == "__main__":
    fault, argv = sys.argv[1], sys.argv[2:]
    import run

    # run.main sets the platform before jax is imported; the fault is
    # planted by the harness's first step after that
    import harness

    real_device_info = harness.device_info

    def device_info_then_plant():
        info = real_device_info()
        plant(fault, argv[argv.index("--workload") + 1])
        return info

    harness.device_info = device_info_then_plant
    sys.exit(run.main(argv))
