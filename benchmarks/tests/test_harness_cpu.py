"""The whole path of every cell at a tiny size on the CPU, the control,
and the faults a cell can have: each in a child process, because a cell
fixes the platform and the device count before JAX starts.

A fault run skips nothing but the look for a chip (``--cpu-rehearsal``)
and has to come out with ``correct`` false."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

import faults
import harness

MANIFEST = harness.load_manifest(ROOT)
CELLS = [c["name"] for c in MANIFEST["workloads"]]
ROWS = "12000"


def _driver(cell: str) -> str:
    return harness.traffic_of(harness.find_cell(MANIFEST, cell))["driver"]


# every cell under every fault its driver declares (faults/<driver>.py)
FAULTS = {cell: faults.of(_driver(cell)) for cell in CELLS}


def _run(cell: str, fault: str = None, trace: int = 0, seed: int = 2**31 + 11, controls: int = 0):
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    args = ["--workload", cell, "--seed", str(seed), "--seconds", "1", "--trace", str(trace),
            "--controls", str(controls), "--cpu-rehearsal", "--rows", ROWS]
    prog = [os.path.join(HERE, "fault_runner.py"), fault] if fault else [os.path.join(BENCH, "run.py")]
    p = subprocess.run([sys.executable] + prog + args, cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    return line, p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_the_whole_path_and_the_control_fails(cell):
    line, err = _run(cell, trace=1, controls=1)
    assert line["cpu_rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert "TPU" not in json.dumps(line["device"])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks" and line["checks"]
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    for name, c in line["checks"].items():      # each number beside its limit, on stderr too
        assert f"compared {name}: {c['value']} (limit {c['limit']})" in err
    assert err.strip().splitlines()[-1] == "bench: correct: True"
    assert line["controls"] and all(not c["correct"] for c in line["controls"].values())
    per_layer = {m["name"] for m in MANIFEST["per_layer"] if cell in m["workloads"]}
    assert set(line["metrics"]) <= per_layer
    assert any(n.startswith("compiles_in_window") for n in line["metrics"])


def test_end_to_end_line_has_exactly_the_cells_metrics():
    line, _ = _run(CELLS[0])
    want = {m["name"] for m in MANIFEST["end_to_end"] if CELLS[0] in m.get("workloads", CELLS)}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_the_cells_driver_declares_a_fault(cell):
    assert FAULTS[cell], (
        f"driver {_driver(cell)!r} of cell {cell!r} declares no fault "
        f"(benchmarks/tests/faults/{_driver(cell)}.py): a cell whose check nothing can fail decides nothing")


@pytest.mark.parametrize("cell,fault", [(c, f) for c in CELLS for f in FAULTS[c]])
def test_a_broken_program_comes_out_not_correct(cell, fault):
    line, err = _run(cell, fault=fault)
    assert line["correct"] is False, (line["checks"], err[-1500:])
    failed = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    _plant, must, may = FAULTS[cell][fault]
    assert must <= failed <= must | may, failed


def test_the_runner_without_a_fault_is_correct():
    line, _ = _run(CELLS[0], fault="none")
    assert line["correct"] is True


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELLS[0],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
