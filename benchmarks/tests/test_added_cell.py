"""A cell with a second driver and a second index kind is added by new
files and manifest entries alone.

``data/zorder_cell/`` holds what a later PR would add for a z-order
covering build: a configuration (``index.kind`` ``zorder``, no
``num_buckets``), a traffic file, a minimal driver, its fault module,
and the entries for ``BENCHMARK.json``. Each test lays them into a
temporary copy of ``benchmarks/`` + ``BENCHMARK.json`` — no file of the
copy is edited but the manifest — and runs the copy's own tests there.
The real ``BENCHMARK.json`` has no such cell.
"""

import json
import os
import re
import shutil
import subprocess
import sys

from conftest import BENCH, HERE, ROOT

ADDED = os.path.join(HERE, "data", "zorder_cell")
FAULT_MODULE = os.path.join("tests", "faults", "zorder_build.py")
CELL = "tpch-zorder-build"


def _copy_with_the_cell(tmp_path, fault_module: bool) -> str:
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", ".pytest_cache"))
    os.symlink(os.path.join(ROOT, "hyperspace_tpu"), os.path.join(root, "hyperspace_tpu"))
    for base, _dirs, files in os.walk(ADDED):
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ADDED)
            if rel == "manifest_entries.json" or (rel == FAULT_MODULE and not fault_module):
                continue
            dst = os.path.join(root, "benchmarks", rel)
            assert not os.path.exists(dst), f"{rel} would edit a file that is there"
            shutil.copy(os.path.join(base, f), dst)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(ADDED, "manifest_entries.json")) as f:
        entries = json.load(f)
    assert entries["workload"]["name"] == CELL
    assert CELL not in [c["name"] for c in manifest["workloads"]]
    manifest["configs"].append(entries["config"])
    manifest["workloads"].append(entries["workload"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in entries["joins"]:
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return root


def _pytest(root: str, *args: str):
    """The copy's own tests, run in the copy -> (exit code, {outcome: count}, output)."""
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    p = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", *args],
                       cwd=root, env=env, capture_output=True, text=True, timeout=1500)
    counts = {k: int(n) for n, k in re.findall(r"(\d+) (passed|failed|error|errors)\b",
                                               p.stdout.strip().splitlines()[-1])}
    return p.returncode, counts, p.stdout[-4000:] + p.stderr[-2000:]


def test_a_second_driver_and_index_kind_come_by_files_alone(tmp_path):
    root = _copy_with_the_cell(tmp_path, fault_module=True)
    code, _counts, out = _pytest(root, "benchmarks/tests", "--collect-only")
    assert code == 0, out
    code, counts, out = _pytest(root, "benchmarks/tests/test_manifest.py")
    assert code == 0 and set(counts) == {"passed"}, out
    # the added cell through the copy's own harness tests: the whole path
    # with the control (correct, served as ZOCI), its driver's fault
    # declared, and the fault coming out not correct
    code, counts, out = _pytest(root, "benchmarks/tests/test_harness_cpu.py", "-k", CELL)
    assert code == 0 and counts == {"passed": 3}, out


def test_a_driver_that_declares_no_fault_fails_one_test_not_the_collection(tmp_path):
    root = _copy_with_the_cell(tmp_path, fault_module=False)
    code, _counts, out = _pytest(root, "benchmarks/tests", "--collect-only")
    assert code == 0, out
    code, counts, out = _pytest(root, "benchmarks/tests/test_harness_cpu.py", "-k",
                                f"declares_a_fault or ({CELL} and broken_program)")
    assert code == 1 and counts == {"failed": 1, "passed": 2}, out
    assert f"test_the_cells_driver_declares_a_fault[{CELL}]" in out
    assert "a cell whose check nothing can fail decides nothing" in out
