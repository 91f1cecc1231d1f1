"""A cell with a second driver, a second index kind and a new kernel's
roofline is added by new files and manifest entries alone, however many
cells the benchmark has.

``data/sketch_cell/`` holds what a later PR would add for a z-order
covering build: a configuration (``index.kind`` ``zorder``, no
``num_buckets``), a traffic file, a minimal driver, its fault module, a
``roofline_sketch.py`` with the bytes of its kernel, two per-layer
metrics that read it, and the entries for ``BENCHMARK.json``. Each test
lays them into a temporary copy of ``benchmarks/`` + ``BENCHMARK.json``
— no file of the copy is edited but the manifest — and runs the copy's
own tests there; ``grown`` copies hold one more plain cell besides (a
copied ``traffic/build-loop.json`` under another name), so that the
proof is run on a benchmark larger than today's. Every expected count
comes from the copy's manifest. The real ``BENCHMARK.json`` has no such
cell, and nothing of the real tree is named ``sketch*``
(``test_manifest.py``), so the sketch is in no real cell's way.
"""

import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

from readers import trace_module

ADDED = os.path.join(HERE, "data", "sketch_cell")
FAULT_MODULE = os.path.join("tests", "faults", "sketch_zorder.py")
with open(os.path.join(ADDED, "manifest_entries.json")) as _f:
    ENTRIES = json.load(_f)
CELL = ENTRIES["workload"]["name"]
# the plain cell of a grown copy: tpch-build's traffic and metrics again
PLAIN_LIKE, PLAIN, PLAIN_TRAFFIC = "tpch-build", "sketch-plain-build", "sketch-plain"
# the copy is the one tree in which names of the sketch are a cell's
NO_SKETCH_TEST = "benchmarks/tests/test_manifest.py::test_nothing_of_the_tree_is_named_as_the_sketch_is"


def _sketch_faults() -> dict:
    spec = importlib.util.spec_from_file_location("sketch_faults", os.path.join(ADDED, FAULT_MODULE))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.FAULTS


def _join(manifest: dict, cell: str, metrics) -> None:
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in metrics:
            m["workloads"].append(cell)


def _copy_with_the_cell(tmp_path, fault_module: bool, grown: bool) -> tuple:
    """-> (the copy's root, its manifest)."""
    root = str(tmp_path / "checkout")
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", ".pytest_cache"))
    os.symlink(os.path.join(ROOT, "hyperspace_tpu"), os.path.join(root, "hyperspace_tpu"))
    for base, _dirs, files in os.walk(ADDED):
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ADDED)
            if rel == "manifest_entries.json" or (rel == FAULT_MODULE and not fault_module):
                continue
            dst = os.path.join(bench, rel)
            assert not os.path.exists(dst), f"{rel} would edit a file that is there"
            shutil.copy(os.path.join(base, f), dst)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert not {CELL, PLAIN} & {c["name"] for c in manifest["workloads"]}
    manifest["configs"].append(ENTRIES["config"])
    manifest["workloads"].append(ENTRIES["workload"])
    _join(manifest, CELL, ENTRIES["joins"])
    manifest["per_layer"] += ENTRIES["per_layer"]
    if grown:
        like = next(c for c in manifest["workloads"] if c["name"] == PLAIN_LIKE)
        traffic = os.path.join(bench, "traffic", "{}.json")
        assert not os.path.exists(traffic.format(PLAIN_TRAFFIC))
        shutil.copy(traffic.format(like["traffic"]), traffic.format(PLAIN_TRAFFIC))
        manifest["workloads"].append(dict(like, name=PLAIN, traffic=PLAIN_TRAFFIC))
        _join(manifest, PLAIN, [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
                                if PLAIN_LIKE in m.get("workloads", [])])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return root, manifest


def _pytest(root: str, *args: str):
    """The copy's own tests, run in the copy -> (exit code, {outcome: count}, output)."""
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    p = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", *args],
                       cwd=root, env=env, capture_output=True, text=True, timeout=1500)
    counts = {k: int(n) for n, k in re.findall(r"(\d+) (passed|failed|error|errors)\b",
                                               p.stdout.strip().splitlines()[-1])}
    return p.returncode, counts, p.stdout[-4000:] + p.stderr[-2000:]


@pytest.mark.parametrize("grown", [False, True], ids=["as_it_is", "grown"])
def test_a_second_driver_and_index_kind_come_by_files_alone(tmp_path, grown):
    root, manifest = _copy_with_the_cell(tmp_path, fault_module=True, grown=grown)
    code, _counts, out = _pytest(root, "benchmarks/tests", "--collect-only")
    assert code == 0, out
    code, counts, out = _pytest(root, "benchmarks/tests/test_manifest.py", "--deselect", NO_SKETCH_TEST)
    assert code == 0 and set(counts) == {"passed"}, out
    # each cell resolves to its files and each per-layer metric to its reader
    # and bytes function, the sketch's pair among them
    assert counts["passed"] >= len(manifest["workloads"]) + len(manifest["per_layer"]), out
    # the added cell through the copy's own harness tests: the whole path
    # with the control (correct, served as ZOCI), its driver's fault
    # declared, and each of its faults coming out not correct
    code, counts, out = _pytest(root, "benchmarks/tests/test_harness_cpu.py", "-k", CELL)
    assert code == 0 and counts == {"passed": 2 + len(_sketch_faults())}, out


@pytest.mark.parametrize("grown", [False, True], ids=["as_it_is", "grown"])
def test_a_driver_that_declares_no_fault_fails_one_test_not_the_collection(tmp_path, grown):
    root, manifest = _copy_with_the_cell(tmp_path, fault_module=False, grown=grown)
    code, _counts, out = _pytest(root, "benchmarks/tests", "--collect-only")
    assert code == 0, out
    code, counts, out = _pytest(root, "benchmarks/tests/test_harness_cpu.py", "-k",
                                f"declares_a_fault or ({CELL} and broken_program)")
    # every cell of the copy but the sketch's declares a fault, as the real
    # tree's own run of test_harness_cpu.py holds them to
    assert code == 1 and counts == {"failed": 1, "passed": len(manifest["workloads"]) - 1}, out
    assert f"test_the_cells_driver_declares_a_fault[{CELL}]" in out
    assert "a cell whose check nothing can fail decides nothing" in out


def test_a_new_kernels_roofline_is_read_with_no_edit(monkeypatch):
    """The sketch's two kernel metrics, read by the tree's own reader off
    a small trace that holds a ``jit__interleave`` module (a CPU
    rehearsal has no device line): the bytes function lives in the
    sketch's ``roofline_sketch.py`` and sees the configuration."""
    monkeypatch.syspath_prepend(ADDED)

    def spec(name):
        with open(os.path.join(ADDED, "layer_metrics", name + ".json")) as f:
            return json.load(f)

    with open(os.path.join(ADDED, os.path.relpath(ENTRIES["config"]["file"], "benchmarks"))) as f:
        config = json.load(f)
    modules = [["jit__interleave(7)", 1000, 300], ["jit_lexsort_indices(8)", 1400, 5000],
               ["jit__interleave(9)", 9000, 500]]
    record = {
        "trace": {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": modules}]}]},
        "ops": [{"kind": "build"}, {"kind": "build"}, {"kind": "delete"}],
        "rows": 1000, "config": config, "device": {"kind": "TPU v5 lite"},
    }
    assert {m["name"] for m in ENTRIES["per_layer"]} == {"sketch_interleave_kernel_s", "sketch_interleave_roofline"}
    assert trace_module.read(record, spec("sketch_interleave_kernel_s")["arg"]) == pytest.approx(400e-9)
    # three indexed columns in, ceil(3 x 16 / 32) = 2 planes out: 20 B a row
    # of two builds, at 819 GB/s, over the module's 800 ns
    least = 2 * 1000 * 20 / 819e9
    assert trace_module.read(record, spec("sketch_interleave_roofline")["arg"]) == pytest.approx(100 * least / 800e-9)
    config["index"]["indexed"] = config["index"]["indexed"][:2]    # 2 in, 1 out: 12 B a row
    assert trace_module.read(record, spec("sketch_interleave_roofline")["arg"]) == pytest.approx(
        100 * (2 * 1000 * 12 / 819e9) / 800e-9)
    record["trace"] = None
    assert trace_module.read(record, spec("sketch_interleave_roofline")["arg"]) is None
