"""A cell on TPC-H's ORDERS, with a string payload in its index, is added
by new files and manifest entries alone.

``data/orders_cell/`` holds what a later PR would add for Q13's ORDERS
side: a configuration (``table`` ``orders``; a covering index on
``o_custkey`` including ``o_orderkey`` and ``o_comment``, 200 buckets, 8
files), a traffic file, a minimal driver over ``queries.readback`` and
``queries.structure`` (8 customer keys from the seed, the last a multiple
of 3 with no order), its fault module and the entries for
``BENCHMARK.json``. The test lays them into a temporary copy of
``benchmarks/`` + ``BENCHMARK.json`` — no file of the copy is edited but
the manifest — and runs the copy's CPU rehearsal: every compared number 0,
the ``clipped`` control and the fault seen. The real ``BENCHMARK.json``
has no such cell (its names start ``sketch``, which
``test_manifest.py`` keeps out of the real tree).
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import HERE, ROOT

import harness

ADDED = os.path.join(HERE, "data", "orders_cell")
with open(os.path.join(ADDED, "manifest_entries.json")) as _f:
    ENTRIES = json.load(_f)
CELL = ENTRIES["workload"]["name"]
# orders of a rehearsal: 3,200 customer keys, so that each of the 200
# buckets holds rows as at the configuration's 4,000,000 orders (an empty
# bucket has no file to count)
ROWS = "48000"
SEED = "2147483659"
CHECKS = {"readback_rows_gap", "readback_digest_differs", "point_answers_wrong", "not_index_served",
          "bucket_files_gap", "bucket_rows_gap", "misbucketed_rows", "unsorted_bucket_files"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory) -> str:
    root = str(tmp_path_factory.mktemp("orders_cell") / "checkout")
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), bench,
                    ignore=shutil.ignore_patterns("__pycache__", ".pytest_cache"))
    os.symlink(os.path.join(ROOT, "hyperspace_tpu"), os.path.join(root, "hyperspace_tpu"))
    for base, _dirs, files in os.walk(ADDED):
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ADDED)
            if rel == "manifest_entries.json":
                continue
            dst = os.path.join(bench, rel)
            assert not os.path.exists(dst), f"{rel} would edit a file that is there"
            shutil.copy(os.path.join(base, f), dst)
    manifest = harness.load_manifest(ROOT)
    assert CELL not in {c["name"] for c in manifest["workloads"]}
    manifest["configs"].append(ENTRIES["config"])
    manifest["workloads"].append(ENTRIES["workload"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in ENTRIES["joins"]:
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return root


def _run(root: str, prog: list, trace: int = 0, controls: int = 0) -> tuple:
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    args = ["--workload", CELL, "--seed", SEED, "--seconds", "1", "--trace", str(trace),
            "--controls", str(controls), "--cpu-rehearsal", "--rows", ROWS]
    p = subprocess.run([sys.executable] + prog + args, cwd=root, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_the_cells_files_resolve(checkout):
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    p = subprocess.run([sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
                        "benchmarks/tests/test_manifest.py", "-k", f"{CELL} or top_level or names or reports"],
                       cwd=checkout, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:]
    assert f"test_cell_resolves_to_files[{CELL}] PASSED" in p.stdout


def test_the_rehearsal_is_correct_and_the_clipped_control_is_not(checkout):
    line, err = _run(checkout, ["benchmarks/run.py"], trace=1, controls=1)
    assert line["cpu_rehearsal"] is True and line["correct"] is True and line["attempted"] > 0
    assert set(line["checks"]) == CHECKS
    assert {k: c["value"] for k, c in line["checks"].items()} == dict.fromkeys(CHECKS, 0)
    assert err.strip().splitlines()[-1] == "bench: correct: True"
    clipped = line["controls"]["clipped_strings"]
    assert clipped["correct"] is False
    got = {k: c["value"] for k, c in clipped["checks"].items()}
    # every row's comment is cut: the whole read back and the 7 lookups that have rows
    assert got == {"readback_rows_gap": 0, "readback_digest_differs": 1, "point_answers_wrong": 7,
                   "not_index_served": 0}
    assert {"compiles_in_window.build", "build_scan_s", "build_write_s"} <= set(line["metrics"])


def test_an_altered_comment_is_not_correct(checkout):
    spec = importlib.util.spec_from_file_location(
        "sketch_orders_faults", os.path.join(ADDED, "tests", "faults", "sketch_orders.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    _plant, must, may = module.FAULTS["altered_comment"]
    line, _err = _run(checkout, ["benchmarks/tests/fault_runner.py", "altered_comment"])
    assert line["correct"] is False
    failed = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert must == {"readback_digest_differs"} and must <= failed <= must | may, failed


def test_the_row_count_is_the_named_tables(monkeypatch):
    """An ORDERS configuration states as many rows as orders; LINEITEM,
    four an order. Refused before anything is made; else set-up begins."""
    manifest = harness.load_manifest(ROOT)
    with open(os.path.join(ADDED, "configs", "sketch-orders-1chip.json")) as f:
        orders = json.load(f)
    cell = manifest["workloads"][0]
    monkeypatch.setattr(harness, "device_info", lambda: {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})

    def made(*_a, **_k):
        raise AssertionError("set-up began")

    monkeypatch.setattr(harness.tempfile, "mkdtemp", made)
    for config, refused in ((orders, False), (dict(orders, rows=4 * orders["orders"]), True),
                            (dict(orders, table="lineitem"), True),
                            (dict(orders, table="lineitem", rows=4 * orders["orders"]), False)):
        monkeypatch.setattr(harness, "config_of", lambda *_a, _c=config, **_k: _c)
        with pytest.raises(RuntimeError if refused else AssertionError) as e:
            harness.run_cell(cell["name"], 1, 1.0, False, 0.0, manifest=manifest)
        assert ("row(s) an order" if refused else "set-up began") in str(e.value)
