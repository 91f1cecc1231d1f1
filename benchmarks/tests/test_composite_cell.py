"""The composite-key cell's own checks: a rehearsal traced and untraced
prints what the manifest lists for it, the metrics this cell brought are
read off the spans of a two-key build, the hash's roofline counts the
configuration's indexed columns, and the seed's lookups end on a pair
that is not in the table.

Children held to the CPU; no number of a rehearsal is a device metric."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import BENCH, ROOT

import reference_composite
import roofline
import roofline_composite
from drivers import composite_build
from readers import trace_module

CELL = "tpch-q9-build"
ROWS = 12000

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(BENCH, "configs", "tpch-q9-1chip.json")) as _f:
    CONFIG = json.load(_f)

# what no CPU rehearsal can read: the device's trace and memory, and the
# spans of the device hash (12,000 rows hash on the host)
DEVICE_ONLY = {"hash_kernel_s", "hash_keys_roofline", "device_idle_share.build", "device_peak_mem_gb.build",
               "hash_transfer_s", "hash_d2h_bytes.build", "hash_h2d_bytes.build", "hash_split_words_s",
               "warmup_compile_s"}


def _rehearse(trace: int):
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", str(2**31 + 38),
         "--seconds", "1", "--trace", str(trace), "--cpu-rehearsal", "--rows", str(ROWS)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["cpu_rehearsal"] is True and line["failed"] == 0
    assert sorted(line["checks"]) == sorted([
        "readback_rows_gap", "readback_digest_differs", "point_answers_wrong", "not_index_served",
        "bucket_files_gap", "bucket_rows_gap", "misbucketed_rows", "unsorted_bucket_files"])
    assert all(c == {"value": 0, "limit": 0} for c in line["checks"].values())
    return {k: v["value"] for k, v in line["metrics"].items()}, p.stderr


def test_the_untraced_line_has_the_cells_two_end_to_end_metrics():
    got, _err = _rehearse(0)
    assert set(got) == {"build_rows_per_s", "setup_s"} and all(v > 0 for v in got.values())


def test_the_traced_line_has_every_metric_a_rehearsal_can_read():
    got, err = _rehearse(1)
    listed = {m["name"] for m in MANIFEST["per_layer"] if CELL in m["workloads"]}
    assert set(got) == listed - DEVICE_ONLY, sorted(listed - DEVICE_ONLY - set(got))
    assert "hash_roofline" not in listed     # its bytes are one 8-byte key's
    # the five this cell brought for the host's side of a build
    assert 0 < got["build_bucket_sorts_s"] and 0 < got["bucket_sorts_thread_s"]
    assert 0 < got["write_take_s"] < got["write_thread_s"]
    assert got["build_unattributed_share"] < 3.0 and got["compiles_in_window.build"] == 0
    table = [ln for ln in err.splitlines() if "bench: spans:" in ln]
    assert any("key_reps" in ln and "key_columns=2" in ln for ln in table)
    assert any("bucket_sorts" in ln and "planes=4" in ln and "max_rows=" in ln for ln in table)
    assert any(" write " in ln and "take_s=" in ln and "encode_s=" in ln and "columns=6" in ln for ln in table)
    assert any("action.CreateAction" in ln and "key_columns=2" in ln for ln in table)
    assert sum("pair lookup" in ln for ln in err.splitlines()) == 8


def test_the_hash_roofline_counts_the_configurations_key_columns():
    assert CONFIG["index"]["indexed"] == ["l_partkey", "l_suppkey"]
    assert roofline_composite.bucket_hash_bytes(16_000_000, CONFIG) == 16_000_000 * 20
    one = dict(CONFIG, index=dict(CONFIG["index"], indexed=["l_orderkey"]))
    assert roofline_composite.bucket_hash_bytes(1000, one) == roofline.bucket_hash_bytes(1000, one) == 12_000
    with open(os.path.join(BENCH, "layer_metrics", "hash_keys_roofline.json")) as f:
        spec = json.load(f)
    modules = [["jit__bucket_ids_words(3)", 1000, 2000], ["jit__bucket_ids_words(3)", 9000, 3000]]
    record = {
        "trace": {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": modules}]}]},
        "ops": [{"kind": "build"}, {"kind": "build"}], "rows": 1000, "config": CONFIG,
        "device": {"kind": "TPU v5 lite"},
    }
    # two builds of 1000 rows x 20 B at 819 GB/s over the module's 5000 ns
    assert trace_module.read(record, spec["arg"]) == pytest.approx(100 * (2 * 1000 * 20 / 819e9) / 5000e-9)
    record["trace"] = None
    assert trace_module.read(record, spec["arg"]) is None


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 38, 3800000101])
def test_the_seeds_lookups_end_on_a_pair_that_is_not_there(seed):
    rng = np.random.default_rng(5)
    parts = rng.integers(1, 400, 6000)
    cols = {"l_partkey": parts, "l_suppkey": (parts + rng.integers(0, 4, 6000) * 5) % 20 + 1}
    ctx = SimpleNamespace(config=CONFIG, traffic={"pair_lookups": 8}, seed=seed, rows=6000, items_cols=cols)
    index = reference_composite.PairIndex(cols, "l_partkey", "l_suppkey")
    pairs = composite_build.lookup_pairs(ctx, index)
    assert len(pairs) == 8 and pairs == composite_build.lookup_pairs(ctx, index)
    assert all(len(index.rows_of(a, b)) > 0 for a, b in pairs[:-1])
    a, b = pairs[-1]
    assert len(index.rows_of(a, b)) == 0 and a in parts and b in cols["l_suppkey"]
