"""The mesh cell's own checks: the exchange broken two ways has to come
out not ``correct`` by the numbers that can see it, the exchange's
per-layer metrics have to be read on the path that crosses the chips,
and the exchange's roofline has to count what the configuration states.

Children held to the CPU, four host devices, the ``compact`` strategy
forced inside the runner (``auto`` on a CPU mesh is ``host``)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

import roofline_exchange
from readers import exchange_module

CELL = "tpch-mesh-build"
# enough rows that a bucket keeps some when three quarters of them vanish
ROWS = "48000"

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

# fault -> (the numbers that must see it, the numbers that also may)
CAUGHT_BY = {
    "peer_rows_dropped": ({"bucket_rows_gap", "readback_rows_gap", "readback_digest_differs"},
                          {"point_answers_wrong"}),
    "peers_swapped": ({"misbucketed_rows"},
                      {"unsorted_bucket_files", "point_answers_wrong"}),
}


def _run(fault: str, trace: int = 0):
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    args = ["--workload", CELL, "--seed", str(2**31 + 29), "--seconds", "1", "--trace", str(trace),
            "--cpu-rehearsal", "--rows", ROWS]
    p = subprocess.run([sys.executable, os.path.join(HERE, "mesh_fault_runner.py"), fault] + args,
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_the_exchange_metrics_are_read_on_the_path_that_crosses_chips():
    line, err = _run("none", trace=1)
    assert line["correct"] is True and line["device"]["count"] == 4
    assert line["resolved"]["exchange_strategy"] == ["compact"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for name in ("exchange_host_pack_s", "exchange_device_s", "exchange_transfer_s",
                 "exchange_slot_bytes.build", "build_tail_wall_s"):
        assert got[name] > 0, name
    # the device leg holds its transfers; no device trace on the CPU
    assert got["exchange_transfer_s"] <= got["exchange_device_s"]
    assert "exchange_kernel_s" not in got and "exchange_roofline" not in got
    assert got["build_unattributed_share"] < 3.0
    per_layer = {m["name"] for m in MANIFEST["per_layer"] if CELL in m["workloads"]}
    # the mesh path hashes on the host: of the hash's metrics only the key reps are there
    assert set(got) <= per_layer and {n for n in per_layer if n.startswith("hash_")} == {"hash_key_reps_s"}
    assert got["hash_key_reps_s"] > 0
    for span in ("exchange_plan", "pack", "exchange", "h2d", "kernel", "d2h", "unpack"):
        assert f"bench: spans:       {span}" in err or f"bench: spans:         {span}" in err, span


@pytest.mark.parametrize("fault", sorted(CAUGHT_BY))
def test_a_broken_exchange_comes_out_not_correct(fault):
    line, err = _run(fault)
    assert line["correct"] is False, (line["checks"], err[-1500:])
    failed = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    must, may = CAUGHT_BY[fault]
    assert must <= failed <= must | may, failed
    if fault == "peers_swapped":
        assert line["checks"]["readback_rows_gap"]["value"] == 0


def test_roofline_counts_what_the_configuration_states():
    config = json.load(open(os.path.join(BENCH, "configs", "tpch-4chip-mesh.json")))
    assert roofline_exchange.index_row_bytes(config) == 28
    n_bytes = roofline_exchange.bytes_out_of_a_chip(config["rows"], config["chips"], 28)
    assert n_bytes == 84_000_000
    assert roofline_exchange.least_seconds(n_bytes, "TPU v5 lite") == pytest.approx(0.42e-3)


def _record(strategy, module, durations_ns, trace=True):
    planes = [{"name": f"/device:TPU:{d}", "lines": [{"name": "XLA Modules", "events": [
        [f"{module}({7 + i})", 1000.0 * i, float(ns)] for i, ns in enumerate(per_chip)]}]}
        for d, per_chip in enumerate(durations_ns)]
    config = json.load(open(os.path.join(BENCH, "configs", "tpch-4chip-mesh.json")))
    ops = [{"kind": "build", "telemetry": {"shuffle_strategy": strategy} if strategy else {}}] * 2
    return {"trace": {"planes": planes} if trace else None, "ops": ops, "config": config,
            "rows": config["rows"], "device": {"kind": "TPU v5 lite", "count": 4}}


def test_exchange_module_reader():
    seconds = {"kind": "build", "stat": "seconds_per_op"}
    share = {"kind": "build", "stat": "roofline"}
    # two builds; the busiest chip spent 1.5 ms + 1.3 ms in the program
    rec = _record("compact", "jit__compact_program", [[1.0e6, 1.1e6], [1.5e6, 1.3e6]])
    assert exchange_module.read(rec, seconds) == pytest.approx(1.4e-3)
    assert exchange_module.read(rec, share) == pytest.approx(100 * 0.42e-3 / 1.4e-3)
    # nothing to read: no trace, the parent's silence, the host strategy, another program
    assert exchange_module.read(_record("compact", "jit__compact_program", [[1e6]], trace=False), seconds) is None
    assert exchange_module.read(_record(None, "jit__compact_program", [[1e6]]), seconds) is None
    assert exchange_module.read(_record("host", "jit__compact_program", [[1e6]]), share) is None
    assert exchange_module.read(_record("flat", "jit__compact_program", [[1e6]]), share) is None
