"""The reduction from a trace to numbers, on a small recorded trace."""

import json
import os

import pytest

import trace_reduce as tr
from conftest import BENCH, HERE
from readers import trace_module


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return json.load(f)


def test_interval_arithmetic():
    u = tr.union([[5, 7], [0, 2], [1, 3], [7, 8], [9, 9]])
    assert u == [[0, 3], [5, 8]] and tr.total(u) == 6
    assert tr.complement(u, 0, 10) == [[3, 5], [8, 10]]


def test_busy_is_a_union_not_a_sum(trace):
    # device 0: [1000,1600) + [5000,5400) + [8000,8100); ops overlap 1200-1300
    assert tr.busy_seconds(trace) == pytest.approx([1100e-9, 100e-9])


def test_module_time_by_name_up_to_the_run_id(trace):
    seconds, runs = tr.module_seconds(trace, "jit__bucket_ids_words")
    assert (seconds, runs) == (pytest.approx(1000e-9), 2)
    assert tr.module_seconds(trace, "jit_never_ran") == (None, 0)


def test_the_hash_metrics_as_their_data_files_read_them(trace):
    # the module ran 1000 ns for two builds of 16 rows: 2 x 16 x 12 B at 819 GB/s
    record = {"trace": trace, "ops": [{"kind": "build"}] * 2, "rows": 16, "config": {},
              "device": {"kind": "TPU v5 lite"}}

    def read(metric, **other):
        with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
            return trace_module.read(dict(record, **other), json.load(f)["arg"])

    assert read("hash_kernel_s") == pytest.approx(500e-9)
    assert read("hash_roofline") == pytest.approx(100 * (2 * 16 * 12 / 819e9) / 1000e-9)
    assert read("hash_kernel_s", ops=[]) is None and read("hash_roofline", trace=None) is None


def test_top_ops_and_gap_attribution(trace):
    ops = dict(tr.top_device_ops(trace))
    assert ops["add_xor_fusion"] == pytest.approx(700e-9)
    assert ops["all-to-all"] == pytest.approx(100e-9)
    assert ops["custom-call:X64SplitHigh"] == pytest.approx(100e-9)
    gaps = dict(tr.idle_gaps(trace, 10000))
    # busiest device idle: 10000 - 1100 = 8900 ns; create_index covers
    # [0,6000)+[7000,9000) of which busy 1000+100; another thread's
    # bench.serve started later and takes its idle [500,1000) from it
    assert gaps["bench.serve"] == pytest.approx((1000 - 500) * 1e-9)
    assert gaps["bench.create_index"] == pytest.approx((8000 - 1100 - 500) * 1e-9)
    assert gaps["bench.delete_vacuum"] == pytest.approx(1000e-9)
    assert gaps[tr.NO_SPAN] == pytest.approx(1000e-9)
    assert sum(gaps.values()) == pytest.approx(8900e-9)
    assert len(tr.describe(trace)) == 5


def test_a_gap_goes_to_the_innermost_span_of_the_program():
    with open(os.path.join(HERE, "data", "nested_trace.json")) as f:
        trace = json.load(f)
    # the device runs [3000,3100) and [7000,7050): idle 10000 - 150 = 9850 ns
    assert tr.busy_seconds(trace) == pytest.approx([150e-9])
    gaps = {k: round(v * 1e9) for k, v in tr.idle_gaps(trace, 10000)}
    assert gaps == {
        "bench.create_index": 300,       # what no stage covers: [0,200) + [6100,6200)
        "hs.action.CreateAction": 400,   # the root's own: [200,500) + [6000,6100)
        "hs.scan": 2000,                 # [500,2500)
        "hs.hash_shuffle": 800,          # [2500,3500) less hs.kernel's [2950,3150)
        "hs.kernel": 100,                # its 200 less the 100 in which the device ran
        "hs.sidecar_capture": 2100,      # [3500,6000) less a later span of another thread
        "hs.write": 400,                 # [4000,4400) on a writer's thread
        "bench.delete_vacuum": 700,      # [6200,7200) less hs.vacuum and 50 busy
        "hs.vacuum": 250,
        tr.NO_SPAN: 2800,                # [7200,10000)
    }
    assert sum(gaps.values()) == 9850
    top = tr.idle_gaps(trace, 10000, n=3)
    assert [k for k, _v in top] == [tr.NO_SPAN, "hs.sidecar_capture", "hs.scan"]
