"""``readers/action_trace.py`` on a recorded tree (each ``arg`` form, and
the cases in which it has to give nothing), on the program's own spans,
and in a traced rehearsal of the build cell."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

from readers import action_trace

with open(os.path.join(HERE, "data", "action_tree.json")) as _f:
    TREE = json.load(_f)

ARG = {"root": "action.CreateAction", "kind": "build"}
LOG = ["validate", "begin_log", "log_entry", "log_commit", "publish_event"]


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(action_trace, "finished_roots", lambda name: list(TREE["roots"]))
    action_trace._logged.clear()
    return {"ops": [dict(o) for o in TREE["ops"]]}


def read(record, **arg):
    return action_trace.read(record, dict(ARG, **arg))


def test_spans_are_the_mean_union_per_operation(recorded):
    # the zone-map's 0.1 s and the aggregate sidecar's 4 s, then 5 s
    assert read(recorded, spans=["sidecar_capture"]) == pytest.approx((4.1 + 5.1) / 2)
    assert read(recorded, spans=LOG) == pytest.approx(0.14)
    assert read(recorded, spans=["h2d", "d2h"]) == pytest.approx(0.3 + 0.399)
    # sort and write overlap by 0.3 s in the second build: counted once
    assert read(recorded, spans=["sort", "write"]) == pytest.approx((3.0 + 3.0) / 2)


def test_a_summed_span_has_no_interval(recorded):
    assert read(recorded, spans=["pack"]) is None
    assert read(recorded, spans=["pack", "kernel"]) == pytest.approx(0.001)


def test_unattributed_share_has_the_wall_time_below_it(recorded):
    # direct children leave 0.07 + 0.09 s of each root uncovered, and the
    # operations' wall time holds 0.3 and 0.8 s more than the roots
    named = (10.0 - 0.16) + (11.0 - 0.16)
    assert read(recorded, unattributed_share=True) == pytest.approx(
        100.0 * (22.5 - named) / 22.5)


def test_counter_is_the_mean_per_operation(recorded):
    assert read(recorded, counter="d2h_bytes") == 64.0
    assert read(recorded, counter="rows") == 1000.0


def test_what_is_not_there_gives_nothing(recorded):
    assert read(recorded, spans=["no_such_span"]) is None
    assert read(recorded, counter="no_such_counter") is None
    assert action_trace.read(recorded, {"root": ARG["root"], "kind": "refresh",
                                        "spans": ["scan"]}) is None


def test_the_warm_up_root_is_never_read(recorded, monkeypatch):
    # three roots, two operations: the last two are paired, in order
    pairs = action_trace.paired_roots(recorded, ARG)
    assert [r["trace_id"] for r, _o in pairs] == ["one", "two"]
    assert [o["wall_s"] for _r, o in pairs] == [10.5, 12.0]
    # only the warm-up's root there (a window whose builds left none)
    monkeypatch.setattr(action_trace, "finished_roots", lambda name: TREE["roots"][:1])
    assert read(recorded, spans=["scan"]) is None


@pytest.mark.parametrize("arg", [{"spans": ["sidecar_capture"]},
                                 {"unattributed_share": True}, {"counter": "d2h_bytes"}])
def test_a_root_longer_than_its_operation_gives_nothing(recorded, arg):
    recorded["ops"][0]["wall_s"] = 9.9      # the first root took 10.0 s
    assert read(recorded, **arg) is None


def test_one_table_per_root_goes_to_stderr_once(recorded, capsys):
    read(recorded, spans=["scan"])
    read(recorded, counter="rows")
    err = capsys.readouterr().err
    assert err.count("bench: spans: action.CreateAction") == 2
    line = next(ln for ln in err.splitlines() if "hash_shuffle" in ln)
    seconds, self_s = line.split()[3:5]
    assert float(seconds) == pytest.approx(1.2) and float(self_s) == pytest.approx(0.0, abs=1e-6)
    assert any("pack" in ln and "summed" in ln for ln in err.splitlines())
    assert any("sidecar=aggstate" in ln for ln in err.splitlines())


def test_the_programs_own_roots_come_in_the_neutral_form():
    from hyperspace_tpu.obs import trace

    trace.reset()
    root = trace.root("action.Probe", always=True, index="x")
    with trace.activate(root):
        with trace.span("scan"):
            trace.accumulate("rows", 5)
        trace.stage("pack", seconds=0.5)
    root.finish()
    try:
        got = action_trace.finished_roots("action.Probe")
    finally:
        trace.reset()
    assert len(got) == 1 and got[0]["attrs"] == {"index": "x", "rows": 5}
    spans = {s["name"]: s for s in got[0]["spans"]}
    assert set(spans) == {"scan", "pack", "action.Probe"}
    assert spans["scan"]["parent_id"] == got[0]["span_id"] and not spans["scan"]["summed"]
    assert spans["pack"]["summed"] and spans["scan"]["start_ns"] <= spans["scan"]["end_ns"]
    assert action_trace.finished_roots("action.Nobody") == []


def test_traced_rehearsal_reports_the_build_account():
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "tpch-build",
         "--seed", str(2**31 + 29), "--seconds", "1", "--trace", "1",
         "--cpu-rehearsal", "--rows", "12000"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    got = line["metrics"]
    for name in ("build_sidecar_s", "build_log_s", "build_unattributed_share",
                 "build_scan_s", "build_partition_s", "hash_key_reps_s"):
        assert name in got, sorted(got)
        assert got[name]["value"] > 0, name
    assert got["build_unattributed_share"]["value"] < 10 and "build_unnamed_share" not in got
    # 12,000 rows hash on the host: no device round trip to read, no words to split
    assert "hash_transfer_s" not in got and "hash_d2h_bytes.build" not in got
    assert "hash_split_words_s" not in got
    gaps = dict(line["breakdown"]["idle_gaps"])
    assert {"hs.scan", "hs.sidecar_capture"} <= set(gaps), sorted(gaps)
    assert "bench: spans: action.CreateAction" in p.stderr
    assert any("host_hash" in ln for ln in p.stderr.splitlines() if "bench: spans:" in ln)
