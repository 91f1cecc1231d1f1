"""``readers/span_attr.py`` on a recorded tree whose spans say what their
seconds went to (the attr's mean, the ``where`` filter, the warm-up's
root read only on request, nothing where the attr is absent), on each
new metric's data file, and in a traced rehearsal of two cells."""

import copy
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

from readers import action_trace, span_attr

with open(os.path.join(HERE, "data", "action_tree.json")) as _f:
    _TREE = json.load(_f)

ARG = {"root": "action.CreateAction", "kind": "build"}
AGG = {"sidecar": "aggstate"}

#: what this test writes on the recorded roots' spans, root by root (the
#: first is the warm-up's): (span, where) -> attrs
INSIDES = [
    {("scan", None): {"read_s": 9.0}, ("action.CreateAction", None): {"compile_s": 7.5, "cpu_s": 30.0}},
    {("scan", None): {"read_s": 1.0, "concat_s": 0.3},
     ("sidecar_capture", "aggstate"): {"files_s": 3.8, "python_s": 1.0, "turn_wait_s": 0},
     ("sidecar_capture", "zonemap"): {"files_s": 0.05},
     ("action.CreateAction", None): {"cpu_s": 20.0}},
    {("scan", None): {"read_s": 1.2},
     ("sidecar_capture", "aggstate"): {"files_s": 4.8, "python_s": 1.4, "turn_wait_s": 0},
     ("action.CreateAction", None): {"cpu_s": 22.0}},
]


def _tree():
    roots = copy.deepcopy(_TREE["roots"])
    for root, insides in zip(roots, INSIDES):
        for span in root["spans"]:
            for (name, sidecar), attrs in insides.items():
                if span["name"] == name and sidecar in (None, span["attrs"].get("sidecar")):
                    span["attrs"].update(attrs)
    return roots


@pytest.fixture
def recorded(monkeypatch):
    roots = _tree()
    monkeypatch.setattr(action_trace, "finished_roots", lambda name: list(roots))
    action_trace._logged.clear()
    return {"ops": [dict(o) for o in _TREE["ops"]]}


def read(record, span, attr, **more):
    return span_attr.read(record, dict(ARG, span=span, attr=attr, **more))


def test_an_attr_is_the_mean_per_operation(recorded):
    assert read(recorded, "scan", "read_s") == pytest.approx((1.0 + 1.2) / 2)
    # held by one of the two builds' spans: still a mean over both
    assert read(recorded, "scan", "concat_s") == pytest.approx(0.3 / 2)
    # a counter reads 0 where it counted nothing
    assert read(recorded, "sidecar_capture", "turn_wait_s", where=AGG) == 0.0


def test_where_picks_the_spans_of_one_kind(recorded):
    assert read(recorded, "sidecar_capture", "files_s", where=AGG) == pytest.approx((3.8 + 4.8) / 2)
    assert read(recorded, "sidecar_capture", "files_s", where={"sidecar": "zonemap"}) == pytest.approx(0.05 / 2)
    # without it, every span of the name is summed
    assert read(recorded, "sidecar_capture", "files_s") == pytest.approx((3.85 + 4.8) / 2)
    assert read(recorded, "sidecar_capture", "files_s", where={"sidecar": "bloom"}) is None


def test_the_roots_own_span_holds_its_counters_and_its_seconds(recorded):
    assert read(recorded, ARG["root"], "cpu_s") == pytest.approx(21.0)
    assert read(recorded, ARG["root"], "duration_s") == pytest.approx((10.0 + 11.0) / 2)
    assert read(recorded, "scan", "duration_s") == pytest.approx(
        action_trace.read(recorded, dict(ARG, spans=["scan"])))


def test_the_warm_up_root_is_read_only_on_request(recorded, monkeypatch, capsys):
    assert read(recorded, "scan", "read_s", op="warmup") == 9.0
    assert read(recorded, ARG["root"], "compile_s", op="warmup") == 7.5
    assert read(recorded, ARG["root"], "duration_s", op="warmup") == _TREE["roots"][0]["duration_s"]
    # the window's builds compiled nothing: their roots hold no such counter
    assert read(recorded, ARG["root"], "compile_s") is None
    # set-up's table is printed once, and marked
    err = capsys.readouterr().err
    assert err.count("(set-up's build)") == 1 and err.count("bench: spans: action.CreateAction") == 3
    # no root before the window's own: nothing
    monkeypatch.setattr(action_trace, "finished_roots", lambda name: _tree()[1:])
    assert read(recorded, "scan", "read_s", op="warmup") is None
    assert read(recorded, "scan", "read_s") == pytest.approx(1.1)


def test_what_is_not_there_gives_nothing(recorded):
    assert read(recorded, "scan", "no_such_attr") is None
    assert read(recorded, "no_such_span", "read_s") is None
    assert read(recorded, "scan", "no_such_attr", op="warmup") is None
    assert span_attr.read(recorded, {"root": ARG["root"], "kind": "refresh",
                                     "span": "scan", "attr": "read_s"}) is None


@pytest.mark.parametrize("more", [{}, {"op": "warmup"}])
def test_a_root_longer_than_its_operation_gives_nothing(recorded, more):
    recorded["ops"][0]["wall_s"] = 9.9      # the first of the window's roots took 10.0 s
    assert read(recorded, "scan", "read_s", **more) is None


def _span_attr_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    out = []
    for m in manifest["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        if spec["reader"] == "span_attr":
            out.append((m, spec["arg"]))
    return out


@pytest.mark.parametrize("metric,arg", _span_attr_metrics(), ids=lambda v: v.get("name", "") if "moves" in v else "")
def test_each_metrics_file_names_a_declared_span(metric, arg):
    sys.path.insert(0, ROOT)
    from hyperspace_tpu.obs import sites

    assert set(arg) <= {"root", "kind", "span", "attr", "where", "op"}
    assert arg["span"] in sites.BUILD_STAGES or arg["span"] == arg["root"]
    assert metric["layer"] == "Build" and metric["unit"] == "s"
    # set-up's metrics read the warm-up's root, the window's read the window's
    assert (arg.get("op") == "warmup") == (metric["moves"] == "setup_s")


def _rehearse(cell, rows):
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2**31 + 36), "--seconds", "1", "--trace", "1",
         "--cpu-rehearsal", "--rows", str(rows)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    return {k: v["value"] for k, v in line["metrics"].items()}, p.stderr


def test_traced_rehearsal_splits_the_scan_and_the_capture():
    got, err = _rehearse("tpch-build", 12000)
    for name in ("scan_read_s", "scan_decode_s", "scan_concat_s", "agg_capture_wall_s",
                 "agg_python_s", "agg_sweep_s", "agg_turn_wait_s", "build_cpu_s",
                 "warmup_build_s"):
        assert name in got, sorted(got)
    assert "zorder_encode_order_s" not in got and "zorder_words_scale_s" not in got
    # 12,000 rows hash on the host: set-up's build compiled nothing, and says nothing
    assert "warmup_compile_s" not in got and got["warmup_build_s"] > 0
    assert got["scan_read_s"] + got["scan_decode_s"] + got["scan_concat_s"] <= got["build_scan_s"]
    assert got["scan_read_s"] >= 0.5 * got["build_scan_s"]
    assert 0 < got["agg_python_s"] <= got["agg_capture_wall_s"] <= got["build_sidecar_s"]
    assert got["agg_sweep_s"] > 0 and got["build_cpu_s"] > 0
    assert got["compiles_in_window.build"] == 0
    table = [ln for ln in err.splitlines() if "bench: spans:" in ln]
    assert any("python_s=" in ln and "turn_wait_s=" in ln for ln in table)
    assert not any("compile_s=" in ln for ln in table)      # the window's builds compiled nothing


def test_traced_rehearsal_splits_the_zorder_encode():
    got, _err = _rehearse("tpch-zorder-build", 12000)
    for name in ("zorder_encode_order_s", "zorder_words_scale_s", "scan_read_s",
                 "agg_python_s", "agg_sweep_s", "build_cpu_s", "warmup_build_s", "warmup_compile_s"):
        assert name in got, sorted(got)
    assert 0 < got["zorder_encode_order_s"] <= got["zorder_encode_s"]
    assert 0 < got["zorder_words_scale_s"] <= got["zorder_interleave_s"]
    # one file, one worker: the turn is never another task's
    assert got["agg_turn_wait_s"] == 0
    assert got["agg_python_s"] + got["agg_sweep_s"] <= got["agg_capture_wall_s"]
    assert 0 < got["warmup_compile_s"] <= got["warmup_build_s"]
