"""Observability plane (hyperspace_tpu/obs/, docs/observability.md).

Four legs, mirroring the ISSUE's acceptance criteria:

* span propagation: a pipelined join serve's stage spans (recorded on
  scan-pool and per-bucket-pool worker threads) attach to the query's
  root span, and parent-child integrity holds under a concurrent
  client storm;
* metrics exact-accounting: the registry's live views ARE the
  frontend/cache ``stats()`` dicts and the breakdown instruments ARE
  ``last_serve_breakdown`` — one storage, never a fork;
* trace linkage across the fleet claim/spool plane: a cross-process
  single-flight loser's root span records the winner's trace id;
* querylog: one row per executed query, schema-valid, replayable
  (rotation + crash-mid-rotate recovery live in
  ``tests/test_crash_recovery.py::TestQuerylogRotateCrash``).
"""

import os
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.indexes.covering import CoveringIndexConfig
from hyperspace_tpu.obs import merge_snapshots, metrics, querylog, trace
from hyperspace_tpu.serve.frontend import ServeFrontend


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Tracing is a process-global switch: leave it OFF and the ring
    empty for whatever test runs next."""
    trace.reset()
    yield
    trace.set_enabled(False)
    trace.reset()


def _lake(tmp_path, n=20_000, n_orders=2_000):
    rng = np.random.default_rng(23)
    idir, odir = tmp_path / "items", tmp_path / "orders"
    idir.mkdir()
    odir.mkdir()
    items = pa.table(
        {
            "k": rng.integers(0, n_orders, n).astype(np.int64),
            "q": rng.integers(1, 51, n).astype(np.int64),
        }
    )
    orders = pa.table(
        {
            "ok": np.arange(n_orders, dtype=np.int64),
            "cust": rng.integers(0, 500, n_orders).astype(np.int64),
        }
    )
    for i in range(4):
        lo, hi = i * n // 4, (i + 1) * n // 4
        pq.write_table(items.slice(lo, hi - lo), str(idir / f"p{i}.parquet"))
        lo, hi = i * n_orders // 4, (i + 1) * n_orders // 4
        pq.write_table(orders.slice(lo, hi - lo), str(odir / f"p{i}.parquet"))
    return str(idir), str(odir)


@pytest.fixture
def obs_env(session_factory, tmp_path):
    """One obs-enabled session over an indexed two-table lake."""
    s = session_factory(1)
    idir, odir = _lake(tmp_path)
    hs = Hyperspace(s)
    items = s.read.parquet(idir)
    orders = s.read.parquet(odir)
    hs.create_index(items, CoveringIndexConfig("oi1", ["k"], ["q"]))
    hs.create_index(orders, CoveringIndexConfig("oo1", ["ok"], ["cust"]))
    s.enable_hyperspace()
    s.conf.set(C.OBS_ENABLED, True)
    return {"s": s, "hs": hs, "items": items, "orders": orders,
            "idir": idir, "odir": odir}


def _assert_trace_integrity(root):
    """Every recorded span belongs to the root's trace and its parent
    chain terminates at the root."""
    by_id = {sp.span_id: sp for sp in root.spans}
    by_id[root.span_id] = root
    for sp in root.spans:
        assert sp.trace_id == root.trace_id, (sp.name, sp.trace_id)
        if sp is root:
            continue
        assert sp.parent_id in by_id, (sp.name, sp.parent_id)
        hops, cur = 0, sp
        while cur is not root:
            cur = by_id[cur.parent_id]
            hops += 1
            assert hops < 100, "parent cycle"
        assert sp.duration_s is not None and sp.duration_s >= 0.0


# ---------------------------------------------------------------------------
# Trace core
# ---------------------------------------------------------------------------


class TestTraceCore:
    def test_disabled_is_noop(self):
        trace.set_enabled(False)
        assert trace.root("serve.query") is trace.NOOP
        with trace.span("scan") as sp:
            assert sp is trace.NOOP
        trace.stage("scan", 0.0)
        assert trace.finished() == []
        assert trace.current_trace_id() is None

    def test_root_child_shape(self):
        trace.set_enabled(True)
        root = trace.root("serve.query", slo_class="t")
        with trace.activate(root):
            with trace.span("pin"):
                pass
            trace.stage("scan", seconds=0.25)
            trace.event("retry", attempt=2)
        root.finish()
        roots = trace.finished("serve.query")
        assert len(roots) == 1
        _assert_trace_integrity(roots[0])
        stages = roots[0].stage_seconds()
        assert set(stages) == {"pin", "scan"}
        assert abs(stages["scan"] - 0.25) < 0.02
        assert roots[0].events[0]["name"] == "retry"
        assert roots[0].attrs["slo_class"] == "t"

    def test_finish_idempotent_and_span_cap(self):
        trace.set_enabled(True)
        import hyperspace_tpu.obs.trace as tr

        old = tr._max_spans
        tr._max_spans = 3
        try:
            root = trace.root("serve.query")
            with trace.activate(root):
                for _ in range(10):
                    with trace.span("scan"):
                        pass
            root.finish()
            root.finish()  # idempotent
            assert len(trace.finished()) == 1
            assert len(root.spans) == 3
            assert root.spans_dropped > 0
        finally:
            tr._max_spans = old

    def test_carry_propagates_across_pool(self):
        from concurrent.futures import ThreadPoolExecutor

        trace.set_enabled(True)
        root = trace.root("serve.query")
        with trace.activate(root):
            with ThreadPoolExecutor(max_workers=4) as pool:
                # carried: records under the root from worker threads
                list(
                    pool.map(
                        trace.carry(lambda i: trace.stage("scan", 0.0)),
                        range(8),
                    )
                )
                # NOT carried: context does not leak to pool threads
                def bare(i):
                    assert trace.current() is None
                    return i

                list(pool.map(bare, range(4)))
        root.finish()
        _assert_trace_integrity(root)
        assert len([s for s in root.spans if s.name == "scan"]) == 8

    def test_ring_bounded_by_retain(self):
        trace.set_enabled(True)
        import hyperspace_tpu.obs.trace as tr

        with tr._rec_lock:
            old = tr._finished.maxlen
        from collections import deque

        with tr._rec_lock:
            tr._finished = deque(maxlen=5)
        try:
            for _ in range(12):
                trace.root("serve.query").finish()
            assert len(trace.finished()) == 5
        finally:
            with tr._rec_lock:
                tr._finished = deque(maxlen=old)


# ---------------------------------------------------------------------------
# merge_snapshots (the one documented counter-merge helper)
# ---------------------------------------------------------------------------


class TestMergeSnapshots:
    def test_sum_max_drop_semantics(self):
        a = {
            "completed": 3,
            "p50_ms": 10.0,
            "snapshot_at_ms": 100,
            "high_water_bytes": 50,
            "max_bytes": 100,
            "fleet": {"spool_hits": 1},
            "name": "a",
        }
        b = {
            "completed": 4,
            "p50_ms": 99.0,
            "snapshot_at_ms": 200,
            "high_water_bytes": 70,
            "max_bytes": 100,
            "fleet": {"spool_hits": 2},
            "name": "b",
        }
        m = merge_snapshots(a, b)
        assert m["completed"] == 7  # counters sum
        assert "p50_ms" not in m  # percentiles do not merge
        assert m["snapshot_at_ms"] == 200  # stamps take the max
        assert m["high_water_bytes"] == 70  # watermarks take the max
        assert m["max_bytes"] == 100
        assert m["fleet"]["spool_hits"] == 3  # nested dicts merge
        assert m["name"] == "a"  # non-numeric keeps first

    def test_empty_and_non_dict_tolerated(self):
        assert merge_snapshots() == {}
        assert merge_snapshots({}, None, {"x": 1}) == {"x": 1}


# ---------------------------------------------------------------------------
# Serve-path spans: propagation through scan/prepare pools
# ---------------------------------------------------------------------------


class TestServeSpans:
    def test_one_root_per_query_with_stage_children(self, obs_env):
        s, items = obs_env["s"], obs_env["items"]
        fe = ServeFrontend(s)
        try:
            q = items.filter(items["k"] == 7).select("k", "q")
            out = fe.serve(q)
        finally:
            fe.close()
        roots = trace.finished("serve.query")
        assert len(roots) == 1
        root = roots[0]
        _assert_trace_integrity(root)
        stages = root.stage_seconds()
        assert "queue_wait" in stages
        assert "pin" in stages
        assert "execute" in stages
        assert root.attrs["status"] == "ok"
        assert root.attrs["rows_returned"] == out.num_rows
        assert root.attrs["fingerprint"]
        assert root.attrs["indexes"] == ["oi1"]
        assert root.attrs["rule"] == "filter"
        # predicate shape is literal-scrubbed
        assert "7" not in root.attrs["predicate"].replace("int64", "")

    def test_join_spans_cross_scan_pool(self, obs_env):
        """The pipelined join's scan/prepare/match stages record on
        scan-pool and per-bucket-pool worker threads; trace.carry must
        hand them the root context — the breakdown keys and the span
        names are the same taxonomy."""
        from hyperspace_tpu.execution import join_exec

        s, items, orders = obs_env["s"], obs_env["items"], obs_env["orders"]
        fe = ServeFrontend(s)
        try:
            q = orders.join(items, on=orders["ok"] == items["k"]).select(
                "ok", "cust", "q"
            )
            fe.serve(q)
        finally:
            fe.close()
        roots = trace.finished("serve.query")
        assert len(roots) == 1
        root = roots[0]
        _assert_trace_integrity(root)
        stages = root.stage_seconds()
        for want in ("scan", "prepare", "match", "expand", "assemble"):
            assert want in stages, (want, sorted(stages))
        # span timings and the legacy breakdown are the SAME measurement
        # (this was the only query since the executor's reset)
        bd = dict(join_exec.last_serve_breakdown)
        for stage_name, sec in bd.items():
            assert stage_name in stages, stage_name
            assert abs(stages[stage_name] - sec) < 0.05, (stage_name, sec)
        assert root.attrs["rule"] == "join"
        assert set(root.attrs["indexes"]) == {"oi1", "oo1"}

    def test_obs_off_bit_identical_and_traceless(self, obs_env):
        s, items = obs_env["s"], obs_env["items"]
        q = items.filter(items["k"] == 9).select("k", "q")
        fe = ServeFrontend(s)
        try:
            with_obs = fe.serve(q)
        finally:
            fe.close()
        s.conf.set(C.OBS_ENABLED, False)
        trace.reset()
        fe2 = ServeFrontend(s)
        try:
            without = fe2.serve(q)
        finally:
            fe2.close()
        assert with_obs.equals(without)
        assert trace.finished() == []

    def test_concurrent_parent_child_integrity(self, obs_env):
        """16 clients x 4 distinct queries each: every trace's spans
        chain to ITS root (no cross-trace leakage through the shared
        scan pool), and roots == executions (dedup shares a trace)."""
        s, items = obs_env["s"], obs_env["items"]
        s.conf.set(C.SERVE_MAX_QUEUE_DEPTH, 0)
        fe = ServeFrontend(s)
        errors = []
        try:
            def client(ci):
                try:
                    for j in range(4):
                        k = (ci * 17 + j * 5) % 200
                        q = items.filter(items["k"] == k).select("k", "q")
                        fe.serve(q)
                except Exception as exc:  # pragma: no cover
                    errors.append(exc)

            threads = [
                threading.Thread(target=client, args=(ci,))
                for ci in range(16)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stats = fe.stats()
        finally:
            fe.close()
        assert not errors, errors[:3]
        roots = trace.finished("serve.query")
        assert len(roots) == stats["completed"]
        assert stats["completed"] + stats["deduped"] == stats["admitted"]
        seen_trace_ids = set()
        for root in roots:
            _assert_trace_integrity(root)
            assert root.trace_id not in seen_trace_ids
            seen_trace_ids.add(root.trace_id)


# ---------------------------------------------------------------------------
# Metrics: views ARE the stats, instruments ARE the breakdowns
# ---------------------------------------------------------------------------


class TestMetricsAccounting:
    def test_frontend_view_is_stats(self, obs_env):
        s, items = obs_env["s"], obs_env["items"]
        fe = ServeFrontend(s)
        try:
            for k in (1, 2, 3):
                fe.serve(items.filter(items["k"] == k).select("k"))
            snap = metrics.registry.snapshot()
            direct = fe.stats()
            view = snap["views"]["serve_frontend"]
            for key in ("admitted", "completed", "deduped", "shed",
                        "retries", "degraded", "failed"):
                assert view[key] == direct[key], key
            assert direct["completed"] == 3
            assert "snapshot_at_ms" in direct
        finally:
            fe.close()
        # closed frontends unregister; the exporter must not fail
        assert "serve_frontend" not in metrics.registry.snapshot()["views"]

    def test_breakdown_is_registry_instrument(self, obs_env):
        from hyperspace_tpu.execution import join_exec
        from hyperspace_tpu.indexes import covering_build

        inst = metrics.registry.stage_timer("hs_serve_stage_seconds")
        assert inst.data is join_exec.last_serve_breakdown
        binst = metrics.registry.stage_timer("hs_build_stage_seconds")
        assert binst.data is covering_build.last_build_breakdown
        s, items, orders = obs_env["s"], obs_env["items"], obs_env["orders"]
        fe = ServeFrontend(s)
        try:
            fe.serve(orders.join(items, on=orders["ok"] == items["k"]))
        finally:
            fe.close()
        assert inst.snapshot() == dict(join_exec.last_serve_breakdown)
        assert inst.snapshot(), "join recorded no stages"

    def test_serve_cache_view_live(self, obs_env):
        s = obs_env["s"]
        s.conf.set(C.SERVE_CACHE_ENABLED, True)
        cache = s.serve_cache
        assert cache is not None
        snap = metrics.registry.snapshot()["views"]["serve_cache"]
        assert snap == cache.stats() or (
            # snapshot_at_ms may tick between the two reads
            {k: v for k, v in snap.items() if k != "snapshot_at_ms"}
            == {
                k: v
                for k, v in cache.stats().items()
                if k != "snapshot_at_ms"
            }
        )

    def test_prometheus_render_contains_instruments(self, obs_env):
        s, items = obs_env["s"], obs_env["items"]
        fe = ServeFrontend(s)
        try:
            fe.serve(items.filter(items["k"] == 5).select("k"))
            text = metrics.registry.render_prometheus()
        finally:
            fe.close()
        assert "# TYPE hs_obs_traces_total counter" in text
        assert "hs_view_serve_frontend" in text
        assert 'key="completed"' in text

    def test_events_counter_and_emit_time_stamp(self, obs_env):
        from hyperspace_tpu import telemetry as T

        s = obs_env["s"]
        before = metrics.events_total.snapshot().get("CreateActionEvent", 0)
        ev = T.CreateActionEvent(index_name="x")
        assert ev.timestamp_ms == 0  # NOT stamped at construction
        s.event_logging.log_event(ev)
        assert ev.timestamp_ms > 0  # stamped at emit
        after = metrics.events_total.snapshot().get("CreateActionEvent", 0)
        assert after == before + 1

    def test_jsonl_event_logger_writes(self, obs_env, tmp_path):
        from hyperspace_tpu import telemetry as T

        s = obs_env["s"]
        path = str(tmp_path / "events.jsonl")
        s.conf.set(C.OBS_EVENTLOG_PATH, path)
        s.conf.set(
            C.EVENT_LOGGER_CLASS,
            "hyperspace_tpu.telemetry.JsonlEventLogger",
        )
        s.event_logging.log_event(T.RefreshActionEvent(index_name="idx"))
        s.event_logging.log_event(T.VacuumActionEvent(index_name="idx"))
        recs = metrics.read_jsonl(path)
        assert [r["event"] for r in recs] == [
            "RefreshActionEvent",
            "VacuumActionEvent",
        ]
        assert all(r["timestamp_ms"] > 0 for r in recs)
        assert recs[0]["index_name"] == "idx"


# ---------------------------------------------------------------------------
# Lifecycle action spans
# ---------------------------------------------------------------------------


class TestActionSpans:
    def test_create_action_root_with_build_stages(
        self, session_factory, tmp_path
    ):
        s = session_factory(1)
        idir, _odir = _lake(tmp_path)
        s.conf.set(C.OBS_ENABLED, True)
        hs = Hyperspace(s)
        items = s.read.parquet(idir)
        hs.create_index(items, CoveringIndexConfig("ai1", ["k"], ["q"]))
        roots = trace.finished("action.CreateAction")
        assert len(roots) == 1
        root = roots[0]
        _assert_trace_integrity(root)
        assert root.attrs["status"] == "ok"
        assert root.attrs["index"] == "ai1"
        stages = root.stage_seconds()
        for want in (
            "validate", "begin_log", "resolve", "scan", "hash_shuffle",
            "sort", "write", "sidecar_capture", "log_entry", "log_commit",
            "publish_event",
        ):
            assert want in stages, (want, sorted(stages))
        # the build breakdown and the spans are one measurement
        from hyperspace_tpu.indexes import covering_build

        for name, sec in covering_build.last_build_breakdown.items():
            if name in ("tail_wall", "tail_shards"):
                continue  # derived values, not stage increments
            assert name in stages, name

    def test_failed_action_still_finishes_root(
        self, session_factory, tmp_path
    ):
        from hyperspace_tpu.exceptions import HyperspaceException

        s = session_factory(1)
        idir, _ = _lake(tmp_path)
        s.conf.set(C.OBS_ENABLED, True)
        hs = Hyperspace(s)
        items = s.read.parquet(idir)
        hs.create_index(items, CoveringIndexConfig("dup", ["k"], ["q"]))
        trace.reset()
        with pytest.raises(HyperspaceException):
            hs.create_index(items, CoveringIndexConfig("dup", ["k"], ["q"]))
        roots = trace.finished("action.CreateAction")
        assert len(roots) == 1
        assert roots[0].attrs["status"] == "failed"


# ---------------------------------------------------------------------------
# The action trace is the build's account: always recorded, one clock
# ---------------------------------------------------------------------------


def _build(session_factory, tmp_path, name="acc1", warm=True, buckets=None):
    """One covering build under the DEFAULT configuration (obs off);
    a warm-up build first pays the one-time imports."""
    s = session_factory(1)
    assert s.conf.obs_enabled is False
    if buckets is not None:
        s.conf.set(C.INDEX_NUM_BUCKETS, buckets)
    idir, _odir = _lake(tmp_path)
    hs = Hyperspace(s)
    items = s.read.parquet(idir)
    if warm:
        hs.create_index(items, CoveringIndexConfig("warm0", ["k"], ["q"]))
        trace.reset()
    hs.create_index(items, CoveringIndexConfig(name, ["k"], ["q"]))
    return s, hs, items


class TestActionAccount:
    def test_recorded_with_obs_off_on_one_clock(
        self, session_factory, tmp_path
    ):
        _s, hs, items = _build(session_factory, tmp_path)
        assert trace.enabled() is False
        roots = trace.finished("action.CreateAction")
        assert len(roots) == 1
        root = roots[0]
        _assert_trace_integrity(root)
        assert root.start_ms is not None and root.attrs["status"] == "ok"
        for sp in root.spans:
            assert sp.start_ns <= sp.end_ns, sp.name
            if sp is not root:
                assert sp.start_ms is None  # roots alone keep wall time
                assert root.start_ns <= sp.start_ns, sp.name
                assert sp.end_ns <= root.end_ns, sp.name
        # op() has no span of its own: what the root's direct children
        # leave uncovered IS the unnamed time, and it is small. The
        # limit is a share of the wall clock of a 20,000-row build, and
        # a thread that loses its core between two stages is unnamed
        # time too, while a stage that has no name is unnamed in every
        # build: under load the best of three builds is held to it
        # (tests/test_zorder_reference.py does the same)
        covered = [root.children_union_s() / root.duration_s]
        while covered[-1] < 0.95 and len(covered) < 3:
            hs.create_index(
                items,
                CoveringIndexConfig(f"acc1_{len(covered)}", ["k"], ["q"]),
            )
            again = trace.finished("action.CreateAction")[-1]
            covered.append(again.children_union_s() / again.duration_s)
        assert max(covered) >= 0.95, covered
        selfs = root.self_seconds()
        assert selfs["action.CreateAction"] == pytest.approx(
            root.duration_s - root.children_union_s(), abs=1e-9
        )
        # counts at the same boundaries
        assert root.attrs["rows"] == 20_000
        assert root.attrs["source_bytes"] > 0
        assert root.attrs["index_files"] > 0 and root.attrs["index_bytes"] > 0
        assert root.attrs["sidecar_bytes"] > 0
        sidecars = {
            sp.attrs["sidecar"]: sp.attrs
            for sp in root.spans
            if sp.name == "sidecar_capture"
        }
        assert set(sidecars) == {"aggstate", "zonemap"}
        for key in ("files", "tasks", "split_files", "python_s", "read_s",
                    "sweep_s", "turn_wait_s", "publish_s", "bytes", "workers",
                    "files_s",
                    "sweeps_native", "sweeps_twin", "early_rejects"):
            assert key in sidecars["aggstate"], key
        assert "partials_s" not in sidecars["aggstate"]
        # what a key column adds is said where it is added: one key
        # column, two order planes a sort, the files' gather and parquet
        # write apart (20,000 rows hash on the host: no split_words)
        by_name = {sp.name: sp.attrs for sp in root.spans}
        assert root.attrs["key_columns"] == 1
        assert by_name["key_reps"]["key_columns"] == 1
        # one int64 key is its own reps: nothing was copied on the way;
        # the order words say which of the pass and its twin made them
        assert by_name["key_reps"]["copied"] == 0
        assert by_name["partition"]["native"] in (0, 1)
        assert by_name["bucket_sorts"]["planes"] == 2
        assert 0 < by_name["bucket_sorts"]["max_rows"] <= 20_000
        write = by_name["write"]
        assert write["columns"] == 2
        assert 0 < write["take_s"] + write["encode_s"] <= write["sum_s"] + 1e-6

    @pytest.mark.parametrize("keys", [["k"], ["k", "q"]], ids=["1key", "2keys"])
    def test_the_word_split_keeps_its_spans_and_says_what_ran(
        self, session_factory, tmp_path, monkeypatch, keys
    ):
        """PR 39: the int64 -> uint32 word split is one native pass
        into the hash's block and into the sort's order planes. The
        spans are where they were (``key_reps`` and ``split_words``
        under ``hash_shuffle``, ``partition`` under ``sort``); what ran
        is attrs on them; the device is handed the bytes it was."""
        from hyperspace_tpu import native
        from hyperspace_tpu.ops import hash as hash_ops
        from hyperspace_tpu.ops import pad_len

        rows = 1 << 20
        monkeypatch.setattr(hash_ops, "_HOST_HASH_MAX_ROWS", rows - 1)
        s = session_factory(1)
        idir, _odir = _lake(tmp_path, n=rows)
        Hyperspace(s).create_index(
            s.read.parquet(idir),
            CoveringIndexConfig("ws1", keys, [c for c in ("k", "q") if c not in keys]),
        )
        root = trace.finished("action.CreateAction")[-1]
        _assert_trace_integrity(root)
        by_id = {sp.span_id: sp for sp in root.spans}
        found = {}
        for name in ("key_reps", "split_words", "partition"):
            (found[name],) = [sp for sp in root.spans if sp.name == name]
        parents = {n: by_id[sp.parent_id].name for n, sp in found.items()}
        assert parents == {
            "key_reps": "hash_shuffle",
            "split_words": "hash_shuffle",
            "partition": "sort",
        }
        ran = int(native.load() is not None)  # 2^20 rows: over every threshold
        assert found["split_words"].attrs["native"] == ran
        assert found["partition"].attrs["native"] == ran
        assert found["split_words"].attrs["words"] == 2 * len(keys)
        assert found["key_reps"].attrs["key_columns"] == len(keys)
        assert found["key_reps"].attrs["copied"] == (0 if len(keys) == 1 else 2)
        # 8 B a key column a padded row, as before the pass
        assert root.attrs["h2d_bytes"] == 8 * len(keys) * pad_len(rows)
        assert root.attrs["h2d_bytes"] == 8 * len(keys) * rows
        (h2d,) = [sp for sp in root.spans if sp.name == "h2d"]
        assert h2d.attrs["bytes"] == root.attrs["h2d_bytes"]

    def test_aggstate_span_counts_its_sweeps(
        self, session_factory, tmp_path, monkeypatch
    ):
        """Still ONE span for the capture, however many files a pool
        takes: what ran on it is attrs — the files, the workers, the
        passes by the implementation that made them."""
        import itertools

        from hyperspace_tpu import native
        from hyperspace_tpu.indexes import aggindex

        passes, real = itertools.count(), aggindex._sweep

        def counted(*a, **k):
            next(passes)
            return real(*a, **k)

        monkeypatch.setattr(aggindex, "_sweep", counted)
        monkeypatch.setattr(native, "_cores", lambda: 3)
        _build(session_factory, tmp_path, warm=False, buckets=8)
        root = trace.finished("action.CreateAction")[-1]
        spans = [
            sp for sp in root.spans
            if sp.name == "sidecar_capture" and sp.attrs["sidecar"] == "aggstate"
        ]
        assert len(spans) == 1
        assert {sp.name for sp in root.spans if sp.parent_id == spans[0].span_id} == set()
        at = spans[0].attrs
        assert at["files"] == root.attrs["index_files"] == 8
        assert at["workers"] == 3  # min(16, cores, tasks), past 4 tasks
        # as many files as workers or more: a task a file, no footer opened
        assert at["tasks"] == 8 and at["split_files"] == 0
        assert at["sweeps_native"] + at["sweeps_twin"] == next(passes) > 0
        if native.load() is not None:
            assert at["sweeps_twin"] == 0
        assert 0 <= at["early_rejects"] <= at["sweeps_native"]
        # what the tasks' seconds went to, summed over the files on their
        # threads: Python under the turn, the reads and the sweeps
        # outside it, the wait for it
        parts = ("python_s", "read_s", "sweep_s", "turn_wait_s")
        for key in (*parts, "files_s", "publish_s"):
            assert at[key] >= 0.0, key
        assert at["files_s"] + at["publish_s"] <= spans[0].duration_s
        # the turn is one task's at a time: its seconds cannot pass the
        # pool's wall, and the four cannot pass the pool's thread seconds
        assert at["python_s"] <= at["files_s"] + 1e-3
        assert sum(at[k] for k in parts) <= at["workers"] * at["files_s"] + 1e-3
        if at["sweeps_native"]:
            assert at["sweep_s"] > 0.0

    def test_breakdown_is_the_same_measurement(
        self, session_factory, tmp_path
    ):
        from hyperspace_tpu.indexes import covering_build

        _build(session_factory, tmp_path, warm=False)
        root = trace.finished("action.CreateAction")[-1]
        stages = root.stage_seconds()
        bd = dict(covering_build.last_build_breakdown)
        assert {"scan", "hash_shuffle", "sort", "write",
                "sidecar_capture"} <= set(bd)
        for name, sec in bd.items():
            assert stages[name] == pytest.approx(sec, abs=1e-9), name

    def test_serve_with_obs_off_still_leaves_nothing(
        self, session_factory, tmp_path
    ):
        s, _hs, items = _build(session_factory, tmp_path, warm=False)
        assert [r.name for r in trace.finished()] == ["action.CreateAction"]
        s.enable_hyperspace()
        trace.reset()
        fe = ServeFrontend(s)
        try:
            out = fe.serve(items.filter(items["k"] == 9).select("k", "q"))
        finally:
            fe.close()
        assert out.num_rows > 0
        assert trace.finished() == []
        assert trace.current() is None

    def test_no_span_per_bucket(self, session_factory, tmp_path):
        _build(session_factory, tmp_path, warm=False, buckets=200)
        root = trace.finished("action.CreateAction")[-1]
        assert len(root.spans) <= 64 and root.spans_dropped == 0
        by_name = {sp.name: sp for sp in root.spans}
        # repeated work is attrs on the enclosing span
        assert by_name["bucket_sorts"].attrs["buckets"] > 1
        for sp in (by_name["bucket_sorts"], by_name["write"]):
            assert sp.attrs["max_s"] <= sp.attrs["sum_s"] + 1e-9
        assert by_name["write"].attrs["files"] == root.attrs["index_files"]

    def test_one_worker_capture_never_waits_for_the_turn(
        self, session_factory, tmp_path
    ):
        """Up to four files the capture runs inline: the turn is always
        free, no wait is counted, and the task's three other states are
        the whole of ``files_s``."""
        _build(session_factory, tmp_path, warm=False, buckets=4)
        root = trace.finished("action.CreateAction")[-1]
        (at,) = [
            sp.attrs for sp in root.spans
            if sp.name == "sidecar_capture" and sp.attrs["sidecar"] == "aggstate"
        ]
        assert at["files"] == 4 and at["workers"] == 1
        # fewer files than cores, but none with rows for two tasks
        assert at["tasks"] == 4 and at["split_files"] == 0
        assert at["turn_wait_s"] == 0
        account = at["python_s"] + at["read_s"] + at["sweep_s"]
        assert account <= at["files_s"] + 1e-4
        assert account >= 0.9 * at["files_s"]

    def test_one_file_with_rows_for_many_tasks_is_swept_by_a_pool(
        self, session_factory, tmp_path, monkeypatch
    ):
        """One bucket, so one file: its row groups go to the pool as
        ranges, a task per ``_TASK_ROWS`` rows — and the capture is still
        the one span with its parts as attrs, no span a task."""
        from hyperspace_tpu import native
        from hyperspace_tpu.indexes import aggindex
        from hyperspace_tpu.io import parquet as pio

        monkeypatch.setattr(native, "_cores", lambda: 4)
        monkeypatch.setattr(pio, "INDEX_ROW_GROUP_SIZE", 1000)
        monkeypatch.setattr(aggindex, "_TASK_ROWS", 2000)
        _build(session_factory, tmp_path, warm=False, buckets=1)
        root = trace.finished("action.CreateAction")[-1]
        spans = [
            sp for sp in root.spans
            if sp.name == "sidecar_capture" and sp.attrs["sidecar"] == "aggstate"
        ]
        assert len(spans) == 1
        assert not [sp for sp in root.spans if sp.parent_id == spans[0].span_id]
        at = spans[0].attrs
        assert at["files"] == root.attrs["index_files"] == 1
        # 20,000 rows in 20 row groups: ten tasks' worth of rows
        assert at["split_files"] == 1 and at["tasks"] == 10
        assert at["workers"] == 4
        assert at["sweeps_native"] + at["sweeps_twin"] >= 20
        parts = ("python_s", "read_s", "sweep_s", "turn_wait_s")
        assert at["python_s"] <= at["files_s"] + 1e-3
        assert sum(at[k] for k in parts) <= at["workers"] * at["files_s"] + 1e-3

    def test_scan_span_says_what_its_seconds_went_to(
        self, session_factory, tmp_path
    ):
        _build(session_factory, tmp_path)
        root = trace.finished("action.CreateAction")[-1]
        (scan,) = [sp for sp in root.spans if sp.name == "scan"]
        at = scan.attrs
        assert at["files"] == 4 and at["rows"] == root.attrs["rows"] == 20_000
        phases = [at[k] for k in ("read_s", "decode_s", "lineage_s", "concat_s")]
        assert all(p >= 0.0 for p in phases)
        # one file after another: the phases add up to the span but for
        # the loop around them
        assert sum(phases) <= scan.duration_s + 1e-6
        assert sum(phases) >= 0.9 * scan.duration_s
        assert 0.0 < at["max_read_s"] <= at["read_s"] + 1e-9
        assert not [sp for sp in root.spans if sp.parent_id == scan.span_id]

    def test_every_stage_holds_its_cpu_seconds(self, session_factory, tmp_path):
        from hyperspace_tpu.indexes import covering_build

        _build(session_factory, tmp_path, warm=False, buckets=200)
        root = trace.finished("action.CreateAction")[-1]
        staged = set(covering_build.last_build_breakdown)
        assert {"resolve", "scan", "hash_shuffle", "dict_probe", "sort",
                "write", "sidecar_capture"} <= staged
        for sp in root.spans:
            if sp.name in staged:
                assert sp.attrs["cpu_s"] >= 0.0, sp.name
                assert "thread_cpu_s" not in sp.attrs, sp.name
            elif sp is not root:    # the hook alone reads the CPU clock
                assert "cpu_s" not in sp.attrs, sp.name
        # the action's own, over all of it: no stage can have had more
        assert root.attrs["cpu_s"] > 0.0
        assert max(
            sp.attrs["cpu_s"] for sp in root.spans if sp.name in staged
        ) <= root.attrs["cpu_s"] + 1e-6
        by_name = {sp.name: sp for sp in root.spans}
        for sp in (by_name["bucket_sorts"], by_name["write"]):
            assert 0.0 <= sp.attrs["cpu_sum_s"]
            assert sp.attrs["cpu_sum_s"] <= sp.attrs["sum_s"] + 0.05, sp.name

    def test_shard_tails_hold_their_own_threads_cpu(
        self, session_factory, tmp_path
    ):
        """The mesh's shard tails run side by side, so a span that names
        its shard holds its own thread's CPU seconds under a name of
        their own, never the process's."""
        s = session_factory(4)
        idir, _odir = _lake(tmp_path)
        items = s.read.parquet(idir)
        Hyperspace(s).create_index(items, CoveringIndexConfig("sh1", ["k"], ["q"]))
        root = trace.finished("action.CreateAction")[-1]
        tails = [sp for sp in root.spans if "shard" in sp.attrs]
        assert sorted((sp.name, sp.attrs["shard"]) for sp in tails) == [
            (name, shard) for name in ("sort", "write") for shard in range(4)
        ]
        for sp in tails:
            assert "cpu_s" not in sp.attrs
            assert 0.0 <= sp.attrs["thread_cpu_s"] <= sp.duration_s + 0.05
            assert 0.0 <= sp.attrs["cpu_sum_s"]
        assert len(root.spans) <= 64 and root.spans_dropped == 0

    def test_zorder_stages_name_their_passes(self, session_factory, tmp_path):
        from hyperspace_tpu.indexes.zorder import ZOrderCoveringIndexConfig

        s = session_factory(1)
        idir, _odir = _lake(tmp_path)
        items = s.read.parquet(idir)
        Hyperspace(s).create_index(
            items, ZOrderCoveringIndexConfig("zo1", ["k", "q"], [])
        )
        root = trace.finished("action.CreateAction")[-1]
        by_name = {sp.name: sp for sp in root.spans}
        encode, words = by_name["zorder_encode"], by_name["words"]
        # sums over the columns, not a span a column
        assert not [sp for sp in root.spans if sp.parent_id == encode.span_id]
        assert encode.attrs["order_s"] > 0.0 and encode.attrs["minmax_s"] > 0.0
        assert (
            encode.attrs["order_s"] + encode.attrs["minmax_s"]
            <= encode.duration_s + 1e-6
        )
        parts = [words.attrs[k] for k in ("scale_s", "stack_s", "pad_s")]
        assert parts[0] > 0.0 and all(p >= 0.0 for p in parts)
        assert sum(parts) <= words.duration_s + 1e-6
        assert words.parent_id == by_name["zorder_interleave"].span_id
        assert len(root.spans) <= 64 and root.spans_dropped == 0

    def test_a_compile_names_the_span_it_ran_under(self):
        """A jitted function first called inside a span leaves the
        compile on that span and on its root; one called with no span
        live leaves nothing anywhere."""
        import jax
        import jax.numpy as jnp

        root = trace.root("action.T", always=True)
        with trace.activate(root):
            with trace.span("scan") as quiet:
                pass
            with trace.span("sort") as sp:
                jax.jit(lambda x: x * 3 + 1)(jnp.arange(37)).block_until_ready()
        root.finish()
        assert sp.attrs["compiles"] >= 1 and sp.attrs["compile_s"] > 0.0
        assert root.attrs["compiles"] == sp.attrs["compiles"]
        assert root.attrs["compile_s"] == pytest.approx(sp.attrs["compile_s"])
        assert sp.attrs.get("compile_cache_hits", 0) <= sp.attrs["compiles"]
        assert not set(quiet.attrs) & {"compiles", "compile_s"}
        # a compile under the root itself counts once, not twice
        lone = trace.root("action.T", always=True)
        with trace.activate(lone):
            jax.jit(lambda x: x * 5 + 2)(jnp.arange(39)).block_until_ready()
        lone.finish()
        assert lone.attrs["compiles"] >= 1
        # no span live: nothing recorded, on either finished root
        before = [dict(r.attrs) for r in (root, lone)]
        assert trace.current() is None
        jax.jit(lambda x: x * 7 + 3)(jnp.arange(41)).block_until_ready()
        assert [dict(r.attrs) for r in (root, lone)] == before
        quiet_root = trace.root("action.T", always=True)
        quiet_root.finish()
        assert not set(quiet_root.attrs) & {"compiles", "compile_s"}

    def test_a_compile_on_a_carried_thread_finds_its_span(self):
        import jax
        import jax.numpy as jnp
        from concurrent.futures import ThreadPoolExecutor

        root = trace.root("action.T", always=True)

        def work(n):
            jax.jit(lambda x: x - 11)(jnp.arange(n)).block_until_ready()

        with trace.activate(root):
            with trace.span("sort") as sp:
                with ThreadPoolExecutor(max_workers=2) as pool:
                    list(pool.map(trace.carry(work), [43, 47]))
        root.finish()
        assert sp.attrs["compiles"] == root.attrs["compiles"] >= 2

    def test_write_span_names_its_writers(
        self, session_factory, tmp_path, monkeypatch
    ):
        """Still ONE ``write`` span, whatever pool the files went
        through: the pool's size is an attr beside the files' seconds
        summed over its threads."""
        from hyperspace_tpu import native

        monkeypatch.setattr(native, "core_budget", lambda: 5)
        _build(session_factory, tmp_path, warm=False, buckets=200)
        root = trace.finished("action.CreateAction")[-1]
        (write,) = [sp for sp in root.spans if sp.name == "write"]
        assert write.parent_id == root.span_id
        assert write.attrs["writers"] == 5 <= write.attrs["buckets"]
        assert write.attrs["buckets"] == write.attrs["files"]
        assert 0.0 < write.attrs["max_s"] <= write.attrs["sum_s"] + 1e-9
        assert not [sp for sp in root.spans if sp.parent_id == write.span_id]

    def test_self_seconds_on_a_hand_built_tree(self):
        """Overlapping children count once; a summed span has no
        interval and is left out of every union and self time."""
        S = 1_000_000_000
        root = trace.Span("action.T", start_ns=0)

        def mk(name, parent, lo, hi):
            sp = trace.Span(name, parent=parent, start_ns=int(lo * S))
            sp.end_ns = int(hi * S)
            root.spans.append(sp)
            return sp

        a = mk("a", root, 1, 5)       # one thread
        mk("b", root, 3, 8)           # another, overlapping a
        mk("a1", a, 1, 2)
        mk("a2", a, 1.5, 3)           # overlaps a1
        mk("a2", a, 4.5, 6)           # sticks out of its parent: clipped
        with trace.activate(root):
            trace.stage("pack", seconds=2.5)
        root.end_ns = 10 * S
        root.spans.append(root)
        assert root.children_union_s() == pytest.approx(7.0)
        selfs = root.self_seconds()
        assert selfs["action.T"] == pytest.approx(3.0)
        assert selfs["a"] == pytest.approx(4.0 - 2.0 - 0.5)
        assert selfs["b"] == pytest.approx(5.0)
        assert selfs["a1"] == pytest.approx(1.0)
        assert selfs["a2"] == pytest.approx(1.5 + 1.5)
        assert "pack" not in selfs
        assert root.stage_seconds()["pack"] == pytest.approx(2.5)
        packed = [sp for sp in root.spans if sp.name == "pack"]
        assert packed[0].summed and packed[0].to_dict()["summed"] is True

    def test_children_on_two_threads_count_once(self):
        import time
        from concurrent.futures import ThreadPoolExecutor

        root = trace.root("action.T", always=True)

        def work(name):
            with trace.span(name):
                time.sleep(0.08)

        with trace.activate(root):
            with ThreadPoolExecutor(max_workers=2) as pool:
                list(pool.map(trace.carry(work), ["a", "b"]))
        root.finish()
        _assert_trace_integrity(root)
        stages = root.stage_seconds()
        assert stages["a"] + stages["b"] >= 0.16
        # side by side, not end to end
        assert root.children_union_s() < stages["a"] + stages["b"] - 0.02
        assert root.children_union_s() <= root.duration_s

    def test_carry_and_span_follow_the_parent_not_the_switch(self):
        trace.set_enabled(False)
        fn = lambda: trace.current()  # noqa: E731
        assert trace.carry(fn) is fn           # no parent: identity
        root = trace.root("action.T", always=True)
        with trace.activate(root):
            assert trace.carry(fn) is not fn   # a live parent: wrapped
            with trace.span("scan") as sp:
                assert sp is not trace.NOOP
            trace.accumulate("rows", 3)
            trace.accumulate("rows", 4)
        root.finish()
        assert root.attrs["rows"] == 7
        assert [s.name for s in root.spans] == ["scan", "action.T"]

    def test_profiler_trace_holds_hs_annotations(
        self, session_factory, tmp_path
    ):
        """Every ``with trace.span`` also enters an ``hs.<name>``
        TraceAnnotation: on a profiled run the program's spans lie in
        the profiler's own trace, on the device trace's clock."""
        import glob

        import jax

        s = session_factory(1)
        idir, _odir = _lake(tmp_path)
        hs = Hyperspace(s)
        items = s.read.parquet(idir)
        out_dir = str(tmp_path / "prof")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(out_dir, profiler_options=opts)
        try:
            hs.create_index(items, CoveringIndexConfig("pf1", ["k"], ["q"]))
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(
            os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
        assert files
        data = jax.profiler.ProfileData.from_file(sorted(files)[-1])
        names = {
            e.name
            for plane in data.planes
            for line in plane.lines
            for e in line.events
            if e.name.startswith("hs.")
        }
        for want in ("hs.scan", "hs.sort", "hs.write", "hs.sidecar_capture",
                     "hs.log_commit"):
            assert want in names, (want, sorted(names))


# ---------------------------------------------------------------------------
# Querylog: one row per execution
# ---------------------------------------------------------------------------


class TestQuerylogIntegration:
    def test_row_per_execution_and_schema(self, obs_env):
        s, items = obs_env["s"], obs_env["items"]
        fe = ServeFrontend(s)
        try:
            for k in (11, 12, 13, 11):
                fe.serve(items.filter(items["k"] == k).select("k", "q"))
            completed = fe.stats()["completed"]
        finally:
            fe.close()
        records = querylog.read_records(querylog.obs_root(s.conf))
        assert len(records) == completed
        fps = set()
        for r in records:
            assert querylog.validate_record(r) is None, r
            assert r["trace_id"]
            assert r["stages"].get("execute", 0) >= 0
            assert r["indexes"] == ["oi1"]
            fps.add(r["fingerprint"])
        # k=11 served twice -> same fingerprint; 3 distinct literals
        assert len(fps) == 3
        shapes = {r["predicate"] for r in records}
        assert len(shapes) == 1, "literal scrubbing failed"
        # the rows replay against the trace ring
        ring = {t.trace_id for t in trace.finished("serve.query")}
        assert {r["trace_id"] for r in records} <= ring

    def test_querylog_disabled_writes_nothing(self, obs_env):
        s, items = obs_env["s"], obs_env["items"]
        s.conf.set(C.OBS_QUERYLOG_ENABLED, False)
        fe = ServeFrontend(s)
        try:
            fe.serve(items.filter(items["k"] == 3).select("k"))
        finally:
            fe.close()
        assert querylog.read_records(querylog.obs_root(s.conf)) == []


# ---------------------------------------------------------------------------
# Fleet: trace linkage through the claim/spool plane
# ---------------------------------------------------------------------------


class TestFleetTraceLinkage:
    def test_spool_hit_links_winner_trace(self, session_factory, tmp_path):
        """Two in-process FleetFrontends (separate sessions, shared
        lake — the same stand-in tests/test_fleet.py uses): the loser
        serving from the winner's spooled result records a spool_hit
        event carrying the WINNER's trace id."""
        from hyperspace_tpu.session import HyperspaceSession

        src = tmp_path / "src"
        src.mkdir()
        rng = np.random.default_rng(5)
        pq.write_table(
            pa.table(
                {
                    "k": pa.array(
                        rng.integers(0, 50, 3000), pa.int64()
                    ),
                    "v": pa.array(
                        rng.integers(0, 100, 3000), pa.int64()
                    ),
                }
            ),
            str(src / "p0.parquet"),
        )
        index_root = str(tmp_path / "indexes")

        def make_session():
            s = HyperspaceSession()
            s.conf.set(C.INDEX_SYSTEM_PATH, index_root)
            s.conf.set(C.INDEX_NUM_BUCKETS, 4)
            s.conf.set(C.FLEET_ENABLED, True)
            # this test witnesses the DURABLE claim/spool trace linkage;
            # the fast plane would turn the second serve into a routed
            # owner handoff and elect nobody
            s.conf.set(C.FLEET_FAST_ENABLED, False)
            s.conf.set(C.OBS_ENABLED, True)
            s.enable_hyperspace()
            return s

        s1 = make_session()
        hs1 = Hyperspace(s1)
        df = s1.read.parquet(str(src))
        hs1.create_index(df, CoveringIndexConfig("fl1", ["k"], ["v"]))
        trace.reset()
        s2 = make_session()
        fe1, fe2 = s1.serve_frontend, s2.serve_frontend
        try:
            q1 = s1.read.parquet(str(src))
            q1 = q1.filter(q1["k"] == 9)
            q2 = s2.read.parquet(str(src))
            q2 = q2.filter(q2["k"] == 9)
            t1 = fe1.serve(q1)
            t2 = fe2.serve(q2)
            assert t1.sort_by("v").equals(t2.sort_by("v"))
            st1, st2 = fe1.stats()["fleet"], fe2.stats()["fleet"]
            assert st1["claims_won"] + st2["claims_won"] == 1
            assert st1["spool_hits"] + st2["spool_hits"] == 1
        finally:
            fe1.close()
            fe2.close()
        roots = trace.finished("serve.query")
        assert len(roots) == 2
        winner = next(
            r for r in roots
            if any(e["name"] == "singleflight_won" for e in r.events)
        )
        loser = next(r for r in roots if r is not winner)
        hits = [e for e in loser.events if e["name"] == "spool_hit"]
        assert hits, loser.events
        assert hits[0]["winner_trace_id"] == winner.trace_id
        # both queries hashed to the same fleet digest
        won = [e for e in winner.events if e["name"] == "singleflight_won"]
        assert won[0]["digest"] == hits[0]["digest"]

    @pytest.mark.slow
    def test_two_real_processes_link_traces(self, tmp_path):
        """The real thing: two OS processes over one lake with obs on.
        Cross-process single-flight must link a loser's spool hit to a
        root trace id owned by the OTHER process, and the querylog must
        union per-process files to one row per execution."""
        from hyperspace_tpu.testing import fleet_harness

        out = fleet_harness.run_fleet(
            str(tmp_path / "fleet"),
            n_procs=2,
            iters=3,
            rows=12_000,
            conf={
                C.OBS_ENABLED: True,
                C.OBS_TRACE_RETAIN: 4096,
                # durable-plane linkage under test: force the claim/
                # spool election path, not routed owner handoffs
                C.FLEET_FAST_ENABLED: False,
            },
        )
        assert out["wrong_answers"] == 0
        assert out["cross_process_dedup"] > 0
        assert out["leaked_pin_files"] == 0
        obs_reports = out["worker_obs"]
        assert len(obs_reports) == 2
        roots_by_worker = [set(r["root_trace_ids"]) for r in obs_reports]
        assert roots_by_worker[0].isdisjoint(roots_by_worker[1])
        all_roots = roots_by_worker[0] | roots_by_worker[1]
        links = [
            (wi, link)
            for wi, r in enumerate(obs_reports)
            for link in r["spool_hit_links"]
            if link
        ]
        assert links, "no spool hit carried a winner trace id"
        for _wi, link in links:
            assert link in all_roots
        # later iterations legitimately hit a worker's OWN earlier
        # spooled result; the linkage contract needs at least one
        # CROSS-process link (loser -> the other process's root)
        assert any(
            link not in roots_by_worker[wi] for wi, link in links
        ), "no cross-process trace link observed"
        # querylog: per-process files union to one row per execution
        index_root = os.path.join(str(tmp_path / "fleet"), "indexes")
        records = querylog.read_records(
            os.path.join(index_root, C.HYPERSPACE_OBS_DIR)
        )
        assert records, "no querylog rows from the fleet"
        writers = {r["trace_id"] for r in records}
        # every recorded trace belongs to some worker's root set
        # (warmup serves are roots too; subset, not equality)
        assert {r["trace_id"] for r in records if r["trace_id"] in all_roots}
        for r in records:
            assert querylog.validate_record(r) is None, r
        assert len(writers) == len(set(writers))

    def test_bus_event_carries_action_trace_id(
        self, session_factory, tmp_path
    ):
        from hyperspace_tpu.serve import bus as fleet_bus
        from hyperspace_tpu.session import HyperspaceSession

        src = tmp_path / "src"
        src.mkdir()
        pq.write_table(
            pa.table({"k": pa.array(range(100), pa.int64())}),
            str(src / "p0.parquet"),
        )
        s = HyperspaceSession()
        s.conf.set(C.INDEX_SYSTEM_PATH, str(tmp_path / "indexes"))
        s.conf.set(C.INDEX_NUM_BUCKETS, 2)
        s.conf.set(C.FLEET_ENABLED, True)
        s.conf.set(C.OBS_ENABLED, True)
        s.enable_hyperspace()
        hs = Hyperspace(s)
        hs.create_index(
            s.read.parquet(str(src)), CoveringIndexConfig("bi1", ["k"], [])
        )
        roots = trace.finished("action.CreateAction")
        assert len(roots) == 1
        bus = fleet_bus.FleetBus(fleet_bus.bus_dir(s.conf), owner="probe")
        bus.prime = lambda: None  # see every event, incl. history
        bus._primed = True
        events = bus.poll_once()
        changed = [e for e in events if e.get("type") == "index_changed"]
        assert changed
        assert changed[-1]["trace_id"] == roots[0].trace_id
