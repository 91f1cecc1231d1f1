"""Replicated serve fleet (docs/fleet-serve.md): durable pins, version
fanout, cross-process single-flight, per-tenant SLO classes.

The durable-pin × GC/vacuum interaction lives in
``tests/test_crash_recovery.py`` (``TestCrossProcessPins``); this file
covers the serve-tier planes — the bus, the claim/spool single-flight
(driven through two in-process ``FleetFrontend`` instances, which share
NO in-process state by construction, so the file protocol is what
coordinates them), the SLO-class scheduler, and (slow) the real
multi-process harness with its kill -9 rung.
"""

import json
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu import functions as F
from hyperspace_tpu.exceptions import ServeOverloadedError
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.indexes.covering import CoveringIndexConfig
from hyperspace_tpu.serve.bus import FleetBus
from hyperspace_tpu.serve.fleet import FleetFrontend, spool_dir
from hyperspace_tpu.serve.frontend import ServeFrontend


def sorted_table(t):
    return t.sort_by([(c, "ascending") for c in t.column_names])


@pytest.fixture
def fleet_env(tmp_path):
    """One lake + two fleet sessions over it (the in-process stand-in
    for two frontend processes: separate sessions, separate caches,
    coordination only through the lake's files)."""
    from hyperspace_tpu.session import HyperspaceSession

    src = tmp_path / "src"
    src.mkdir()
    rng = np.random.default_rng(7)
    n = 4000
    pq.write_table(
        pa.table(
            {
                "k": pa.array(rng.integers(0, 60, n), pa.int64()),
                "v": pa.array(rng.integers(-500, 500, n), pa.int64()),
            }
        ),
        str(src / "part-0.parquet"),
    )
    index_root = str(tmp_path / "indexes")

    def make_session(**conf):
        s = HyperspaceSession()
        s.conf.set(C.INDEX_SYSTEM_PATH, index_root)
        s.conf.set(C.INDEX_NUM_BUCKETS, 4)
        s.conf.set(C.FLEET_ENABLED, True)
        s.conf.set(C.SERVE_CACHE_ENABLED, True)
        s.conf.set(C.FLEET_BUS_POLL_MS, 20)
        for k, v in conf.items():
            s.conf.set(k, v)
        s.enable_hyperspace()
        return s

    s1 = make_session()
    hs1 = Hyperspace(s1)
    df = s1.read.parquet(str(src))
    hs1.create_index(df, CoveringIndexConfig("fidx", ["k"], ["v"]))
    return {
        "src": str(src),
        "index_root": index_root,
        "make_session": make_session,
        "s1": s1,
        "hs1": hs1,
        "rng": rng,
    }


# ---------------------------------------------------------------------------
# The fanout bus
# ---------------------------------------------------------------------------


class TestFleetBus:
    def test_publish_poll_roundtrip(self, tmp_path):
        d = str(tmp_path / "bus")
        a = FleetBus(d, retain_ms=60_000)
        b = FleetBus(d, retain_ms=60_000)
        b.prime()
        a.publish({"type": "index_changed", "root": "/x"})
        a.publish({"type": "index_changed", "root": "/y"})
        events = b.poll_once()
        assert [e["root"] for e in events] == ["/x", "/y"]
        assert b.poll_once() == []  # seen once
        assert b.received == 2

    def test_own_events_skipped(self, tmp_path):
        d = str(tmp_path / "bus")
        a = FleetBus(d)
        a.prime()
        a.publish({"type": "index_changed", "root": "/x"})
        assert a.poll_once() == []

    def test_prime_skips_history(self, tmp_path):
        d = str(tmp_path / "bus")
        a = FleetBus(d)
        a.publish({"type": "index_changed", "root": "/old"})
        b = FleetBus(d)
        b.prime()
        assert b.poll_once() == []
        a.publish({"type": "index_changed", "root": "/new"})
        assert [e["root"] for e in b.poll_once()] == ["/new"]

    def test_retention_prune(self, tmp_path):
        d = str(tmp_path / "bus")
        a = FleetBus(d, retain_ms=80)
        a.publish({"type": "index_changed", "root": "/x"})
        time.sleep(0.15)
        a.publish({"type": "index_changed", "root": "/y"})
        assert a.pruned >= 1
        names = [f for f in os.listdir(d) if f.endswith(".json")]
        assert len(names) == 1

    def test_torn_event_skipped(self, tmp_path):
        d = str(tmp_path / "bus")
        os.makedirs(d)
        b = FleetBus(d)
        b.prime()
        with open(os.path.join(d, "9999999999999.dead.000001.json"), "w") as f:
            f.write('{"type": "ind')
        assert b.poll_once() == []

    def test_subscriber_thread_delivers(self, tmp_path):
        d = str(tmp_path / "bus")
        got = []
        done = threading.Event()
        b = FleetBus(d, poll_ms=10)
        b.start(lambda e: (got.append(e), done.set()))
        try:
            FleetBus(d).publish({"type": "index_changed", "root": "/z"})
            assert done.wait(5.0)
            assert got[0]["root"] == "/z"
        finally:
            b.stop()


# ---------------------------------------------------------------------------
# ServeCache fanout eviction
# ---------------------------------------------------------------------------


class TestEvictPathsUnder:
    def test_evicts_only_matching_index(self):
        from hyperspace_tpu.execution.serve_cache import ServeCache

        c = ServeCache(1 << 20)
        fp_a = (("/lake/idxA/v__=1/part-0.parquet", 10, 1),)
        fp_b = (("/lake/idxB/v__=1/part-0.parquet", 10, 1),)
        c.put(("scan", fp_a), "a", 10)
        c.put(("zonemap", fp_a), "za", 10)
        c.put(("joinside", (fp_a, fp_b), ("k",), ("k",)), "j", 10)
        c.put(("scan", fp_b), "b", 10)
        assert c.evict_paths_under("/lake/idxA") == 3
        assert c.get(("scan", fp_b)) == "b"
        assert c.get(("scan", fp_a)) is None
        assert c.resident_bytes == 10


# ---------------------------------------------------------------------------
# Aggstate push payloads (ROADMAP 2c)
# ---------------------------------------------------------------------------


class TestAggstatePush:
    def test_payload_roundtrip(self, fleet_env):
        from hyperspace_tpu.execution.serve_cache import ServeCache
        from hyperspace_tpu.indexes import aggindex

        s1 = fleet_env["s1"]
        entries = s1.index_manager.get_indexes([C.States.ACTIVE])
        files = entries[0].content.files
        payload = aggindex.fanout_payload(files)
        assert payload is not None
        # JSON round trip, as the bus would carry it
        payload = json.loads(json.dumps(payload))
        cache = ServeCache(1 << 24)
        aggindex.invalidate_local_cache()
        assert aggindex.install_fanout_payload(payload, cache)
        assert cache.bytes_by_kind().get("aggstate", 0) > 0

    def test_stale_payload_dropped(self, fleet_env):
        from hyperspace_tpu.indexes import aggindex

        s1 = fleet_env["s1"]
        entries = s1.index_manager.get_indexes([C.States.ACTIVE])
        payload = aggindex.fanout_payload(entries[0].content.files)
        payload["fp"][0][1] += 1  # stats moved on: stale push
        assert not aggindex.install_fanout_payload(payload, None)

    def test_refresh_fans_out_to_peer(self, fleet_env):
        src, rng = fleet_env["src"], fleet_env["rng"]
        s2 = fleet_env["make_session"]()
        fe2 = s2.serve_frontend
        try:
            assert isinstance(fe2, FleetFrontend)
            pq.write_table(
                pa.table(
                    {
                        "k": pa.array(rng.integers(0, 60, 500), pa.int64()),
                        "v": pa.array(
                            rng.integers(-500, 500, 500), pa.int64()
                        ),
                    }
                ),
                os.path.join(src, "part-1.parquet"),
            )
            fleet_env["hs1"].refresh_index("fidx", "incremental")
            # wait on bus_installed, not bus_events: the callback counts
            # the event BEFORE it installs the payload
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                st = fe2.stats()["fleet"]
                if st["bus_installed"] >= 1:
                    break
                time.sleep(0.02)
            st = fe2.stats()["fleet"]
            assert st["bus_events"] >= 1, st
            assert st["bus_installed"] >= 1, st
            # the peer serves the NEW snapshot correctly
            df = s2.read.parquet(src)
            q = df.filter(df["k"] >= 10).agg(F.count().alias("n"))
            got = fe2.serve(q)
            s2.disable_hyperspace()
            want = q.collect()
            s2.enable_hyperspace()
            assert got.equals(want)
        finally:
            fe2.close()


# ---------------------------------------------------------------------------
# Cross-process single-flight (claim + spool)
# ---------------------------------------------------------------------------


class TestSingleFlight:
    """The durable claim/spool plane, tested in isolation: fast routing
    OFF, so every dedup goes through the claim election (the plane the
    fast path degrades to — it must keep working on its own)."""

    def test_two_frontends_one_execution(self, fleet_env):
        s1 = fleet_env["make_session"](**{C.FLEET_FAST_ENABLED: False})
        s2 = fleet_env["make_session"](**{C.FLEET_FAST_ENABLED: False})
        fe1, fe2 = s1.serve_frontend, s2.serve_frontend
        try:
            src = fleet_env["src"]
            q1 = s1.read.parquet(src)
            q1 = q1.filter(q1["k"] == 11)
            q2 = s2.read.parquet(src)
            q2 = q2.filter(q2["k"] == 11)
            t1 = fe1.serve(q1)
            t2 = fe2.serve(q2)
            assert sorted_table(t1).equals(sorted_table(t2))
            st1, st2 = fe1.stats()["fleet"], fe2.stats()["fleet"]
            assert st1["claims_won"] + st2["claims_won"] == 1
            assert st1["spool_hits"] + st2["spool_hits"] == 1
            # the election telemetry agrees with the outcome
            assert st1["election_wins"] + st2["election_wins"] == 1
            assert (
                st1["election_attempts"] + st2["election_attempts"] >= 1
            )
            # the answer is correct vs the unindexed truth
            s1.disable_hyperspace()
            want = q1.collect()
            s1.enable_hyperspace()
            assert sorted_table(t1).equals(sorted_table(want))
        finally:
            fe1.close()
            fe2.close()

    def test_expired_claim_taken_over(self, fleet_env):
        s2 = fleet_env["make_session"]()
        s2.conf.set(C.FLEET_SINGLEFLIGHT_CLAIM_MS, 30)
        fe2 = s2.serve_frontend
        try:
            # a dead winner's claim (kill -9 mid-serve) sits in the
            # spool; its lease expires and fe2 takes the claim over
            claim = os.path.join(spool_dir(s2.conf), "deadbeef.claim")
            os.makedirs(os.path.dirname(claim), exist_ok=True)
            with open(claim, "w") as f:
                json.dump({"owner": "dead", "expiresAtMs": 1}, f)
            assert fe2._try_claim(claim) == "won"
            # a LIVE claim is respected
            claim2 = os.path.join(spool_dir(s2.conf), "cafebabe.claim")
            with open(claim2, "w") as f:
                json.dump(
                    {
                        "owner": "live",
                        "expiresAtMs": int(time.time() * 1000) + 600_000,
                    },
                    f,
                )
            assert fe2._try_claim(claim2) == "held"
        finally:
            fe2.close()

    def test_wait_timeout_executes_locally(self, fleet_env):
        s2 = fleet_env["make_session"](**{C.FLEET_FAST_ENABLED: False})
        s2.conf.set(C.FLEET_SINGLEFLIGHT_WAIT_MS, 50)
        s2.conf.set(C.FLEET_SINGLEFLIGHT_CLAIM_MS, 600_000)
        fe2 = s2.serve_frontend
        try:
            src = fleet_env["src"]
            q = s2.read.parquet(src)
            q = q.filter(q["k"] == 31)
            pin = fe2._pin()
            digest = fe2._plan_digest(q.logical_plan, pin)
            claim = os.path.join(spool_dir(s2.conf), digest + ".claim")
            os.makedirs(os.path.dirname(claim), exist_ok=True)
            with open(claim, "w") as f:
                json.dump(
                    {
                        "owner": "live-elsewhere",
                        "expiresAtMs": int(time.time() * 1000) + 600_000,
                    },
                    f,
                )
            t = fe2.serve(q)  # waits 50ms, then serves locally
            s2.disable_hyperspace()
            want = q.collect()
            s2.enable_hyperspace()
            assert sorted_table(t).equals(sorted_table(want))
            st = fe2.stats()["fleet"]
            assert st["singleflight_local"] >= 1, st
            assert st["claim_waits"] >= 1, st
            # the held claim shows up as election losses, and the
            # backoff means a 50ms wait attempts only a few elections
            # (not 50ms / 10ms-poll fixed-cadence hammering)
            assert st["election_losses"] >= 1, st
            assert st["election_wins"] == 0, st
        finally:
            fe2.close()

    def test_spool_prune_respects_budget(self, fleet_env):
        s2 = fleet_env["make_session"](**{C.FLEET_FAST_ENABLED: False})
        s2.conf.set(C.FLEET_SPOOL_MAX_BYTES, 1)
        fe2 = s2.serve_frontend
        try:
            src = fleet_env["src"]
            q = s2.read.parquet(src)
            q = q.filter(q["k"] == 42)
            fe2.serve(q)
            sd = spool_dir(s2.conf)
            arrows = [f for f in os.listdir(sd) if f.endswith(".arrow")]
            assert arrows == []  # over-budget results pruned immediately
        finally:
            fe2.close()


# ---------------------------------------------------------------------------
# The fast data plane: push bus + owner routing (hyperspace.fleet.fast.*)
# ---------------------------------------------------------------------------


def _query_owned_by(fe, session, src, target_owner):
    """A probe DataFrame whose (plan, snapshot) digest rendezvous-routes
    to ``target_owner`` (searched over a predicate family disjoint from
    the other tests' plans)."""
    from hyperspace_tpu.serve.router import rendezvous_owner

    members = fe._router.members(refresh=True)
    pin = fe._pin()
    for kk in range(300):
        df = session.read.parquet(src)
        df = df.filter((df["k"] == kk % 60) & (df["v"] > -(10**6) - kk))
        digest = fe._plan_digest(df.logical_plan, pin)
        if rendezvous_owner(members.keys(), digest) == target_owner:
            return df, digest
    raise AssertionError(f"no probe routed to {target_owner}")


class TestFastPath:
    def test_owner_local_serve_skips_claim_election(self, fleet_env):
        s = fleet_env["make_session"]()
        fe = s.serve_frontend
        try:
            assert fe._router is not None  # the fast plane came up
            src = fleet_env["src"]
            q = s.read.parquet(src)
            q = q.filter(q["k"] == 13)
            t1 = fe.serve(q)
            # sole member: every digest routes to self — served through
            # the in-memory single-flight, no claim file, no election
            st = fe.stats()["fleet"]
            assert st["election_attempts"] == 0, st
            assert st["claims_won"] == 0, st
            sd = spool_dir(s.conf)
            if os.path.isdir(sd):
                assert [f for f in os.listdir(sd) if f.endswith(".claim")] == []
            # the repeat serve is an in-memory result-cache hit
            q2 = s.read.parquet(src)
            q2 = q2.filter(q2["k"] == 13)
            t2 = fe.serve(q2)
            assert sorted_table(t1).equals(sorted_table(t2))
            assert fe.stats()["fleet"]["fast_result_hits"] >= 1
            # ...and the owner's result still reaches the durable spool
            # (async) for cross-host peers and crash recovery
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if fe.stats()["fleet"]["spool_publishes"] >= 1:
                    break
                time.sleep(0.02)
            assert fe.stats()["fleet"]["spool_publishes"] >= 1
        finally:
            fe.close()

    def test_remote_handoff_skips_spool(self, fleet_env):
        s1 = fleet_env["make_session"]()
        s2 = fleet_env["make_session"]()
        fe1, fe2 = s1.serve_frontend, s2.serve_frontend
        try:
            src = fleet_env["src"]
            q, _d = _query_owned_by(fe1, s1, src, fe2._router.owner)
            t = fe1.serve(q)
            st1, st2 = fe1.stats()["fleet"], fe2.stats()["fleet"]
            # the requester streamed the answer straight from the owner:
            # no claim election, no spool read, anywhere
            assert st1["fast_handoffs"] == 1, st1
            assert st2["fast_requests_served"] == 1, st2
            assert st1["claims_won"] + st2["claims_won"] == 0
            assert st1["spool_hits"] + st2["spool_hits"] == 0
            # bit-identical vs the unindexed truth
            s1.disable_hyperspace()
            want = q.collect()
            s1.enable_hyperspace()
            assert sorted_table(t).equals(sorted_table(want))
        finally:
            fe1.close()
            fe2.close()

    def test_refresh_push_beats_poll(self, fleet_env):
        # a refresh's fanout is PUSHED to the peer's socket (microsecond
        # delivery) and the durable poll then dedups it by event name
        src, rng = fleet_env["src"], fleet_env["rng"]
        s2 = fleet_env["make_session"](**{C.FLEET_BUS_POLL_MS: 60_000})
        fe2 = s2.serve_frontend
        try:
            pq.write_table(
                pa.table(
                    {
                        "k": pa.array(rng.integers(0, 60, 300), pa.int64()),
                        "v": pa.array(
                            rng.integers(-500, 500, 300), pa.int64()
                        ),
                    }
                ),
                os.path.join(src, "part-push.parquet"),
            )
            fleet_env["hs1"].refresh_index("fidx", "incremental")
            # the poll plane is parked for 60s: only the push can
            # deliver this fast
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if fe2.stats()["fleet"]["fast_push_received"] >= 1:
                    break
                time.sleep(0.01)
            st = fe2.stats()["fleet"]
            assert st["fast_push_received"] >= 1, st
            assert st["bus_events"] >= 1, st
        finally:
            fe2.close()

    def test_dead_owner_falls_back_bit_identical(self, fleet_env):
        # the in-process twin of the harness's kill -9 probe: the
        # owner's socket dies (member file stays — lease not expired),
        # the requester's fast path fails, the durable claim plane
        # answers, and the answer is bit-identical to the truth
        s1 = fleet_env["make_session"]()
        s2 = fleet_env["make_session"]()
        fe1, fe2 = s1.serve_frontend, s2.serve_frontend
        try:
            src = fleet_env["src"]
            q, _d = _query_owned_by(fe1, s1, src, fe2._router.owner)
            fe2._router._server.stop()  # kill the socket, keep the lease
            t = fe1.serve(q)
            st1 = fe1.stats()["fleet"]
            assert st1["fast_fallbacks"] == 1, st1
            assert st1["claims_won"] == 1, st1  # durable election won
            s1.disable_hyperspace()
            want = q.collect()
            s1.enable_hyperspace()
            assert sorted_table(t).equals(sorted_table(want))
        finally:
            fe1.close()
            fe2.close()

    def test_owner_verifies_digest_before_answering(self, fleet_env):
        # the fast-path correctness invariant: an owner whose snapshot
        # disagrees with the requested digest replies miss, never an
        # answer to a different question
        s1 = fleet_env["make_session"]()
        fe1 = s1.serve_frontend
        try:
            from hyperspace_tpu.obs import planspec
            from hyperspace_tpu.serve import fastbus

            src = fleet_env["src"]
            df = s1.read.parquet(src)
            df = df.filter(df["k"] == 7)
            spec = planspec.to_spec(df.logical_plan)
            reply, body = fastbus.request(
                fe1._router._server.path,
                {"type": "exec", "digest": "f" * 40, "spec": spec},
            )
            assert reply["status"] == "miss", reply
            assert reply["reason"] == "snapshot"
            assert body == b""
        finally:
            fe1.close()

    def test_member_files_reaped(self, tmp_path):
        from hyperspace_tpu.serve import router as fleet_router

        d = str(tmp_path / "members")
        os.makedirs(d)
        now = int(time.time() * 1000)
        # expired lease: reaped (socket file too)
        sock = str(tmp_path / "dead.sock")
        with open(sock, "w") as f:
            f.write("")
        with open(os.path.join(d, "aa.json"), "w") as f:
            json.dump(
                {"owner": "aa", "pid": 1, "sock": sock, "expiresAtMs": 1}, f
            )
        # live lease, live pid: kept
        with open(os.path.join(d, "bb.json"), "w") as f:
            json.dump(
                {
                    "owner": "bb",
                    "pid": os.getpid(),
                    "sock": "/tmp/x.sock",
                    "expiresAtMs": now + 600_000,
                },
                f,
            )
        # live lease, DEAD pid: reaped only under force_dead
        with open(os.path.join(d, "cc.json"), "w") as f:
            json.dump(
                {
                    "owner": "cc",
                    "pid": 2**22 + 12345,
                    "sock": "/tmp/y.sock",
                    "expiresAtMs": now + 600_000,
                },
                f,
            )
        reaped, leftovers = fleet_router.reap_members(d)
        assert reaped == 1 and leftovers == []
        assert not os.path.exists(sock)
        assert set(fleet_router.read_members(d)) == {"bb", "cc"}
        reaped, leftovers = fleet_router.reap_members(d, force_dead=True)
        assert reaped == 1 and leftovers == []
        assert set(fleet_router.read_members(d)) == {"bb"}

    def test_rendezvous_is_stable_and_balanced(self):
        from hyperspace_tpu.serve.router import rendezvous_owner

        owners = ["m1", "m2", "m3"]
        digests = [f"{i:040x}" for i in range(600)]
        first = [rendezvous_owner(owners, d) for d in digests]
        assert first == [rendezvous_owner(owners, d) for d in digests]
        counts = {o: first.count(o) for o in owners}
        assert all(c > 100 for c in counts.values()), counts
        # removing a member only moves ITS digests
        moved = sum(
            1
            for d, was in zip(digests, first)
            if was != "m3" and rendezvous_owner(["m1", "m2"], d) != was
        )
        assert moved == 0

    def test_spool_sweep_reaps_orphans_and_counts(self, fleet_env):
        s = fleet_env["make_session"](**{C.FLEET_FAST_ENABLED: False})
        s.conf.set(C.FLEET_SINGLEFLIGHT_CLAIM_MS, 100)
        fe = s.serve_frontend
        try:
            sd = spool_dir(s.conf)
            os.makedirs(sd, exist_ok=True)
            old = time.time() - 60.0
            for name in (
                "deadbeef.arrow.trace",  # orphan sidecar (no .arrow)
                "deadbeef.claim",  # stale claim
                ".tmp_spool_zz",  # crash-leaked publish temp
            ):
                p = os.path.join(sd, name)
                with open(p, "w") as f:
                    f.write("x")
                os.utime(p, (old, old))
            src = fleet_env["src"]
            q = s.read.parquet(src)
            q = q.filter(q["k"] == 21)
            fe.serve(q)  # the winner's publish runs the sweep
            names = os.listdir(sd)
            assert "deadbeef.arrow.trace" not in names
            assert "deadbeef.claim" not in names
            assert ".tmp_spool_zz" not in names
            st = fe.stats()["fleet"]
            assert st["spool_reaped_traces"] == 1, st
            assert st["spool_reaped_claims"] == 1, st
            assert st["spool_reaped_tmp"] == 1, st
        finally:
            fe.close()

    def test_fleet_wide_slo_sheds_on_gossiped_depth(self, fleet_env):
        conf = {
            C.FLEET_CLASS_KEY_PREFIX + "batch.maxConcurrency": 1,
            C.FLEET_CLASS_KEY_PREFIX + "batch.maxQueueDepth": 2,
            C.SERVE_MAX_CONCURRENCY: 8,
        }
        s1 = fleet_env["make_session"](**conf)
        s2 = fleet_env["make_session"](**conf)
        fe1, fe2 = s1.serve_frontend, s2.serve_frontend
        try:
            gate = threading.Event()
            fe2._execute_pinned = lambda plan, pin: (
                gate.wait(10.0),
                pa.table({"x": pa.array([1])}),
            )[1]
            src = fleet_env["src"]

            def q(sess, i):
                df = sess.read.parquet(src)
                return df.filter(df["k"] == i)

            # saturate fe2's batch tier (1 running + 1 pending = depth 2)
            futs = [fe2.submit(q(s2, i), slo_class="batch") for i in (0, 1)]
            # wait for fe1 to have RECEIVED the depth-2 gossip (a
            # depth-0 gossip from before the submits does not count)
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                fe2._router.push_gossip_now()
                with fe1._lock:
                    depth = sum(
                        c.get("batch", 0) for _ts, c in fe1._peer_slo.values()
                    )
                if depth >= 2:
                    break
                time.sleep(0.01)
            assert depth >= 2
            # fe1 is idle — but the FLEET's batch tier is at its bound,
            # so admission sheds here too (batch before interactive)
            with pytest.raises(ServeOverloadedError, match="fleet"):
                fe1.submit(q(s1, 50), slo_class="batch")
            t = fe1.serve(q(s1, 51), slo_class="interactive")
            assert t.num_rows >= 0
            gate.set()
            for f in futs:
                f.result(timeout=10)
        finally:
            gate.set()
            fe1.close()
            fe2.close()


# ---------------------------------------------------------------------------
# Per-tenant SLO classes
# ---------------------------------------------------------------------------


class TestSloClasses:
    def _frontend(self, fleet_env, **conf):
        s = fleet_env["make_session"](**{C.FLEET_ENABLED: False, **conf})
        return s, ServeFrontend(s)

    def test_class_max_concurrency_gates_running(self, fleet_env):
        s, fe = self._frontend(
            fleet_env,
            **{
                C.FLEET_CLASS_KEY_PREFIX + "batch.maxConcurrency": 1,
                C.SERVE_MAX_CONCURRENCY: 8,
            },
        )
        try:
            gate = threading.Event()
            running = []

            def slow_exec(plan, pin):
                running.append(1)
                assert gate.wait(10.0)
                return pa.table({"x": pa.array([len(running)])})

            fe._execute_pinned = slow_exec
            src = fleet_env["src"]
            futs = []
            for i in range(4):
                q = s.read.parquet(src)
                q = q.filter(q["k"] == i)  # distinct plans: no dedup
                futs.append(fe.submit(q, slo_class="batch"))
            time.sleep(0.2)
            st = fe.stats()["slo_classes"]["batch"]
            assert st["running"] == 1, st
            assert st["pending"] == 3, st
            assert len(running) == 1
            gate.set()
            for f in futs:
                f.result(timeout=10)
            st = fe.stats()["slo_classes"]["batch"]
            assert st["running"] == 0 and st["pending"] == 0
            assert st["admitted"] == 4
        finally:
            fe.close()

    def test_batch_sheds_before_interactive(self, fleet_env):
        s, fe = self._frontend(
            fleet_env,
            **{
                C.FLEET_CLASS_KEY_PREFIX + "batch.maxConcurrency": 1,
                C.FLEET_CLASS_KEY_PREFIX + "batch.maxQueueDepth": 2,
                C.SERVE_MAX_CONCURRENCY: 8,
                C.SERVE_MAX_QUEUE_DEPTH: 64,
            },
        )
        try:
            gate = threading.Event()
            fe._execute_pinned = lambda plan, pin: (
                gate.wait(10.0),
                pa.table({"x": pa.array([1])}),
            )[1]
            src = fleet_env["src"]

            def q(i):
                df = s.read.parquet(src)
                return df.filter(df["k"] == i)

            futs = [fe.submit(q(i), slo_class="batch") for i in range(2)]
            # the batch tier is at its depth: the third submit sheds...
            with pytest.raises(ServeOverloadedError, match="batch"):
                fe.submit(q(99), slo_class="batch")
            # ...while the interactive tier (and unclassed traffic) is
            # untouched by batch pressure
            f_int = fe.submit(q(7), slo_class="interactive")
            f_un = fe.submit(q(8))
            gate.set()
            for f in futs + [f_int, f_un]:
                f.result(timeout=10)
            st = fe.stats()
            assert st["slo_classes"]["batch"]["shed"] == 1
            assert st["shed"] == 1
        finally:
            fe.close()

    def test_unconfigured_class_unlimited(self, fleet_env):
        s, fe = self._frontend(fleet_env)
        try:
            src = fleet_env["src"]
            q = s.read.parquet(src)
            q = q.filter(q["k"] == 3)
            t = fe.serve(q, slo_class="nosuch")
            assert t.num_rows >= 0
            assert "slo_classes" not in fe.stats()
        finally:
            fe.close()

    def test_close_fails_parked_admissions(self, fleet_env):
        s, fe = self._frontend(
            fleet_env,
            **{C.FLEET_CLASS_KEY_PREFIX + "batch.maxConcurrency": 1},
        )
        gate = threading.Event()
        fe._execute_pinned = lambda plan, pin: (
            gate.wait(10.0),
            pa.table({"x": pa.array([1])}),
        )[1]
        src = fleet_env["src"]

        def q(i):
            df = s.read.parquet(src)
            return df.filter(df["k"] == i)

        f0 = fe.submit(q(0), slo_class="batch")
        f1 = fe.submit(q(1), slo_class="batch")  # parked
        gate.set()
        f0.result(timeout=10)
        fe.close(wait=False)
        # the parked admission either dispatched before close (ran) or
        # was failed with a typed error — never silently dropped
        try:
            f1.result(timeout=10)
        except Exception as exc:
            assert "closed" in str(exc).lower()


# ---------------------------------------------------------------------------
# Session integration
# ---------------------------------------------------------------------------


class TestSessionIntegration:
    def test_frontend_type_follows_fleet_flag(self, fleet_env):
        s = fleet_env["make_session"]()
        fe = s.serve_frontend
        assert isinstance(fe, FleetFrontend)
        s.conf.set(C.FLEET_ENABLED, False)
        fe2 = s.serve_frontend
        assert type(fe2) is ServeFrontend
        assert fe.closed  # the mode-mismatched frontend was retired
        s.conf.set(C.FLEET_ENABLED, True)
        fe3 = s.serve_frontend
        assert isinstance(fe3, FleetFrontend)
        fe3.close()


# ---------------------------------------------------------------------------
# The real thing: N OS processes over one lake (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestFleetProcesses:
    def test_two_processes_single_flight_and_convergence(self, tmp_path):
        from hyperspace_tpu.testing import fleet_harness

        rep = fleet_harness.run_fleet(
            str(tmp_path / "fleet"),
            n_procs=2,
            iters=3,
            rows=8000,
            fastpath_phase=True,
        )
        assert rep["wrong_answers"] == 0
        # workers never open a chip the parent may hold, and the row says
        # where its QPS came from
        assert rep["worker_platform"] == "cpu"
        # cross-process dedup now lands on the fast plane first (owner
        # handoffs / result-cache hits); the spool remains the fallback
        dedup = (
            rep["cross_process_dedup"]
            + rep["fast_handoffs"]
            + rep["fast_result_hits"]
        )
        assert dedup > 0, rep
        assert rep["fast_frontends"] == 2, rep
        assert rep["fast_push_received"] >= 1, rep  # pushed fanout seen
        assert rep["fast_handoffs"] >= 1, rep  # spool-free handoff seen
        assert rep["probe_mismatches"] == 0, rep
        assert rep["leaked_pin_files"] == 0
        assert rep["leaked_fast_members"] == 0

    def test_kill_nine_mid_serve(self, tmp_path):
        from hyperspace_tpu.testing import fleet_harness

        rep = fleet_harness.run_fleet(
            str(tmp_path / "chaos"),
            n_procs=3,
            iters=3,
            rows=8000,
            kill_one=True,
            fastpath_phase=True,
        )
        assert rep["killed"] and rep["workers_reporting"] == 2
        assert rep["wrong_answers"] == 0
        # the dead owner's member file outlives it (generous harness
        # lease): survivor probes MUST degrade fast->durable, answer
        # bit-identically, and the convergence reap must leave no member
        # file or socket behind
        assert rep["fast_fallbacks"] >= 1, rep
        assert rep["probe_mismatches"] == 0, rep
        assert rep["leaked_pin_files"] == 0
        assert rep["leaked_fast_members"] == 0
