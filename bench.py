"""Benchmark harness — measured numbers on the real chip.

Runs the BASELINE.md config-ladder shapes that fit one chip:

  * config 1/2 analogue: covering index build over a TPC-H-like
    ``lineitem`` (int64 key + date + payload), then an indexed point
    filter (FilterIndexRule serve path) vs the unindexed scan;
  * config 3 analogue: ``orders ⋈ lineitem`` via JoinIndexRule
    (co-bucketed, shuffle-free) vs the unindexed sort-merge join.

The Spark-CPU column of BASELINE.md cannot be produced here (the
reference is a JVM/Spark library; no Spark runtime in this image), so
``vs_baseline`` is the measured speedup of the indexed path over the
unindexed path *on the same chip* — the reference's own headline claim
(query acceleration from index-based plan rewrites) measured natively.

Prints exactly ONE JSON line on stdout; progress goes to stderr.

Env knobs: HS_BENCH_ROWS (lineitem rows, default 4M), HS_BENCH_REPS
(timing reps, default 5), HS_BENCH_BUCKETS (default 8).
HS_BENCH_STREAM_LADDER (out-of-core join rung rows, default
64M,256M; append 1000000000 for the opt-in 1B rung),
HS_BENCH_STREAM_MAX_BYTES (wave budget override),
HS_BENCH_STREAM_BASELINE_MAX (largest rung that also times the
materializing stream-off baseline, default 64M).
HS_RESIDENCY_WITNESS=<path> arms the runtime residency witness
(testing/residency_witness.py) for the whole run: per-site peak bytes +
RSS high-water land in the artifact AND in the headline JSON's
"residency" block, and ``hslint --witness <path>`` gates the run
against the ALLOC_SITES bound model (docs/static-analysis.md).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

# NOTE: no JAX_PLATFORMS override — this must run on the real chip when
# one is attached (tests force cpu; the bench must not).


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def rss_hwm() -> int:
    """Process resident-set high-water mark in bytes (monotone over the
    process lifetime — a per-rung reading is the peak *so far*, so
    growth between rungs localizes which rung paid it)."""
    from hyperspace_tpu.testing.residency_witness import rss_high_water_bytes

    return rss_high_water_bytes()


def timeit(fn, reps: int):
    """{p50, iqr, n} over ``reps`` trials — the bench defends its own
    numbers: an anomalous trial (CPU contention, page-cache eviction)
    shows up as a wide IQR instead of silently skewing a bare median."""
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    q1, med, q3 = np.percentile(ts, [25, 50, 75])
    return {"p50": float(med), "iqr": float(q3 - q1), "n": reps}


def gen_data(
    tmp: str, n_items: int, n_orders: int, n_files: int = 8, seed: int = 7
):
    """Write the bench's ``lineitem``/``orders`` tables under ``tmp`` as
    ``n_files`` parquet files each, every value drawn from ``seed``
    (``chip_smoke.py`` runs the same shape on the chip)."""
    rng = np.random.default_rng(seed)
    items_dir = os.path.join(tmp, "lineitem")
    orders_dir = os.path.join(tmp, "orders")
    os.makedirs(items_dir)
    os.makedirs(orders_dir)
    # lineitem: key skewed across orders, date + qty + price payload
    l_orderkey = rng.integers(0, n_orders, n_items, dtype=np.int64)
    base_date = np.datetime64("1994-01-01")
    l_shipdate = base_date + rng.integers(0, 2400, n_items).astype("timedelta64[D]")
    l_quantity = rng.integers(1, 51, n_items, dtype=np.int64)
    l_extendedprice = rng.normal(30000, 8000, n_items)
    # Rows are laid out in ship-date order before slicing into files, the
    # natural layout of an append-mostly fact table (each file ≈ a date
    # window). This gives the data-skipping bench real per-file min/max
    # ranges to prune; l_orderkey stays uniform within every file, so the
    # key-based filter/join benches are unaffected.
    ship_order = np.argsort(l_shipdate, kind="stable")
    items = pa.table(
        {
            "l_orderkey": l_orderkey[ship_order],
            "l_shipdate": pa.array(
                l_shipdate[ship_order].astype("datetime64[D]")
            ),
            "l_quantity": l_quantity[ship_order],
            "l_extendedprice": l_extendedprice[ship_order],
        }
    )
    o_orderkey = np.arange(n_orders, dtype=np.int64)
    orders = pa.table(
        {
            "o_orderkey": o_orderkey,
            "o_custkey": rng.integers(0, max(n_orders // 10, 1), n_orders),
            "o_totalprice": rng.normal(150000, 30000, n_orders),
        }
    )
    for i in range(n_files):
        lo, hi = i * n_items // n_files, (i + 1) * n_items // n_files
        pq.write_table(items.slice(lo, hi - lo), os.path.join(items_dir, f"part{i}.parquet"))
        lo, hi = i * n_orders // n_files, (i + 1) * n_orders // n_files
        pq.write_table(orders.slice(lo, hi - lo), os.path.join(orders_dir, f"part{i}.parquet"))
    return items_dir, orders_dir


def main() -> None:
    n_items = int(os.environ.get("HS_BENCH_ROWS", 4_000_000))
    n_orders = max(n_items // 8, 1)
    reps = int(os.environ.get("HS_BENCH_REPS", 5))
    num_buckets = int(os.environ.get("HS_BENCH_BUCKETS", 8))

    # HS_BENCH_FORCE_CPU_DEVICES=n: simulate an n-device CPU mesh (the
    # smoke uses 8 so the mesh ladder rows exercise the sharded tail on
    # every CI pass). Must be set before the jax backend initializes; no
    # effect unless requested — a real chip keeps its real devices.
    force_dev = os.environ.get("HS_BENCH_FORCE_CPU_DEVICES")
    if force_dev:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={int(force_dev)}"
            ).strip()

    import jax

    from hyperspace_tpu import constants as C
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.indexes.covering import CoveringIndexConfig
    from hyperspace_tpu.session import HyperspaceSession

    platform = jax.devices()[0].platform
    log(f"bench: devices={jax.devices()} rows={n_items:,} buckets={num_buckets}")

    tmp = tempfile.mkdtemp(prefix="hs_bench_")
    try:
        items_dir, orders_dir = gen_data(tmp, n_items, n_orders)
        session = HyperspaceSession()
        session.conf.set(C.INDEX_SYSTEM_PATH, os.path.join(tmp, "indexes"))
        session.conf.set(C.INDEX_NUM_BUCKETS, num_buckets)
        hs = Hyperspace(session)
        items = session.read.parquet(items_dir)
        orders = session.read.parquet(orders_dir)

        # One-time per-MACHINE setup, not per-process: the native kernel
        # compile caches a .so next to its source (like a C extension
        # built at install time). Keep it out of the cold-build timer,
        # which measures fresh-process build cost.
        from hyperspace_tpu import native

        native.load()

        # HS_RESIDENCY_WITNESS=<path>: wrap every ALLOC_SITES-registered
        # allocation site for the whole run and dump per-site peak bytes
        # + RSS high-water into the artifact at the end; bench_smoke then
        # gates `hslint --witness` on it (zero model gaps, zero
        # bound-class violations). Armed before any workload so the
        # witness sees the cold path too.
        residency_art = os.environ.get("HS_RESIDENCY_WITNESS")
        if residency_art:
            from hyperspace_tpu.testing import residency_witness

            residency_witness.install()

        # --- index build (cold = includes XLA compile; warm = steady state)
        cfg_l = CoveringIndexConfig(
            "l_idx", ["l_orderkey"], ["l_shipdate", "l_quantity", "l_extendedprice"]
        )
        t0 = time.perf_counter()
        hs.create_index(items, cfg_l)
        build_cold = time.perf_counter() - t0
        hs.delete_index("l_idx")
        hs.vacuum_index("l_idx")
        session.index_manager.clear_cache()
        t0 = time.perf_counter()
        hs.create_index(items, cfg_l)
        build_warm = time.perf_counter() - t0
        from hyperspace_tpu.indexes.covering_build import last_build_breakdown

        breakdown = {k: round(v, 3) for k, v in last_build_breakdown.items()}
        staged = sum(breakdown.values())
        breakdown["other"] = round(max(build_warm - staged, 0.0), 3)
        log(
            f"build lineitem index: cold {build_cold:.2f}s, warm {build_warm:.2f}s "
            f"({n_items / build_warm:,.0f} rows/s warm); stages: {breakdown}"
        )
        cfg_o = CoveringIndexConfig("o_idx", ["o_orderkey"], ["o_custkey", "o_totalprice"])
        hs.create_index(orders, cfg_o)

        # --- point filter (FilterIndexRule serve path, bucket-pruned)
        session.conf.set(C.INDEX_FILTER_RULE_USE_BUCKET_SPEC, True)
        key = int(n_orders // 3)

        def q_filter(df):
            return df.filter(df["l_orderkey"] == key).select(
                "l_orderkey", "l_shipdate", "l_quantity"
            )

        session.enable_hyperspace()
        plan = q_filter(items).explain()
        if "Hyperspace(Type: CI" not in plan:
            log(f"WARNING: filter not index-served:\n{plan}")
        indexed_rows = q_filter(items).collect().num_rows  # warmup + sanity
        filter_idx = timeit(lambda: q_filter(items).collect(), reps)
        session.disable_hyperspace()
        base_rows = q_filter(items).collect().num_rows
        assert base_rows == indexed_rows, (base_rows, indexed_rows)
        filter_raw = timeit(lambda: q_filter(items).collect(), reps)
        log(
            f"point filter p50: indexed {filter_idx['p50'] * 1e3:.1f}ms vs "
            f"unindexed {filter_raw['p50'] * 1e3:.1f}ms "
            f"({filter_raw['p50'] / filter_idx['p50']:.2f}x)"
        )

        # --- fused serve-pipeline compiler (filter→aggregate;
        # docs/serve-compiler.md): interleaved A/B of the fused native
        # pass vs the interpreted chain
        # (hyperspace.serve.fusedpipeline.enabled on/off within one
        # process, so page-cache/allocator drift hits both legs). The
        # dispatch threshold is pinned low FOR THIS SECTION only: the
        # A/B measures fused-vs-interpreted, not the calibrated
        # crossover (which would route tiny smoke runs to the
        # interpreted chain on both legs and measure nothing).
        from hyperspace_tpu import functions as hsf
        from hyperspace_tpu.execution import pipeline_compiler as _pc

        _fused_min_saved = _pc._NATIVE_FUSED_PIPELINE_MIN_ROWS
        _pc._NATIVE_FUSED_PIPELINE_MIN_ROWS = 1 << 10
        agg_lo = n_orders // 4
        agg_hi = agg_lo + max(n_orders // 8, 1)

        def q_fagg(df):
            return df.filter(
                (df["l_orderkey"] >= agg_lo) & (df["l_orderkey"] < agg_hi)
            ).agg(
                hsf.count().alias("n"),
                hsf.sum("l_extendedprice").alias("rev"),
                hsf.min("l_quantity").alias("qmin"),
                hsf.max("l_quantity").alias("qmax"),
            )

        def q_gagg(df):
            return (
                df.filter(
                    (df["l_orderkey"] >= agg_lo) & (df["l_orderkey"] < agg_hi)
                )
                .group_by("l_quantity")
                .agg(
                    hsf.count().alias("n"),
                    hsf.sum("l_extendedprice").alias("rev"),
                )
            )

        def _ab_stats(ts):
            q1, med, q3 = np.percentile(ts, [25, 50, 75])
            return {"p50": float(med), "iqr": float(q3 - q1), "n": len(ts)}

        def ab_fused(q):
            # reset the telemetry BEFORE the warm run: a silent fused
            # fallback must read as fused_ran=False, not inherit the
            # previous query's stats (the smoke gate depends on this)
            _pc.last_fused_stats = {}
            q(items).collect()  # warm (and capture the fused telemetry)
            stats = {
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in _pc.last_fused_stats.items()
            }
            t_on, t_off = [], []
            rows_on = rows_off = None
            for _ in range(reps):
                t0 = time.perf_counter()
                rows_on = q(items).collect().num_rows
                t_on.append(time.perf_counter() - t0)
                session.conf.set(C.SERVE_FUSEDPIPELINE_ENABLED, False)
                t0 = time.perf_counter()
                rows_off = q(items).collect().num_rows
                t_off.append(time.perf_counter() - t0)
                session.conf.unset(C.SERVE_FUSEDPIPELINE_ENABLED)
            assert rows_on == rows_off, (rows_on, rows_off)
            return _ab_stats(t_on), _ab_stats(t_off), stats

        session.enable_hyperspace()
        plan = q_fagg(items).explain()
        if "Hyperspace(Type: CI" not in plan:
            log(f"WARNING: filter-aggregate not index-served:\n{plan}")
        fagg_on, fagg_off, fagg_stats = ab_fused(q_fagg)
        gagg_on, gagg_off, gagg_stats = ab_fused(q_gagg)
        _pc._NATIVE_FUSED_PIPELINE_MIN_ROWS = _fused_min_saved
        session.disable_hyperspace()
        log(
            "filter→aggregate p50: fused "
            f"{fagg_on['p50'] * 1e3:.1f}ms vs interpreted "
            f"{fagg_off['p50'] * 1e3:.1f}ms "
            f"({fagg_off['p50'] / fagg_on['p50']:.2f}x); "
            f"scanned {fagg_stats.get('rows_scanned', 0):,} rows, "
            f"passed {fagg_stats.get('rows_passed', 0):,}, fused "
            f"materialized {fagg_stats.get('rows_materialized', 0):,} "
            "(interpreted materializes every passing row per column)"
        )
        log(
            "grouped-aggregate p50: fused "
            f"{gagg_on['p50'] * 1e3:.1f}ms vs interpreted "
            f"{gagg_off['p50'] * 1e3:.1f}ms "
            f"({gagg_off['p50'] / gagg_on['p50']:.2f}x); "
            f"{gagg_stats.get('groups', 0)} groups over "
            f"{gagg_stats.get('rows_passed', 0):,} passing rows"
        )

        # --- aggregate index plane (docs/agg-serve.md): a fully-covered
        # grouped point aggregate answered from the _aggstate sidecar
        # with ZERO parquet row groups read, A/B'd interleaved against
        # the fused pass (hyperspace.index.agg.enabled off forces the
        # PR 7 path on the SAME plan); then the sampling plane's
        # approximate COUNT/SUM vs exact. The dedicated single-column
        # z-order index keeps row groups range-sorted on the filter key
        # so whole-row-group coverage is real, not a bucket accident.
        from hyperspace_tpu.indexes.zorder import (
            ZOrderCoveringIndexConfig as _ZCfg,
        )

        hs.create_index(
            items,
            _ZCfg("agg_idx", ["l_orderkey"], ["l_quantity", "l_extendedprice"]),
        )

        def q_meta(df):
            return (
                df.filter(df["l_orderkey"] >= 0)
                .group_by("l_quantity")
                .agg(
                    hsf.count().alias("n"),
                    hsf.min("l_orderkey").alias("kmin"),
                    hsf.max("l_orderkey").alias("kmax"),
                    hsf.sum("l_orderkey").alias("ksum"),
                )
            )

        _pc._NATIVE_FUSED_PIPELINE_MIN_ROWS = 1 << 10
        session.enable_hyperspace()
        _pc.last_aggplane_stats = {}
        meta_rows = q_meta(items).collect().num_rows
        meta_stats = {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in _pc.last_aggplane_stats.items()
        }
        t_meta, t_fused_ab = [], []
        rows_a = rows_b = None
        for _ in range(reps):
            t0 = time.perf_counter()
            rows_a = q_meta(items).collect().num_rows
            t_meta.append(time.perf_counter() - t0)
            session.conf.set(C.INDEX_AGG_ENABLED, False)
            t0 = time.perf_counter()
            rows_b = q_meta(items).collect().num_rows
            t_fused_ab.append(time.perf_counter() - t0)
            session.conf.unset(C.INDEX_AGG_ENABLED)
        assert rows_a == rows_b == meta_rows, (rows_a, rows_b, meta_rows)
        meta_ab = (_ab_stats(t_meta), _ab_stats(t_fused_ab))
        log(
            "agg-metadata p50: sidecar "
            f"{meta_ab[0]['p50'] * 1e3:.2f}ms vs fused "
            f"{meta_ab[1]['p50'] * 1e3:.2f}ms "
            f"({meta_ab[1]['p50'] / meta_ab[0]['p50']:.1f}x); "
            f"{meta_stats.get('row_groups_metadata', 0)}/"
            f"{meta_stats.get('row_groups_total', 0)} row groups from "
            f"metadata, {meta_stats.get('rows_scanned', 0)} rows read"
        )

        # approximate plane: bounded-error COUNT/SUM from the stratified
        # sample (explicit opt-in; exact collect() is never substituted)
        from hyperspace_tpu.execution import approx_exec as _apx

        session.conf.set(C.SERVE_APPROX_ENABLED, True)
        q_apx = items.filter(
            (items["l_orderkey"] >= agg_lo) & (items["l_orderkey"] < agg_hi)
        ).agg(hsf.count().alias("n"), hsf.sum("l_quantity").alias("sq"))
        est = q_apx.collect_approx(max_rel_error=1.0)
        t_apx = timeit(
            lambda: q_apx.collect_approx(max_rel_error=1.0), reps
        )
        t_exact = timeit(lambda: q_apx.collect(), reps)
        truth = q_apx.collect()
        e = est.to_pydict()
        tn = truth.column("n").to_pylist()[0]
        ts_ = truth.column("sq").to_pylist()[0]
        apx_stats = dict(_apx.last_approx_stats)
        n_in_ci = bool(e["n_lo"][0] <= tn <= e["n_hi"][0])
        s_in_ci = bool(e["sq_lo"][0] <= ts_ <= e["sq_hi"][0])
        n_err = abs(e["n"][0] - tn) / max(tn, 1)
        log(
            f"agg-approx p50: estimate {t_apx['p50'] * 1e3:.2f}ms vs exact "
            f"{t_exact['p50'] * 1e3:.2f}ms; COUNT rel err {n_err:.4f} "
            f"(bound held: n={n_in_ci}, sum={s_in_ci}; "
            f"{apx_stats.get('sample_rows', 0):,} sampled of "
            f"{apx_stats.get('population_rows', 0):,} rows)"
        )
        session.conf.unset(C.SERVE_APPROX_ENABLED)
        _pc._NATIVE_FUSED_PIPELINE_MIN_ROWS = _fused_min_saved
        session.disable_hyperspace()
        hs.delete_index("agg_idx")
        hs.vacuum_index("agg_idx")
        session.index_manager.clear_cache()

        # --- indexed join (JoinIndexRule, co-bucketed, shuffle-free)
        def q_join(o, i):
            return o.join(i, on=o["o_orderkey"] == i["l_orderkey"]).select(
                "o_orderkey", "o_custkey", "l_quantity"
            )

        session.enable_hyperspace()
        plan = q_join(orders, items).explain()
        if plan.count("Hyperspace(Type: CI") != 2:
            log(f"WARNING: join not index-served on both sides:\n{plan}")
        j_rows = q_join(orders, items).collect().num_rows
        join_idx = timeit(lambda: q_join(orders, items).collect(), reps)
        # per-stage serve breakdown of the LAST uncached run (busy time;
        # stages overlap under the pipelined serve, so they can sum past
        # the p50 wall — the overlapped excess is the pipeline win)
        from hyperspace_tpu.execution import join_exec

        join_stages = {
            k: round(v * 1e3, 2)
            for k, v in join_exec.last_serve_breakdown.items()
        }
        log(f"join serve stages (last uncached run, busy ms): {join_stages}")
        session.disable_hyperspace()
        jb_rows = q_join(orders, items).collect().num_rows
        assert j_rows == jb_rows, (j_rows, jb_rows)
        join_raw = timeit(lambda: q_join(orders, items).collect(), reps)
        log(
            f"join p50: indexed {join_idx['p50'] * 1e3:.1f}ms vs "
            f"unindexed {join_raw['p50'] * 1e3:.1f}ms "
            f"({join_raw['p50'] / join_idx['p50']:.2f}x)"
        )

        # --- serve-server mode: the same queries with the serve cache on
        # (hyperspace.serve.cache.enabled): decoded index data stays in
        # RAM between queries, so a warm serve pays only match/mask work.
        # Results stay differential-checked against the uncached serve.
        session.enable_hyperspace()
        session.conf.set(C.SERVE_CACHE_ENABLED, True)
        assert q_filter(items).collect().num_rows == indexed_rows  # warm
        filter_cached = timeit(lambda: q_filter(items).collect(), reps)
        assert q_join(orders, items).collect().num_rows == j_rows  # warm
        join_cached = timeit(lambda: q_join(orders, items).collect(), reps)
        cache = session.serve_cache
        log(
            f"serve-server (cached): filter {filter_cached['p50'] * 1e3:.2f}ms "
            f"({filter_raw['p50'] / filter_cached['p50']:.1f}x), "
            f"join {join_cached['p50'] * 1e3:.1f}ms "
            f"({join_raw['p50'] / join_cached['p50']:.2f}x); "
            f"{cache.resident_bytes / 1e6:.0f}MB resident"
        )
        # --- concurrent serve frontend (serve/frontend.py): the
        # contention ladder — the SAME indexed point workload at 1/8/64
        # clients through the admission-controlled frontend (snapshot
        # pinning, single-flight, shedding). p50/p99 are client-observed;
        # QPS counts completed queries over the rung's wall clock. Keys
        # cycle a 256-key working set so the serve cache is exercised
        # (warm hits) without single-flight collapsing the whole rung
        # into one execution.
        from hyperspace_tpu.serve import ServeFrontend
        from hyperspace_tpu.testing import faults as _flt

        rng_k = np.random.default_rng(23)
        ladder_keys = [
            int(k) for k in rng_k.integers(0, n_orders, 256)
        ]

        def q_point_k(k):
            return items.filter(items["l_orderkey"] == k).select(
                "l_orderkey", "l_quantity"
            )

        def serve_rung(clients, queries_per_client=8):
            session.clear_serve_cache()
            fe = ServeFrontend(session)
            lats, errors = [], []
            lat_lock = threading.Lock()

            def client(ci):
                try:
                    for j in range(queries_per_client):
                        k = ladder_keys[
                            (ci * queries_per_client + j) % len(ladder_keys)
                        ]
                        t0 = time.perf_counter()
                        fe.serve(q_point_k(k))
                        dt = time.perf_counter() - t0
                        with lat_lock:
                            lats.append(dt)
                except Exception as exc:
                    errors.append(exc)

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=client, args=(ci,))
                for ci in range(clients)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            cache = session.serve_cache
            stats = fe.stats()
            fe.close()
            assert not errors, errors[:3]
            assert cache.high_water_bytes <= cache.max_bytes
            lats.sort()
            return {
                "clients": clients,
                "queries": len(lats),
                "p50_ms": round(lats[len(lats) // 2] * 1e3, 2),
                "p99_ms": round(
                    lats[min(len(lats) - 1, len(lats) * 99 // 100)] * 1e3, 2
                ),
                "qps": round(len(lats) / wall, 1),
                "cache_high_water_bytes": cache.high_water_bytes,
                "cache_max_bytes": cache.max_bytes,
                "deduped": stats["deduped"],
                "shed": stats["shed"],
                "retries": stats["retries"],
            }

        serve_concurrency = []
        for clients in (1, 8, 64):
            row = serve_rung(clients)
            serve_concurrency.append(row)
            log(
                f"serve frontend {clients:>2} clients: p50 {row['p50_ms']}ms "
                f"p99 {row['p99_ms']}ms {row['qps']} qps "
                f"(deduped {row['deduped']}, cache high-water "
                f"{row['cache_high_water_bytes'] / 1e6:.0f}MB)"
            )

        # --- obs plane A/B (hyperspace_tpu/obs/, docs/observability.md):
        # the SAME 8-client rung with tracing+querylog ON vs OFF,
        # interleaved on/off/on/off so drift hits both legs equally.
        # The on legs additionally prove the structural contract
        # bench_smoke.sh gates on: every EXECUTION yields exactly one
        # root span, and the querylog gains exactly one schema-valid
        # row per execution (deduped submits share the winner's trace).
        from hyperspace_tpu.obs import querylog as _oql
        from hyperspace_tpu.obs import trace as _otr

        obs_dir = _oql.obs_root(session.conf)
        obs_legs = {"on": [], "off": []}
        obs_roots = obs_rows_written = obs_executions = 0
        session.conf.set(C.OBS_TRACE_RETAIN, 4096)
        for leg in ("on", "off", "on", "off"):
            session.conf.set(C.OBS_ENABLED, leg == "on")
            _otr.reset()
            rows_before = len(_oql.read_records(obs_dir))
            row = serve_rung(8)
            obs_legs[leg].append(row)
            if leg == "on":
                executions = row["queries"] - row["deduped"]
                roots = _otr.finished("serve.query")
                all_rows = _oql.read_records(obs_dir)
                rows_now = len(all_rows)
                assert len(roots) == executions, (len(roots), executions)
                for r in roots:
                    assert r.attrs.get("status") == "ok", r.attrs
                assert rows_now - rows_before == executions, (
                    rows_now, rows_before, executions,
                )
                root_ids = {r.trace_id for r in roots}
                new_rows = [
                    rec
                    for rec in all_rows
                    if rec.get("trace_id") in root_ids
                ]
                assert len(new_rows) == executions
                for rec in new_rows:
                    err = _oql.validate_record(rec)
                    assert err is None, (err, rec)
                obs_roots += len(roots)
                obs_rows_written += rows_now - rows_before
                obs_executions += executions
        session.conf.set(C.OBS_ENABLED, False)
        _otr.set_enabled(False)
        _otr.reset()
        obs_p50_on = float(
            np.median([r["p50_ms"] for r in obs_legs["on"]])
        )
        obs_p50_off = float(
            np.median([r["p50_ms"] for r in obs_legs["off"]])
        )
        obs_overhead = obs_p50_on / max(obs_p50_off, 1e-9) - 1.0
        serve_obs = {
            "p50_on_ms": round(obs_p50_on, 2),
            "p50_off_ms": round(obs_p50_off, 2),
            "overhead_ratio": round(obs_overhead, 4),
            "roots": obs_roots,
            "querylog_rows": obs_rows_written,
            "executions": obs_executions,
        }
        if n_items >= 4_000_000:
            # the acceptance bar holds at the real rung; tiny smoke
            # rows are noise-dominated and only gate the structure
            assert obs_overhead <= 0.05, serve_obs
        log(
            f"obs A/B: p50 on {serve_obs['p50_on_ms']}ms / off "
            f"{serve_obs['p50_off_ms']}ms ({obs_overhead * 100:+.1f}%), "
            f"{obs_roots} roots == {obs_executions} executions, "
            f"{obs_rows_written} querylog rows"
        )

        # --- advisor closed-loop rung (hyperspace_tpu/advisor/,
        # docs/advisor.md): a canned skewed workload over a dedicated
        # lake — record it in query-log format, replay for a baseline,
        # run profile → what-if recommend → budgeted apply, replay the
        # SAME workload again, then a second advise() pass. The gates
        # bench_smoke.sh asserts: the top create recommendation indexes
        # the workload's filter key (the bench-fastest index for a point
        # lookup), it applies under budget, the post-apply pass emits
        # ZERO create recommendations (convergence), and replay QPS
        # stays within tolerance of the baseline (the index must never
        # fall off a cliff, even where brute scans win on tiny rows).
        from hyperspace_tpu.advisor import advise as _advise
        from hyperspace_tpu.advisor import (
            apply_recommendations as _advisor_apply,
        )
        from hyperspace_tpu.testing import replay as _replay

        adv_lake = os.path.join(tmp, "advisor_lake")
        os.makedirs(adv_lake)
        adv_rows = min(n_items, 2_000_000)
        adv_files = 8
        rng = np.random.default_rng(29)
        per = max(1, adv_rows // adv_files)
        for i in range(adv_files):
            pq.write_table(
                pa.table(
                    {
                        "key": rng.integers(0, 1000, per),
                        "ts": np.arange(i * per, (i + 1) * per, dtype=np.int64),
                        "payload": rng.integers(0, 1 << 30, per),
                    }
                ),
                os.path.join(adv_lake, f"part-{i:03d}.parquet"),
            )
        adv_records = _replay.skewed_keys(
            [adv_lake],
            "key",
            list(range(0, 1000, 37)),
            24,
            project=["key", "payload"],
        )
        adv_obs_dir = os.path.join(tmp, "advisor_obs")
        _replay.record_workload(adv_records, adv_obs_dir)
        adv_base = _replay.replay_records(session, adv_records)
        assert adv_base.completed == len(adv_records), adv_base.to_dict()
        adv_report = _advise(session, directory=adv_obs_dir)
        adv_creates = [
            r for r in adv_report.recommendations if r.kind == "create"
        ]
        assert adv_creates, "skewed workload must motivate an index"
        assert adv_creates[0].indexed_columns[0] == "key", adv_creates[0]
        adv_summary = _advisor_apply(session, adv_creates, force=True)
        assert adv_summary["applied"] >= 1, adv_summary
        adv_after = _replay.replay_records(session, adv_records)
        assert adv_after.completed == len(adv_records), adv_after.to_dict()
        adv_second = _advise(session, directory=adv_obs_dir)
        adv_creates_after = [
            r for r in adv_second.recommendations if r.kind == "create"
        ]
        assert not adv_creates_after, [r.to_dict() for r in adv_creates_after]
        adv_qps_ratio = adv_after.qps / max(adv_base.qps, 1e-9)
        assert 0.2 <= adv_qps_ratio <= 5.0, (
            adv_base.to_dict(), adv_after.to_dict(),
        )
        advisor_rung = {
            "records": len(adv_records),
            "baseline_p50_ms": round(adv_base.p50_s * 1e3, 2),
            "after_p50_ms": round(adv_after.p50_s * 1e3, 2),
            "baseline_qps": round(adv_base.qps, 1),
            "after_qps": round(adv_after.qps, 1),
            "qps_ratio": round(adv_qps_ratio, 3),
            "recommended": [r.index_name for r in adv_creates],
            "top_indexed_columns": list(adv_creates[0].indexed_columns),
            "applied": adv_summary["applied"],
            "creates_after_apply": len(adv_creates_after),
        }
        log(
            f"advisor loop: {len(adv_creates)} rec(s) "
            f"({adv_creates[0].index_name} on "
            f"{','.join(adv_creates[0].indexed_columns)}), applied "
            f"{adv_summary['applied']}, p50 {advisor_rung['baseline_p50_ms']}"
            f"ms -> {advisor_rung['after_p50_ms']}ms, qps ratio "
            f"{advisor_rung['qps_ratio']}, converged="
            f"{not adv_creates_after}"
        )

        # --- fault-injection rung (testing/faults.py): one serve per
        # injection point x {transient, persistent}, each differential
        # against the fault-free result — the bench-level witness that
        # every point fires and the retry/degrade paths answer
        # bit-identically (bench_smoke.sh asserts the fired counts)
        # two query shapes per leg: the point filter exercises the read/
        # log/cache seams; the filter→aggregate exercises the fused
        # native pass, whose dispatch (native.load) is where the
        # kernel_dispatch point lives — a tiny point query can sit below
        # every native threshold and never touch the loader. The
        # aggregate sums an INT column only: the parquet_read-persistent
        # leg degrades to the source-order plan, and float sums are not
        # associative across the index-vs-source row orders (the same
        # boundary docs/serve-compiler.md documents) — int sums are
        # exact under any order, keeping the differential bitwise.
        def q_fault_agg(df):
            return df.filter(
                (df["l_orderkey"] >= agg_lo) & (df["l_orderkey"] < agg_hi)
            ).agg(
                hsf.count().alias("n"),
                hsf.sum("l_quantity").alias("sq"),
            )

        fault_qs = [q_point_k(ladder_keys[0]), q_fault_agg(items)]
        fault_bases = [session.execute(q.logical_plan) for q in fault_qs]
        _flt.reset()
        fe = ServeFrontend(session)
        for point, spec in (
            ("parquet_read", "transient:1"),
            ("parquet_read", "persistent;match=v__="),
            ("kernel_dispatch", "transient:1"),
            ("kernel_dispatch", "persistent"),
            ("log_read", "transient:1"),
            ("log_read", "persistent"),
            ("cache_insert", "transient:1"),
            ("cache_insert", "persistent"),
        ):
            session.clear_serve_cache()
            session.index_manager.clear_cache()
            _flt.set_fault(point, spec)
            for q, base_t in zip(fault_qs, fault_bases):
                out = fe.serve(q)
                assert out.equals(base_t), (point, spec)
            _flt.clear()
        # the fastbus_send seam lives on the fleet fast plane (serve/
        # fastbus.py), not the single-process serve path: fire it at the
        # transport directly — an armed fault surfaces as the typed
        # OSError every caller catches to fall back to the durable
        # planes (the fleet ladder's chaos rung witnesses that fallback
        # end to end)
        from hyperspace_tpu.serve import fastbus as _fastbus
        from hyperspace_tpu.testing.faults import InjectedFault as _IF

        _flt.set_fault("fastbus_send", "transient:1")
        try:
            _fastbus.push(os.path.join(tmp, "no-such.sock"), {"type": "event"})
            raise AssertionError("armed fastbus_send did not fire")
        except _IF:
            pass
        _flt.clear()
        fault_stats = fe.stats()
        fe.close()
        fault_fired = _flt.stats()
        _flt.reset()
        missing = [p for p in _flt.POINTS if fault_fired.get(p, 0) < 1]
        assert not missing, f"fault points never fired: {missing}"
        log(
            f"fault matrix: fired {fault_fired}; frontend retries "
            f"{fault_stats['retries']}, degraded {fault_stats['degraded']}, "
            f"degraded pins {fault_stats['degraded_pins']}, failed "
            f"{fault_stats['failed']}"
        )
        assert fault_stats["failed"] == 0

        # --- chaos rung (testing/chaos.py, docs/recovery.md): a seeded
        # lifecycle schedule crashed at each (step x point) cell in
        # turn, recovered, retried, and differentially served — the
        # bench-level witness that a crashed writer never strands an
        # index, never changes an answer, and never leaks an orphan
        # (bench_smoke.sh gates on the three zeros below)
        from hyperspace_tpu.testing import chaos as _chaos

        chaos_summary = _chaos.run_crash_matrix(
            os.path.join(tmp, "chaos"),
            seed=11,
            n_steps=10,
            max_cells=int(os.environ.get("HS_BENCH_CHAOS_CELLS", 8)),
        )
        assert chaos_summary["crashes_fired"] >= 1, chaos_summary
        assert chaos_summary["stranded_after_recovery"] == 0, chaos_summary
        assert chaos_summary["orphans_after_gc"] == 0, chaos_summary
        assert chaos_summary["serve_mismatches"] == 0, chaos_summary
        log(
            f"chaos: {chaos_summary['cells']} cells, "
            f"{chaos_summary['crashes_fired']} crashes fired, "
            f"{chaos_summary['rolled_back']} rollbacks, "
            f"{chaos_summary['serves_verified']} serves verified, "
            f"0 stranded / 0 orphans / 0 mismatches"
        )

        # --- multi-process fleet ladder (serve/fleet.py, docs/fleet-
        # serve.md): N REAL frontend processes over one lake, identical
        # schedules from a barrier start — the horizontal twin of the
        # 1/8/64-client ladder above. Each rung reports aggregate QPS,
        # cross-process dedup (claim/spool wins OR fast-plane handoffs/
        # result-cache hits — the dedup that saved 256/512 queries at
        # one process must not regress to 0 at eight), the fast-plane
        # witnesses (pushed fanout events received, spool-free result
        # handoffs, push-vs-poll wait milliseconds), and the zeros
        # bench_smoke.sh gates on: wrong answers, leaked pin files,
        # leaked member/socket files. The final rung is the chaos rung:
        # kill -9 one frontend mid-serve, survivors degrade fast ->
        # durable bit-identically, the dead frontend's durable pins and
        # fast-plane member file reaped at lease expiry.
        from hyperspace_tpu.testing import fleet_harness as _fleet

        fleet_procs = [
            int(x)
            for x in os.environ.get(
                "HS_BENCH_FLEET", "2,4,8,16,32"
            ).split(",")
            if x.strip()
        ]
        fleet_iters = int(os.environ.get("HS_BENCH_FLEET_ITERS", 8))
        fleet_rows = int(os.environ.get("HS_BENCH_FLEET_ROWS", 50_000))
        fleet_root = os.path.join(tmp, "fleet")
        fleet_lake = _fleet.build_lake(fleet_root, rows=fleet_rows)
        fleet_ladder = []
        for np_ in fleet_procs:
            row = _fleet.run_fleet(
                os.path.join(fleet_root, f"rung{np_}"),
                n_procs=np_,
                iters=fleet_iters,
                reuse_lake=fleet_lake,
                fastpath_phase=True,
            )
            assert row["wrong_answers"] == 0, row
            assert row["leaked_pin_files"] == 0, row
            assert row["leaked_fast_members"] == 0, row
            assert row["fast_frontends"] == np_, row
            # dedup may land on any plane: claim/spool wins, owner-routed
            # handoffs, or fast result-cache hits
            assert (
                row["cross_process_dedup"]
                + row["fast_handoffs"]
                + row["fast_result_hits"]
                > 0
            ), row
            # the deterministic fast-path witnesses (two-phase harness):
            # every live worker received the parent refresh as a PUSH,
            # and served at least one spool-free owner-routed probe
            assert row["fast_push_received"] >= 1, row
            assert row["fast_handoffs"] >= 1, row
            fleet_ladder.append(row)
            fast_avg = row["fast_wait_ms_total"] / max(1, row["fast_waits"])
            poll_avg = row["poll_wait_ms_total"] / max(1, row["poll_waits"])
            log(
                f"fleet {np_} procs (workers on {row['worker_platform']}): "
                f"{row['qps']} qps aggregate, p50 "
                f"{row['p50_ms']}ms p99 {row['p99_ms']}ms, dedup "
                f"{row['cross_process_dedup']}+{row['fast_handoffs']}fast"
                f"/{row['queries']}, push recv {row['fast_push_received']}, "
                f"waits fast {row['fast_waits']}x{fast_avg:.2f}ms vs poll "
                f"{row['poll_waits']}x{poll_avg:.2f}ms, 0 wrong / 0 leaked"
            )
        # ladder shape gates: QPS monotone through the rungs (within
        # run-to-run jitter), and the 2-process rung beating the
        # single-process 64-client rung — the whole point of replacing
        # elections + fsync'd spool round-trips with owner routing
        for prev, cur in zip(fleet_ladder, fleet_ladder[1:]):
            assert cur["qps"] >= prev["qps"] * 0.85, (
                "fleet ladder QPS not monotone",
                prev["processes"],
                prev["qps"],
                cur["processes"],
                cur["qps"],
            )
        serve64 = next(
            (r for r in serve_concurrency if r["clients"] == 64), None
        )
        fleet2 = next(
            (r for r in fleet_ladder if r["processes"] == 2), None
        )
        fleet_vs_single = None
        if serve64 is not None and fleet2 is not None:
            fleet_vs_single = {
                "single_process_64c_qps": serve64["qps"],
                "fleet_2proc_qps": fleet2["qps"],
                "fleet_worker_platform": fleet2["worker_platform"],
                "beats_single": bool(fleet2["qps"] > serve64["qps"]),
            }
            log(
                f"fleet 2-proc {fleet2['qps']} qps vs single-process "
                f"64-client {serve64['qps']} qps -> "
                f"{'BEATS' if fleet_vs_single['beats_single'] else 'TRAILS'}"
            )
            if os.environ.get("HS_BENCH_FLEET_STRICT"):
                # the acceptance bar holds at the real rung; tiny smoke
                # rows measure process-spawn overhead, not the plane
                assert fleet_vs_single["beats_single"], fleet_vs_single
        fleet_chaos = _fleet.run_fleet(
            os.path.join(fleet_root, "chaos"),
            n_procs=max(fleet_procs) if fleet_procs else 2,
            iters=fleet_iters,
            kill_one=True,
            reuse_lake=fleet_lake,
            fastpath_phase=True,
        )
        assert fleet_chaos["wrong_answers"] == 0, fleet_chaos
        assert fleet_chaos["leaked_pin_files"] == 0, fleet_chaos
        assert fleet_chaos["leaked_fast_members"] == 0, fleet_chaos
        # fast -> durable degradation witnessed: survivors probed the
        # dead owner's digests, paid one failed connect each, and fell
        # back to the claim/spool plane bit-identically
        assert fleet_chaos["fast_fallbacks"] >= 1, fleet_chaos
        log(
            f"fleet chaos (kill -9 one of {fleet_chaos['processes']}): "
            f"{fleet_chaos['workers_reporting']} survivors, 0 wrong "
            f"answers, 0 leaked pins/members, dedup "
            f"{fleet_chaos['cross_process_dedup']}, fast->durable "
            f"fallbacks {fleet_chaos['fast_fallbacks']}, p99 "
            f"{fleet_chaos['p99_ms']}ms"
        )

        session.conf.set(C.SERVE_CACHE_ENABLED, False)
        session.clear_serve_cache()  # later stages measure uncached paths;
        # keeping 200+MB resident would only add allocator/page pressure
        session.disable_hyperspace()

        # --- Hybrid Scan join (BASELINE config 4 analogue): append ~3%
        # source rows AFTER indexing; the index must still serve, with the
        # delta union-compensated and re-bucketed at execution time
        n_extra = max(n_items // 32, 1)
        extra = pa.table(
            {
                "l_orderkey": np.random.default_rng(9).integers(
                    0, n_orders, n_extra
                ),
                "l_shipdate": pa.array(
                    np.full(n_extra, np.datetime64("1998-01-01"))
                ),
                "l_quantity": np.full(n_extra, 7, dtype=np.int64),
                "l_extendedprice": np.full(n_extra, 1.0),
            }
        )
        pq.write_table(extra, os.path.join(items_dir, "appended.parquet"))
        session.conf.set(C.INDEX_HYBRID_SCAN_ENABLED, True)
        session.index_manager.clear_cache()
        items2 = session.read.parquet(items_dir)
        session.enable_hyperspace()
        plan = q_join(orders, items2).explain()
        hybrid_served = plan.count("Hyperspace(Type: CI") == 2
        if not hybrid_served:
            log(f"WARNING: hybrid join not index-served:\n{plan}")
        h_rows = q_join(orders, items2).collect().num_rows
        hybrid_idx = timeit(lambda: q_join(orders, items2).collect(), reps)
        hybrid_stages = {
            k: round(v * 1e3, 2)
            for k, v in join_exec.last_serve_breakdown.items()
        }
        log(
            "hybrid serve stages (last uncached run, busy ms): "
            f"{hybrid_stages}"
        )
        # serve-server mode over the SAME hybrid state: the joinside cache
        # keys on (index files + appended files) fingerprints, so repeated
        # queries on a stable appended state skip the per-query union
        # compensation entirely
        session.conf.set(C.SERVE_CACHE_ENABLED, True)
        assert q_join(orders, items2).collect().num_rows == h_rows
        hybrid_cached = timeit(lambda: q_join(orders, items2).collect(), reps)

        # cached-DELTA row: evicting everything but the fingerprint-keyed
        # ("delta", …) entry before each trial isolates the steady state
        # of a serve process fielding varied projections over a
        # slowly-appending table — the index side re-prepares, but the
        # appended compensation (read + re-bucket) is already done and
        # the query pays only the per-bucket merge
        hcache = session.serve_cache

        def run_cached_delta():
            for kind in ("joinside", "bucketed", "scan"):
                hcache.evict_kind(kind)
            q_join(orders, items2).collect()

        run_cached_delta()  # warm the delta entry itself
        hybrid_cached_delta = timeit(run_cached_delta, reps)
        log(
            "hybrid cached-delta (only the prepared delta warm) p50: "
            f"{hybrid_cached_delta['p50'] * 1e3:.1f}ms"
        )
        session.conf.set(C.SERVE_CACHE_ENABLED, False)
        session.clear_serve_cache()
        session.disable_hyperspace()
        assert q_join(orders, items2).collect().num_rows == h_rows
        hybrid_raw = timeit(lambda: q_join(orders, items2).collect(), reps)
        log(
            f"hybrid-scan join p50: indexed {hybrid_idx['p50'] * 1e3:.1f}ms vs "
            f"unindexed {hybrid_raw['p50'] * 1e3:.1f}ms "
            f"({hybrid_raw['p50'] / hybrid_idx['p50']:.2f}x); "
            f"serve-server {hybrid_cached['p50'] * 1e3:.1f}ms "
            f"({hybrid_raw['p50'] / hybrid_cached['p50']:.2f}x)"
        )
        session.conf.set(C.INDEX_HYBRID_SCAN_ENABLED, False)

        # --- Delta incremental refresh (BASELINE config 5): index a Delta
        # table with lineage, commit appends, time the incremental refresh
        delta_dir = os.path.join(tmp, "delta_tbl")
        dlog = os.path.join(delta_dir, "_delta_log")
        os.makedirs(dlog)
        rngd = np.random.default_rng(13)
        n_delta = max(n_items // 4, 1)

        def delta_file(name, rows):
            t = pa.table(
                {
                    "k": rngd.integers(0, n_orders, rows),
                    "q": rngd.integers(1, 51, rows),
                }
            )
            fp = os.path.join(delta_dir, name)
            pq.write_table(t, fp)
            st = os.stat(fp)
            return {
                "path": name,
                "size": st.st_size,
                "modificationTime": int(st.st_mtime * 1000),
                "dataChange": True,
            }

        schema_str = json.dumps(
            {
                "type": "struct",
                "fields": [
                    {"name": "k", "type": "long", "nullable": True, "metadata": {}},
                    {"name": "q", "type": "long", "nullable": True, "metadata": {}},
                ],
            }
        )
        with open(os.path.join(dlog, f"{0:020d}.json"), "w") as f:
            f.write(json.dumps({"protocol": {"minReaderVersion": 1, "minWriterVersion": 2}}) + "\n")
            f.write(
                json.dumps(
                    {
                        "metaData": {
                            "id": "bench",
                            "schemaString": schema_str,
                            "partitionColumns": [],
                            "format": {"provider": "parquet"},
                        }
                    }
                )
                + "\n"
            )
            f.write(json.dumps({"add": delta_file("part-0.parquet", n_delta)}) + "\n")


        session.conf.set(C.INDEX_LINEAGE_ENABLED, True)
        ddf = session.read.delta(delta_dir)
        hs.create_index(ddf, CoveringIndexConfig("delta_idx", ["k"], ["q"]))
        n_append = max(n_delta // 8, 1)
        with open(os.path.join(dlog, f"{1:020d}.json"), "w") as f:
            f.write(
                json.dumps({"add": delta_file("part-1.parquet", n_append)}) + "\n"
            )
        session.index_manager.clear_cache()
        t0 = time.perf_counter()
        hs.refresh_index("delta_idx", C.REFRESH_MODE_INCREMENTAL)
        delta_refresh = time.perf_counter() - t0
        log(
            f"delta incremental refresh of {n_append:,} appended rows: "
            f"{delta_refresh:.2f}s ({n_append / delta_refresh:,.0f} rows/s)"
        )

        # --- z-order range query (the index kind had no perf row through
        # round 5 — VERDICT weak #5). Two-dimensional range predicate; the
        # z-layout clusters both dims so row-group min/max stats prune to
        # a narrow band of each bucket file.
        from hyperspace_tpu.indexes.dataskipping import DataSkippingIndexConfig
        from hyperspace_tpu.indexes.sketches import MinMaxSketch
        from hyperspace_tpu.indexes.zorder import ZOrderCoveringIndexConfig

        session.conf.set(C.INDEX_LINEAGE_ENABLED, False)  # delta section left it on
        session.index_manager.clear_cache()
        items3 = session.read.parquet(items_dir)
        hs.create_index(
            items3,
            ZOrderCoveringIndexConfig(
                "z_idx", ["l_shipdate", "l_quantity"], ["l_orderkey"]
            ),
        )
        zlo = np.datetime64("1995-06-01")
        zhi = np.datetime64("1995-06-30")

        def q_zrange(df):
            return df.filter(
                (df["l_shipdate"] >= zlo)
                & (df["l_shipdate"] <= zhi)
                & (df["l_quantity"] <= 5)
            ).select("l_shipdate", "l_quantity", "l_orderkey")

        session.enable_hyperspace()
        plan = q_zrange(items3).explain()
        if "Hyperspace(Type: ZOCI" not in plan:
            log(f"WARNING: z-order range not index-served:\n{plan}")
        z_rows = q_zrange(items3).collect().num_rows
        # INTERLEAVED A/B (round-7 protocol): rangeprune on vs off
        # alternate within one process, so page-cache/allocator drift
        # hits both legs equally. The "off" leg is the pre-range-plane
        # serve path (full index read + interpreter mask), the "on" leg
        # is zone-map file/row-group pruning + the fused residual mask.
        t_on, t_off = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            q_zrange(items3).collect()
            t_on.append(time.perf_counter() - t0)
            session.conf.set(C.SERVE_RANGEPRUNE_ENABLED, False)
            t0 = time.perf_counter()
            q_zrange(items3).collect()
            t_off.append(time.perf_counter() - t0)
            session.conf.unset(C.SERVE_RANGEPRUNE_ENABLED)

        def _stats(ts):
            q1, med, q3 = np.percentile(ts, [25, 50, 75])
            return {"p50": float(med), "iqr": float(q3 - q1), "n": len(ts)}

        zrange_idx = _stats(t_on)
        zrange_off = _stats(t_off)
        # pruning telemetry of the last rangeprune-on run: refresh it
        # (the off leg overwrote nothing — pruning was disabled — but be
        # explicit and re-run one pruned serve before reading)
        from hyperspace_tpu.indexes import zonemaps as _zonemaps

        q_zrange(items3).collect()
        zprune = dict(_zonemaps.last_prune_stats)
        zmaps_seen = (
            zprune.get("zonemap_files_sidecar", 0)
            + zprune.get("zonemap_files_footer", 0)
        )
        zprune["zonemap_hit_rate"] = round(
            zprune.get("zonemap_files_sidecar", 0) / zmaps_seen, 3
        ) if zmaps_seen else 0.0
        session.disable_hyperspace()
        assert q_zrange(items3).collect().num_rows == z_rows
        zrange_raw = timeit(lambda: q_zrange(items3).collect(), reps)
        log(
            f"z-order range p50: indexed {zrange_idx['p50'] * 1e3:.1f}ms "
            f"(rangeprune off {zrange_off['p50'] * 1e3:.1f}ms) vs "
            f"unindexed {zrange_raw['p50'] * 1e3:.1f}ms "
            f"({zrange_raw['p50'] / zrange_idx['p50']:.2f}x, {z_rows:,} rows); "
            f"prune: {zprune}"
        )
        # the z-index also covers l_shipdate and would win the scoring
        # race below; the data-skipping row must measure DS serving
        hs.delete_index("z_idx")
        hs.vacuum_index("z_idx")

        # --- data-skipping file pruning (min/max sketch; also had no
        # perf row). Files are laid out in ship-date order, so a narrow
        # date range prunes most source files from the scan itself.
        session.index_manager.clear_cache()
        items4 = session.read.parquet(items_dir)
        hs.create_index(
            items4, DataSkippingIndexConfig("ds_idx", MinMaxSketch("l_shipdate"))
        )
        session.enable_hyperspace()
        plan = q_zrange(items4).explain()
        if "Hyperspace(Type: DS" not in plan:
            log(f"WARNING: data-skipping not serving:\n{plan}")
        ds_leaves = session.optimize(
            q_zrange(items4).logical_plan
        ).collect_leaves()
        ds_files = len(ds_leaves[0].relation.files)
        ds_total = len(items4.logical_plan.collect_leaves()[0].relation.files)
        ds_rows = q_zrange(items4).collect().num_rows
        assert ds_rows == z_rows, (ds_rows, z_rows)
        ds_idx_t = timeit(lambda: q_zrange(items4).collect(), reps)
        session.disable_hyperspace()
        ds_raw_t = timeit(lambda: q_zrange(items4).collect(), reps)
        log(
            f"data-skipping prune p50: indexed {ds_idx_t['p50'] * 1e3:.1f}ms "
            f"({ds_files}/{ds_total} files scanned) vs unindexed "
            f"{ds_raw_t['p50'] * 1e3:.1f}ms "
            f"({ds_raw_t['p50'] / ds_idx_t['p50']:.2f}x)"
        )
        hs.delete_index("ds_idx")
        hs.vacuum_index("ds_idx")

        # --- build-throughput ladder: the scale story the BASELINE table
        # tracks (4M/16M/64M). Each rung is an independent dataset +
        # fresh index build; per-stage seconds name the bottleneck. The
        # partition-first sort keeps per-bucket working sets resident, so
        # the 64M rung no longer collapses on permutation gathers.
        ladder_env = os.environ.get(
            "HS_BENCH_LADDER", "4000000,16000000,64000000"
        )
        ladder = []
        for rung_rows in [int(x) for x in ladder_env.split(",") if x.strip()]:
            rung_dir = os.path.join(tmp, f"ladder_{rung_rows}")
            try:
                ldir, _odir = gen_data(
                    rung_dir, rung_rows, max(rung_rows // 8, 1)
                )
                lsession = HyperspaceSession()
                lsession.conf.set(
                    C.INDEX_SYSTEM_PATH, os.path.join(rung_dir, "indexes")
                )
                lsession.conf.set(C.INDEX_NUM_BUCKETS, num_buckets)
                lhs = Hyperspace(lsession)
                ldf = lsession.read.parquet(ldir)
                cfg = CoveringIndexConfig(
                    "ladder_idx",
                    ["l_orderkey"],
                    ["l_shipdate", "l_quantity", "l_extendedprice"],
                )
                lhs.create_index(ldf, cfg)  # warm caches/compiles
                lhs.delete_index("ladder_idx")
                lhs.vacuum_index("ladder_idx")
                lsession.index_manager.clear_cache()
                t0 = time.perf_counter()
                lhs.create_index(ldf, cfg)
                rung_warm = time.perf_counter() - t0
                rung_stages = {
                    k: round(v, 3) for k, v in last_build_breakdown.items()
                }
                ladder.append(
                    {
                        "rows": rung_rows,
                        "build_warm_s": round(rung_warm, 3),
                        "build_rows_per_sec": round(rung_rows / rung_warm),
                        "build_stage_seconds": rung_stages,
                        "rss_high_water_bytes": rss_hwm(),
                    }
                )
                log(
                    f"ladder {rung_rows:,} rows: {rung_warm:.2f}s warm "
                    f"({rung_rows / rung_warm:,.0f} rows/s); stages: "
                    f"{rung_stages}"
                )
            except MemoryError:
                log(f"ladder {rung_rows:,} rows: skipped (MemoryError)")
            finally:
                shutil.rmtree(rung_dir, ignore_errors=True)

        # --- mesh build/serve ladder: the scale-out story (ROADMAP item
        # 2). Per (rows, devices) rung: a warm covering build — on >1
        # devices the shard_map all-to-all shuffle plus the sharded
        # sort+write tail — and
        # the co-bucketed indexed join served with per-shard prepare +
        # merge. Stage seconds are busy time (sort/write sum across
        # shard tails; the excess over tail_wall is the sharding win);
        # shuffle telemetry records exchange cap + per-peer skew.
        from hyperspace_tpu.indexes.covering_build import (
            last_build_telemetry,
        )

        mesh_sizes_env = os.environ.get("HS_BENCH_MESH", "1,2,8")
        mesh_rows_env = os.environ.get(
            "HS_BENCH_MESH_ROWS", "4000000,64000000"
        )
        avail = len(jax.devices())
        mesh_sizes = [
            d
            for d in (
                int(x) for x in mesh_sizes_env.split(",") if x.strip()
            )
            if 1 <= d <= avail
        ]
        mesh_ladder = []
        for rung_rows in [
            int(x) for x in mesh_rows_env.split(",") if x.strip()
        ]:
            rung_dir = os.path.join(tmp, f"mesh_{rung_rows}")
            try:
                mldir, modir = gen_data(
                    rung_dir, rung_rows, max(rung_rows // 8, 1)
                )
                for D in mesh_sizes:
                    msession = HyperspaceSession(devices=jax.devices()[:D])
                    msession.conf.set(
                        C.INDEX_SYSTEM_PATH,
                        os.path.join(rung_dir, f"indexes_d{D}"),
                    )
                    msession.conf.set(C.INDEX_NUM_BUCKETS, num_buckets)
                    mhs = Hyperspace(msession)
                    mdf = msession.read.parquet(mldir)
                    mcfg = CoveringIndexConfig(
                        "mesh_l_idx",
                        ["l_orderkey"],
                        ["l_shipdate", "l_quantity", "l_extendedprice"],
                    )
                    mhs.create_index(mdf, mcfg)  # warm caches/compiles
                    mhs.delete_index("mesh_l_idx")
                    mhs.vacuum_index("mesh_l_idx")
                    msession.index_manager.clear_cache()
                    t0 = time.perf_counter()
                    mhs.create_index(mdf, mcfg)
                    m_warm = time.perf_counter() - t0
                    m_stages = {
                        k: round(v, 3)
                        for k, v in last_build_breakdown.items()
                    }
                    m_shuffle = {
                        k: v for k, v in last_build_telemetry.items()
                    }
                    modf = msession.read.parquet(modir)
                    mhs.create_index(
                        modf,
                        CoveringIndexConfig(
                            "mesh_o_idx", ["o_orderkey"], ["o_custkey"]
                        ),
                    )
                    msession.enable_hyperspace()

                    def q_mjoin(o=modf, i=mdf):
                        return o.join(
                            i, on=o["o_orderkey"] == i["l_orderkey"]
                        ).select("o_orderkey", "o_custkey", "l_quantity")

                    mplan = q_mjoin().explain()
                    if mplan.count("Hyperspace(Type: CI") != 2:
                        log(
                            f"WARNING: mesh join (D={D}) not index-served:"
                            f"\n{mplan}"
                        )
                    q_mjoin().collect()  # warmup
                    m_join = timeit(lambda: q_mjoin().collect(), reps)
                    m_join_stages = {
                        k: round(v * 1e3, 2)
                        for k, v in join_exec.last_serve_breakdown.items()
                    }
                    mesh_ladder.append(
                        {
                            "rows": rung_rows,
                            "devices": D,
                            "build_warm_s": round(m_warm, 3),
                            "build_rows_per_sec": round(rung_rows / m_warm),
                            "build_stage_seconds": m_stages,
                            "shuffle": m_shuffle,
                            "join_indexed_p50_ms": round(
                                m_join["p50"] * 1e3, 2
                            ),
                            "join_indexed_iqr_ms": round(
                                m_join["iqr"] * 1e3, 2
                            ),
                            "join_serve_stage_ms": m_join_stages,
                            "rss_high_water_bytes": rss_hwm(),
                        }
                    )
                    log(
                        f"mesh ladder {rung_rows:,} rows x {D} devices: "
                        f"build {m_warm:.2f}s "
                        f"({rung_rows / m_warm:,.0f} rows/s), join "
                        f"{m_join['p50'] * 1e3:.1f}ms; stages: {m_stages}"
                        f"; shuffle: {m_shuffle}"
                    )
            except MemoryError:
                log(f"mesh ladder {rung_rows:,} rows: skipped (MemoryError)")
            finally:
                shutil.rmtree(rung_dir, ignore_errors=True)

        # --- out-of-core streaming ladder (docs/out-of-core.md): the
        # join served in budget-packed waves with the spill tier and
        # mmap reads on. The 256M rung is the tentpole claim: it must
        # COMPLETE with peak residency O(wave), where the materializing
        # path holds both decoded sides at once. The stream-off baseline
        # runs only up to HS_BENCH_STREAM_BASELINE_MAX rows (default
        # 64M) — above that the materializing peak is exactly what the
        # flag exists to avoid. 1B rows is opt-in:
        # HS_BENCH_STREAM_LADDER=64000000,256000000,1000000000.
        from hyperspace_tpu.execution import executor as ex_mod

        stream_env = os.environ.get(
            "HS_BENCH_STREAM_LADDER", "64000000,256000000"
        )
        baseline_max = int(
            os.environ.get("HS_BENCH_STREAM_BASELINE_MAX", 64_000_000)
        )
        stream_ladder = []
        for rung_rows in [
            int(x) for x in stream_env.split(",") if x.strip()
        ]:
            rung_dir = os.path.join(tmp, f"stream_{rung_rows}")
            try:
                sldir, sodir = gen_data(
                    rung_dir,
                    rung_rows,
                    max(rung_rows // 8, 1),
                    n_files=max(8, rung_rows // 8_000_000),
                )
                # buckets scale with rows (~4M rows/bucket) so a wave
                # can pack several buckets under stream.maxBytes — a
                # bucket bigger than the whole budget degrades to
                # one-bucket waves and the peak grows to O(bucket)
                s_buckets = max(num_buckets, rung_rows // 4_000_000)
                ssession = HyperspaceSession()
                ssession.conf.set(
                    C.INDEX_SYSTEM_PATH, os.path.join(rung_dir, "indexes")
                )
                ssession.conf.set(C.INDEX_NUM_BUCKETS, s_buckets)
                shs = Hyperspace(ssession)
                sldf = ssession.read.parquet(sldir)
                sodf = ssession.read.parquet(sodir)
                shs.create_index(
                    sldf,
                    CoveringIndexConfig(
                        "stream_l_idx", ["l_orderkey"], ["l_quantity"]
                    ),
                )
                shs.create_index(
                    sodf,
                    CoveringIndexConfig(
                        "stream_o_idx", ["o_orderkey"], ["o_custkey"]
                    ),
                )
                ssession.enable_hyperspace()

                def q_sjoin(o=sodf, i=sldf):
                    return o.join(
                        i, on=o["o_orderkey"] == i["l_orderkey"]
                    ).select("o_orderkey", "o_custkey", "l_quantity")

                splan = q_sjoin().explain()
                if splan.count("Hyperspace(Type: CI") != 2:
                    log(
                        f"WARNING: stream rung join not index-served:"
                        f"\n{splan}"
                    )
                base_row = None
                if rung_rows <= baseline_max:
                    t0 = time.perf_counter()
                    base_rows = q_sjoin().collect().num_rows
                    base_wall = time.perf_counter() - t0
                    base_row = {
                        "wall_s": round(base_wall, 3),
                        "rows_out": base_rows,
                        "serve_stage_ms": {
                            k: round(v * 1e3, 2)
                            for k, v in (
                                join_exec.last_serve_breakdown.items()
                            )
                        },
                        "rss_high_water_bytes": rss_hwm(),
                    }
                # spill round-trip at rung scale (docs/out-of-core.md):
                # measure one side's decoded filter state, then size the
                # cache to hold exactly that — serving the other side
                # demotes it to the spill tier and the re-serve restores
                # it as a zero-copy mmap view
                ssession.conf.set(C.SERVE_CACHE_ENABLED, True)
                ssession.conf.set(C.SERVE_SPILL_MAX_BYTES, 2 << 30)
                ssession.conf.set(C.IO_MMAP_ENABLED, True)
                k_l = int(max(rung_rows // 8, 1) // 3)

                def q_sfilter_l(i=sldf, k=k_l):
                    return i.filter(i["l_orderkey"] == k).select(
                        "l_orderkey", "l_quantity"
                    )

                def q_sfilter_o(o=sodf, k=k_l):
                    return o.filter(o["o_orderkey"] == k).select(
                        "o_orderkey", "o_custkey"
                    )

                l_rows = q_sfilter_l().collect().num_rows
                resident = ssession.serve_cache.stats()["resident_bytes"]
                if resident > 0:
                    # rebuilds the cache at the tight budget
                    ssession.conf.set(
                        C.SERVE_CACHE_MAX_BYTES, resident + 64
                    )
                    assert q_sfilter_l().collect().num_rows == l_rows
                    q_sfilter_o().collect()  # displaces l -> demote
                    assert q_sfilter_l().collect().num_rows == l_rows
                ssession.conf.set(C.SERVE_STREAM_ENABLED, True)
                # HS_BENCH_STREAM_MAX_BYTES: shrink the wave budget so
                # tiny smoke rows still pack >1 wave (0 = conf default)
                wave_budget = int(
                    os.environ.get("HS_BENCH_STREAM_MAX_BYTES", 0)
                )
                if wave_budget > 0:
                    ssession.conf.set(
                        C.SERVE_STREAM_MAX_BYTES, wave_budget
                    )
                ex_mod.stream_stats_reset()
                t0 = time.perf_counter()
                s_rows = q_sjoin().collect().num_rows
                s_wall = time.perf_counter() - t0
                s_stats = dict(ex_mod.last_stream_stats)
                cache_stats = ssession.serve_cache.stats()
                row = {
                    "rows": rung_rows,
                    "num_buckets": s_buckets,
                    "stream_wall_s": round(s_wall, 3),
                    "rows_out": s_rows,
                    "stream_waves": s_stats.get("stream_waves", 0),
                    "stream_buckets": s_stats.get("stream_buckets", 0),
                    "stream_stage_ms": {
                        k: round(v * 1e3, 2)
                        for k, v in join_exec.last_serve_breakdown.items()
                    },
                    "spill_demotes": cache_stats["spill_demotes"],
                    "spill_restores": cache_stats["spill_restores"],
                    "spill_bytes": cache_stats["spill_bytes"],
                    "rss_high_water_bytes": rss_hwm(),
                }
                if base_row is not None:
                    # cheap at-scale identity proxy; the byte-level
                    # differential is tests/test_stream_serve.py
                    assert s_rows == base_row["rows_out"], (
                        s_rows,
                        base_row["rows_out"],
                    )
                    row["materializing_baseline"] = base_row
                    row["stream_speedup"] = round(
                        base_row["wall_s"] / s_wall, 3
                    )
                stream_ladder.append(row)
                log(
                    f"stream ladder {rung_rows:,} rows: "
                    f"{s_wall:.2f}s in {row['stream_waves']} waves "
                    f"({row['stream_buckets']} buckets), "
                    f"spill {row['spill_demotes']}/{row['spill_restores']} "
                    f"demote/restore, rss hwm "
                    f"{row['rss_high_water_bytes'] / 1e9:.2f}GB"
                )
            except MemoryError:
                log(
                    f"stream ladder {rung_rows:,} rows: skipped "
                    f"(MemoryError)"
                )
            finally:
                shutil.rmtree(rung_dir, ignore_errors=True)

        # headline: geometric mean of the three UNCACHED serve-path
        # speedups — stable under one path's unindexed baseline improving,
        # and directly comparable with rounds 1-4. The serve-server
        # (cached) numbers are reported separately, clearly labeled.
        def ms(d):
            return round(d["p50"] * 1e3, 2)

        def iqr_ms(d):
            return round(d["iqr"] * 1e3, 2)

        speedups = [
            filter_raw["p50"] / filter_idx["p50"],
            join_raw["p50"] / join_idx["p50"],
            hybrid_raw["p50"] / hybrid_idx["p50"],
        ]
        geomean = float(np.prod(speedups) ** (1.0 / len(speedups)))

        # resident-set telemetry: always the process RSS high-water;
        # per-site peak bytes too when the residency witness is armed
        # (the artifact is also written here, for hslint --witness)
        residency: dict = {"rss_high_water_bytes": rss_hwm()}
        if residency_art:
            from hyperspace_tpu.testing import residency_witness

            wdoc = residency_witness.dump(residency_art)
            residency["witness_artifact"] = residency_art
            residency["witnessed_sites"] = len(wdoc["sites"])
            residency["witness_peak_bytes_by_site"] = {
                site: rec["peak_bytes"]
                for site, rec in sorted(wdoc["sites"].items())
            }
        print(
            json.dumps(
                {
                    "metric": "indexed_query_speedup_geomean",
                    "value": round(geomean, 3),
                    "unit": "x (geomean of filter/join/hybrid p50 speedups vs unindexed, same chip; uncached serve)",
                    "vs_baseline": round(geomean, 3),
                    "platform": platform,
                    "rows": n_items,
                    "num_buckets": num_buckets,
                    "trials_per_stage": reps,
                    "build_rows_per_sec": round(n_items / build_warm),
                    "build_cold_s": round(build_cold, 3),
                    "build_warm_s": round(build_warm, 3),
                    "build_stage_seconds": breakdown,
                    "filter_indexed_p50_ms": ms(filter_idx),
                    "filter_indexed_iqr_ms": iqr_ms(filter_idx),
                    "filter_unindexed_p50_ms": ms(filter_raw),
                    "filter_unindexed_iqr_ms": iqr_ms(filter_raw),
                    "filter_speedup": round(
                        filter_raw["p50"] / filter_idx["p50"], 3
                    ),
                    "filter_cached_p50_ms": ms(filter_cached),
                    "filter_cached_iqr_ms": iqr_ms(filter_cached),
                    "filter_cached_speedup": round(
                        filter_raw["p50"] / filter_cached["p50"], 3
                    ),
                    "filter_agg": {
                        "fused_p50_ms": ms(fagg_on),
                        "fused_iqr_ms": iqr_ms(fagg_on),
                        "interp_p50_ms": ms(fagg_off),
                        "interp_iqr_ms": iqr_ms(fagg_off),
                        "fused_speedup": round(
                            fagg_off["p50"] / fagg_on["p50"], 3
                        ),
                        "fused_ran": fagg_stats.get("mode") == "agg",
                        "stats": fagg_stats,
                    },
                    "grouped_agg": {
                        "fused_p50_ms": ms(gagg_on),
                        "fused_iqr_ms": iqr_ms(gagg_on),
                        "interp_p50_ms": ms(gagg_off),
                        "interp_iqr_ms": iqr_ms(gagg_off),
                        "fused_speedup": round(
                            gagg_off["p50"] / gagg_on["p50"], 3
                        ),
                        "fused_ran": gagg_stats.get("mode") == "agg",
                        "stats": gagg_stats,
                    },
                    "agg_metadata": {
                        "metadata_p50_ms": ms(meta_ab[0]),
                        "metadata_iqr_ms": iqr_ms(meta_ab[0]),
                        "fused_p50_ms": ms(meta_ab[1]),
                        "fused_iqr_ms": iqr_ms(meta_ab[1]),
                        "metadata_speedup": round(
                            meta_ab[1]["p50"] / meta_ab[0]["p50"], 3
                        ),
                        "metadata_ran": meta_stats.get("mode")
                        == "agg_metadata",
                        "stats": meta_stats,
                    },
                    "agg_approx": {
                        "approx_p50_ms": ms(t_apx),
                        "approx_iqr_ms": iqr_ms(t_apx),
                        "exact_p50_ms": ms(t_exact),
                        "exact_iqr_ms": iqr_ms(t_exact),
                        "count_rel_err": round(n_err, 6),
                        "count_bound_held": n_in_ci,
                        "sum_bound_held": s_in_ci,
                        "stats": {
                            k: v
                            for k, v in apx_stats.items()
                            if k != "wall_s"
                        },
                    },
                    "join_indexed_p50_ms": ms(join_idx),
                    "join_indexed_iqr_ms": iqr_ms(join_idx),
                    "join_unindexed_p50_ms": ms(join_raw),
                    "join_unindexed_iqr_ms": iqr_ms(join_raw),
                    "join_speedup": round(join_raw["p50"] / join_idx["p50"], 3),
                    "join_cached_p50_ms": ms(join_cached),
                    "join_cached_iqr_ms": iqr_ms(join_cached),
                    "join_cached_speedup": round(
                        join_raw["p50"] / join_cached["p50"], 3
                    ),
                    "serve_concurrency": serve_concurrency,
                    "serve_obs": serve_obs,
                    "advisor": advisor_rung,
                    "fleet_ladder": fleet_ladder,
                    "fleet_vs_single": fleet_vs_single,
                    "fleet_chaos": fleet_chaos,
                    "fleet_vs_64client_qps": round(
                        fleet_ladder[-1]["qps"]
                        / max(
                            next(
                                (
                                    r["qps"]
                                    for r in serve_concurrency
                                    if r["clients"] == 64
                                ),
                                1.0,
                            ),
                            1e-9,
                        ),
                        3,
                    )
                    if fleet_ladder
                    else None,
                    "chaos": chaos_summary,
                    "fault_injection": {
                        "fired": fault_fired,
                        "frontend_retries": fault_stats["retries"],
                        "frontend_degraded": fault_stats["degraded"],
                        "frontend_degraded_pins": fault_stats[
                            "degraded_pins"
                        ],
                        "frontend_failed": fault_stats["failed"],
                    },
                    "join_rows_out": j_rows,
                    "join_serve_stage_ms": join_stages,
                    "hybrid_join_indexed_p50_ms": ms(hybrid_idx),
                    "hybrid_join_indexed_iqr_ms": iqr_ms(hybrid_idx),
                    "hybrid_join_unindexed_p50_ms": ms(hybrid_raw),
                    "hybrid_join_unindexed_iqr_ms": iqr_ms(hybrid_raw),
                    "hybrid_join_speedup": round(
                        hybrid_raw["p50"] / hybrid_idx["p50"], 3
                    ),
                    "hybrid_join_cached_p50_ms": ms(hybrid_cached),
                    "hybrid_join_cached_iqr_ms": iqr_ms(hybrid_cached),
                    "hybrid_join_cached_speedup": round(
                        hybrid_raw["p50"] / hybrid_cached["p50"], 3
                    ),
                    "hybrid_join_cached_delta_p50_ms": ms(hybrid_cached_delta),
                    "hybrid_join_cached_delta_iqr_ms": iqr_ms(
                        hybrid_cached_delta
                    ),
                    "hybrid_serve_stage_ms": hybrid_stages,
                    "hybrid_index_served": hybrid_served,
                    "delta_incr_refresh_s": round(delta_refresh, 3),
                    "delta_refresh_rows_per_sec": round(n_append / delta_refresh),
                    "zorder_range_indexed_p50_ms": ms(zrange_idx),
                    "zorder_range_indexed_iqr_ms": iqr_ms(zrange_idx),
                    "zorder_range_unindexed_p50_ms": ms(zrange_raw),
                    "zorder_range_unindexed_iqr_ms": iqr_ms(zrange_raw),
                    "zorder_range_speedup": round(
                        zrange_raw["p50"] / zrange_idx["p50"], 3
                    ),
                    "zorder_range_pruneoff_p50_ms": ms(zrange_off),
                    "zorder_range_pruneoff_iqr_ms": iqr_ms(zrange_off),
                    "zorder_prune": zprune,
                    "zorder_range_rows_out": z_rows,
                    "ds_prune_indexed_p50_ms": ms(ds_idx_t),
                    "ds_prune_indexed_iqr_ms": iqr_ms(ds_idx_t),
                    "ds_prune_unindexed_p50_ms": ms(ds_raw_t),
                    "ds_prune_unindexed_iqr_ms": iqr_ms(ds_raw_t),
                    "ds_prune_speedup": round(
                        ds_raw_t["p50"] / ds_idx_t["p50"], 3
                    ),
                    "ds_prune_files_scanned": ds_files,
                    "ds_prune_files_total": ds_total,
                    "build_ladder": ladder,
                    "mesh_ladder": mesh_ladder,
                    "stream_ladder": stream_ladder,
                    "residency": residency,
                }
            )
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
