"""chip_smoke.py — the quickest proof that hyperspace_tpu runs on the chip.

One process drives the system's main path once, through the entry points
a user calls (``HyperspaceSession``, ``Hyperspace``, ``DataFrame``,
``session.serve_frontend``), under default configuration (200 buckets), on
the bench's ``lineitem``/``orders`` shape made from ``--seed``:

  (a) cold then second covering build of ``lineitem`` on ``l_orderkey``,
      and of ``orders``;
  (b) point filter, range filter->aggregate, grouped aggregate and
      ``orders JOIN lineitem`` through the serve frontend;
  (c) append ~3% new files -> the same join under Hybrid Scan ->
      incremental refresh -> the join again;
  (d) a z-order index with a range query, a data-skipping index with a
      Bloom sketch with a point query (both dispatch to the device under
      default configuration);
  (e) every other jitted program the main path can dispatch, compiled on
      the device over the same data and equal to its host twin.

Every query must be index-served and equal both to the
``disable_hyperspace()`` run and to a plain numpy answer computed here.
Any failed check raises: there is no phase that fails and lets the run
continue.

It refuses to run without a TPU (exit 2, nothing on stdout).
``--cpu-rehearsal`` runs the same phases on the CPU at a tiny ``--rows``
to debug the script; a rehearsal says it is one and never prints the
pass line. Progress goes to stderr. Stdout carries two lines, each one
JSON object: first the report (rows, thresholds, compiles, per-phase
smoke timings and device programs — timings of one run, not benchmark
metrics), then, last, the verdict and nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

    python chip_smoke.py                  # on a machine with a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal --rows 6000
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

# SF10's lineitem row count, the largest rung on record, is the size the
# smoke is meant for. The default is cut to a quarter of it, rows only,
# and every run prints the cut: on one chip with a cold compile cache
# 16M rows take about a third of the contract's 1200 s, most of it
# compiles and unindexed baselines that grow with the rows (PERF.md,
# PR 21 has the timings).
FULL_ROWS = 64_000_000
DEFAULT_ROWS = 16_000_000
MIN_ROWS = 2_000


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def timed(info: dict, key: str):
    """Smoke timing of the block, into ``info[key]`` (seconds)."""
    t0 = time.time()
    yield
    info[key] = round(time.time() - t0, 2)


# ---------------------------------------------------------------------------
# Plain reference side: numpy over the generated data, no engine code
# ---------------------------------------------------------------------------

_MIX = np.uint64(0x9E3779B97F4A7C15)


def _bits(values: np.ndarray) -> np.ndarray:
    """uint64 bit pattern of a numeric/date column (exact, no rounding)."""
    v = np.asarray(values)
    if v.dtype.kind == "f":
        return np.ascontiguousarray(v, dtype=np.float64).view(np.uint64)
    if v.dtype.kind in "mM":
        v = v.astype("datetime64[D]").astype(np.int64)
    return np.ascontiguousarray(v, dtype=np.int64).view(np.uint64)


def digest(cols: dict) -> list:
    """Order-independent digest of a table given as ``{name: array}``:
    [row count, sum and xor of a per-row 64-bit mix over all columns].
    Equal multisets of rows give equal digests; a joined or filtered row
    carries source values unchanged, so bit patterns compare exactly."""
    names = sorted(cols)
    n = len(cols[names[0]]) if names else 0
    h = np.zeros(n, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for i, name in enumerate(names):
            h = (h ^ (_bits(cols[name]) + np.uint64(i + 1))) * _MIX
            h ^= h >> np.uint64(29)
        total = int(h.sum(dtype=np.uint64)) if n else 0
    xor = int(np.bitwise_xor.reduce(h)) if n else 0
    return [n, f"{total:016x}", f"{xor:016x}"]


def table_cols(table) -> dict:
    import pyarrow as pa

    out = {}
    for name in table.column_names:
        col = table.column(name)
        if pa.types.is_date32(col.type):
            col = col.cast(pa.int32())
        out[name] = col.combine_chunks().to_numpy(zero_copy_only=False)
    return out


def same_rows(got, want_cols: dict, what: str) -> list:
    d_got, d_want = digest(table_cols(got)), digest(want_cols)
    check(d_got == d_want, f"{what}: rows differ, got {d_got} want {d_want}")
    return d_got


def same_aggregate(got, want: dict, keys: list, what: str) -> None:
    """Small aggregate results: sorted by ``keys``, integers exact, float
    sums within 1e-9 relative (accumulation order differs by path)."""
    cols = table_cols(got)
    check(sorted(cols) == sorted(want), f"{what}: columns {sorted(cols)}")
    order_g = np.lexsort([cols[k] for k in reversed(keys)]) if keys else slice(None)
    order_w = np.lexsort([want[k] for k in reversed(keys)]) if keys else slice(None)
    for name in want:
        g = np.asarray(cols[name])[order_g]
        w = np.asarray(want[name])[order_w]
        check(len(g) == len(w), f"{what}.{name}: {len(g)} rows, want {len(w)}")
        if g.dtype.kind == "f" or w.dtype.kind == "f":
            ok = np.allclose(g, w, rtol=1e-9, atol=0.0)
        else:
            ok = np.array_equal(g, w)
        check(ok, f"{what}.{name}: got {g[:8]} want {w[:8]}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=DEFAULT_ROWS,
                    help="lineitem rows (orders = rows/8)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="debug the script on the CPU; never a pass")
    args = ap.parse_args(argv)
    if args.rows < MIN_ROWS:
        ap.error(f"--rows must be at least {MIN_ROWS}")
    t_start = time.time()

    # -- first act: which device? ------------------------------------------
    import jax

    dev0 = jax.devices()[0]
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(jax.devices()),
    }
    rehearsal = device["platform"] != "tpu"
    if rehearsal and not args.cpu_rehearsal:
        log(f"no TPU: jax reports {device}; refusing to run on it "
            "(--cpu-rehearsal debugs the script on the CPU)")
        return 2
    if rehearsal:
        log(f"CPU REHEARSAL on {device}: not a chip run, never a pass")

    # compile bookkeeping: every backend compile request and every
    # persistent-cache hit, by jax's own monitoring events
    compiles = {"requests": 0, "cache_hits": 0, "seconds": 0.0}

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["requests"] += 1
            compiles["seconds"] += duration

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            compiles["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    import pyarrow as pa
    import pyarrow.parquet as pq

    import bench
    from hyperspace_tpu import constants as C
    from hyperspace_tpu import functions as F
    from hyperspace_tpu import native
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.indexes.covering import CoveringIndexConfig
    from hyperspace_tpu.indexes.dataskipping import DataSkippingIndexConfig
    from hyperspace_tpu.indexes.sketches import BloomFilterSketch
    from hyperspace_tpu.indexes.zorder import ZOrderCoveringIndexConfig
    from hyperspace_tpu.native import calibrate
    from hyperspace_tpu.session import HyperspaceSession

    cache_dir = jax.config.jax_compilation_cache_dir
    log(f"device {device}, jax {jax.__version__}, rows {args.rows:,}, "
        f"compile cache {cache_dir}")

    # the repo's own jitted programs, watched by their trace-cache size: a
    # size that grew in a phase means the program was compiled for this
    # backend and executed there in that phase
    from hyperspace_tpu.ops import aggregate as agg_ops
    from hyperspace_tpu.ops import bloom as bloom_ops
    from hyperspace_tpu.ops import filter as filter_ops
    from hyperspace_tpu.ops import hash as hash_ops
    from hyperspace_tpu.ops import join as join_ops
    from hyperspace_tpu.ops import sort as sort_ops
    from hyperspace_tpu.ops import zorder as zorder_ops
    from hyperspace_tpu.parallel import shuffle as shuffle_ops

    roster = {
        "parallel.shuffle._compact_program": shuffle_ops._compact_program,
        "ops.join._sharded_join": join_ops._sharded_join,
        "ops.join._jit_vmapped": join_ops._jit_vmapped,
        "ops.filter._run": filter_ops._run,
        "ops.sort.lexsort_indices": sort_ops.lexsort_indices,
        "ops.hash._bucket_ids_words": hash_ops._bucket_ids_words,
        "ops.aggregate._seg_sum_count": agg_ops._seg_sum_count,
        "ops.aggregate._seg_min": agg_ops._seg_min,
        "ops.aggregate._seg_max": agg_ops._seg_max,
        "ops.bloom._bit_indices": bloom_ops._bit_indices,
        "ops.zorder._interleave": zorder_ops._interleave,
    }
    phases: dict = {}

    class phase:
        """Times one phase and records which roster programs ran in it."""

        def __init__(self, name: str):
            self.name = name

        def __enter__(self):
            log(f"phase {self.name} ...")
            self.t0 = time.time()
            self.sizes = {k: f._cache_size() for k, f in roster.items()}
            self.req = compiles["requests"]
            self.info = phases[self.name] = {}
            return self.info

        def __exit__(self, exc_type, exc, tb):
            if exc_type is None:
                self.info["device_programs"] = sorted(
                    {k for k, f in roster.items() if f._cache_size() > self.sizes[k]}
                    | set(self.info.pop("witnessed", ()))
                )
                self.info["compile_requests"] = compiles["requests"] - self.req
                self.info["seconds"] = round(time.time() - self.t0, 2)
                log(f"phase {self.name} done: {json.dumps(self.info)}")

    def on_device(out, program: str, info: dict):
        """A roster program this script called itself: its output must
        live on the device the run is about."""
        leaf = jax.tree_util.tree_leaves(out)[0]
        platforms = {d.platform for d in leaf.devices()}
        check(platforms == {device["platform"]},
              f"{program} produced its output on {platforms}")
        info.setdefault("witnessed", []).append(program)
        return out

    def ran_on_device(info: dict, *programs: str) -> None:
        """The named programs must have been compiled and run in the
        phase — an equal answer alone would also come from a silent host
        fallback (executor._filter_mask catches Unsupported)."""
        for p in programs:
            check(p in info["device_programs"],
                  f"{p} did not run compiled in this phase: "
                  f"{info['device_programs']}")

    # -- native kernels built HERE; a known dispatch policy -----------------
    with phase("probe") as info:
        stale = glob.glob(os.path.join(native._cache_dir(), "_hs_native_*"))
        stale += glob.glob(os.path.join(native._cache_dir(), "_hs_calibration.json*"))
        for path in stale:
            os.unlink(path)
        lib = native.load()
        check(lib is not None,
              "native kernels did not build (g++ missing or compile failed): "
              "the numpy twins would hide it")
        calibrate.invalidate()
        thresholds0 = calibrate.thresholds()
        check(thresholds0.source == "calibrated",
              f"calibration probe did not run: {thresholds0}")
        info["removed_stale_artifacts"] = len(stale)
        info["native_so"] = os.path.basename(native._cache_path())
        log(f"thresholds: {thresholds0}")

    tmp = tempfile.mkdtemp(prefix="hs_chip_smoke_")
    fe = None
    try:
        n_items = args.rows
        n_orders = max(n_items // 8, 1)
        t0 = time.time()
        items_dir, orders_dir = bench.gen_data(
            tmp, n_items, n_orders, seed=args.seed
        )
        src = table_cols(pq.read_table(items_dir))
        o_src = table_cols(pq.read_table(orders_dir))
        check(np.array_equal(o_src["o_orderkey"], np.arange(n_orders)),
              "orders keys are not 0..n-1")
        gen_s = round(time.time() - t0, 2)
        log(f"data: {n_items:,} lineitem / {n_orders:,} orders rows in {gen_s}s")

        session = HyperspaceSession()
        session.conf.set(C.INDEX_SYSTEM_PATH, os.path.join(tmp, "indexes"))
        hs = Hyperspace(session)
        fe = session.serve_frontend
        n_dev = int(session.runtime.mesh.devices.size)
        check(n_dev == device["count"],
              f"session mesh has {n_dev} devices, jax has {device['count']}")
        mesh_info = {"devices": n_dev}

        def peaks():
            return [
                (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in jax.devices()
            ]

        items = session.read.parquet(items_dir)
        orders = session.read.parquet(orders_dir)
        answers: dict = {}

        def served(df, how: str, n_indexes: int = 1) -> None:
            plan = df.explain()
            check(plan.count(f"Hyperspace(Type: {how}") == n_indexes,
                  f"not index-served by {n_indexes}x {how}:\n{plan}")

        def both_ways(df, how: str, n_indexes: int = 1):
            """-> (index-served answer through the frontend, unindexed)."""
            session.enable_hyperspace()
            served(df, how, n_indexes)
            got = fe.serve(df)
            session.disable_hyperspace()
            base = df.collect()
            session.enable_hyperspace()
            return got, base

        # ------------------------------------------------------------------
        with phase("a") as info:
            from hyperspace_tpu.indexes import covering_build

            cfg_l = CoveringIndexConfig(
                "l_idx", ["l_orderkey"],
                ["l_shipdate", "l_quantity", "l_extendedprice"],
            )
            with timed(info, "build_lineitem_cold_s"):
                hs.create_index(items, cfg_l)
            telemetry = dict(covering_build.last_build_telemetry)
            hs.delete_index("l_idx")
            hs.vacuum_index("l_idx")
            session.index_manager.clear_cache()
            with timed(info, "build_lineitem_second_s"):
                hs.create_index(items, cfg_l)
            info["build_stage_s"] = {
                k: round(v, 2)
                for k, v in covering_build.last_build_breakdown.items()
            }
            with timed(info, "build_orders_s"):
                hs.create_index(
                    orders,
                    CoveringIndexConfig(
                        "o_idx", ["o_orderkey"], ["o_custkey", "o_totalprice"]
                    ),
                )
            if n_dev > 1:
                strategy = telemetry.get("shuffle_strategy")
                check(strategy is not None,
                      f"mesh build reported no exchange strategy: {telemetry}")
                mesh_info["exchange_strategy"] = strategy
                mesh_info["exchange_telemetry"] = {
                    k: v for k, v in telemetry.items()
                    if isinstance(v, (int, float, str))
                }
                if device["platform"] != "cpu":
                    check(strategy == "compact",
                          f"accelerator mesh resolved to {strategy!r}")
            peak = mesh_info["peak_bytes_after_build"] = peaks()
            if n_dev > 1 and device["platform"] != "cpu" and n_items >= 16_000_000:
                # operands put whole on device 0 before the program reshards
                # them would show as its peak far above its peers' (read
                # here, before phase (e) runs single-device kernels on it)
                check(peak[0] <= 1.5 * float(np.median(peak[1:])),
                      f"device 0 holds far more than its peers: {peak}")

        # ------------------------------------------------------------------
        POINT_COLS = ("l_orderkey", "l_shipdate", "l_quantity", "l_extendedprice")

        def q_point(df, key):
            # the float payload is selected on purpose: it must come back
            # bit for bit through whatever the build moved it through
            return df.filter(df["l_orderkey"] == key).select(*POINT_COLS)

        def q_range(df, lo, hi):
            return df.filter((df["l_orderkey"] >= lo) & (df["l_orderkey"] < hi))

        def q_fagg(df, lo, hi):
            return q_range(df, lo, hi).agg(
                F.count().alias("n"),
                F.sum("l_extendedprice").alias("rev"),
                F.min("l_quantity").alias("qmin"),
                F.max("l_quantity").alias("qmax"),
            )

        def q_gagg(df, lo, hi):
            return q_range(df, lo, hi).group_by("l_quantity").agg(
                F.count().alias("n"), F.sum("l_extendedprice").alias("rev")
            )

        def q_join(o, i):
            return o.join(i, on=o["o_orderkey"] == i["l_orderkey"]).select(
                "o_orderkey", "o_custkey", "l_quantity"
            )

        def ref_join(s: dict) -> dict:
            # every l_orderkey is in [0, n_orders) and orders holds each
            # key once, so the join is one output row per lineitem row
            return {
                "o_orderkey": s["l_orderkey"],
                "o_custkey": o_src["o_custkey"][s["l_orderkey"]],
                "l_quantity": s["l_quantity"],
            }

        ranges = [
            (n_orders // 4, n_orders // 4 + max(n_orders // 8, 1)),
            (n_orders // 2, n_orders // 2 + max(n_orders // 16, 1)),
        ]

        with phase("b") as info:
            for key in (n_orders // 3, n_orders // 7, n_orders - 1):
                got, base = both_ways(q_point(items, key), "CI")
                m = src["l_orderkey"] == key
                want = {c: src[c][m] for c in POINT_COLS}
                same_rows(base, want, f"point {key} unindexed")
                answers[f"point_{key}"] = same_rows(got, want, f"point {key}")
            for lo, hi in ranges:
                m = (src["l_orderkey"] >= lo) & (src["l_orderkey"] < hi)
                got, base = both_ways(q_fagg(items, lo, hi), "CI")
                want = {
                    "n": np.array([m.sum()]),
                    "rev": np.array([src["l_extendedprice"][m].sum()]),
                    "qmin": np.array([src["l_quantity"][m].min()]),
                    "qmax": np.array([src["l_quantity"][m].max()]),
                }
                same_aggregate(base, want, [], f"filter-agg [{lo},{hi}) unindexed")
                same_aggregate(got, want, [], f"filter-agg [{lo},{hi})")
                answers[f"fagg_{lo}_{hi}"] = [int(m.sum())]
                got, base = both_ways(q_gagg(items, lo, hi), "CI")
                qty = src["l_quantity"][m]
                groups = np.unique(qty)
                want = {
                    "l_quantity": groups,
                    "n": np.bincount(qty)[groups],
                    "rev": np.bincount(qty, weights=src["l_extendedprice"][m])[groups],
                }
                same_aggregate(base, want, ["l_quantity"], f"grouped [{lo},{hi}) unindexed")
                same_aggregate(got, want, ["l_quantity"], f"grouped [{lo},{hi})")
                answers[f"gagg_{lo}_{hi}"] = [int(len(groups)), int(want["n"].sum())]
            with timed(info, "join_both_ways_s"):
                got, base = both_ways(q_join(orders, items), "CI", 2)
                want = ref_join(src)
                same_rows(base, want, "join unindexed")
                answers["join"] = same_rows(got, want, "join")
            del got, base, want

        # ------------------------------------------------------------------
        def append_files(tag: str, n_extra: int, n_files: int, seed: int) -> None:
            rng = np.random.default_rng(seed)
            extra = {
                "l_orderkey": rng.integers(0, n_orders, n_extra, dtype=np.int64),
                "l_shipdate": np.datetime64("1994-01-01")
                + rng.integers(2400, 2500, n_extra).astype("timedelta64[D]"),
                "l_quantity": rng.integers(1, 51, n_extra, dtype=np.int64),
                "l_extendedprice": rng.normal(30000, 8000, n_extra),
            }
            table = pa.table(extra)  # datetime64[D] becomes date32
            for i in range(n_files):
                lo, hi = i * n_extra // n_files, (i + 1) * n_extra // n_files
                pq.write_table(
                    table.slice(lo, hi - lo),
                    os.path.join(items_dir, f"{tag}{i}.parquet"),
                )
            add = table_cols(table)
            for k in src:
                src[k] = np.concatenate([src[k], add[k]])

        def hybrid(on: bool):
            session.conf.set(C.INDEX_HYBRID_SCAN_ENABLED, on)
            session.index_manager.clear_cache()
            return session.read.parquet(items_dir)

        with phase("c") as info:
            append_files("appended", max(n_items // 32, 2), 2, args.seed + 1)
            info["appended_rows"] = max(n_items // 32, 2)
            items2 = hybrid(True)
            want = ref_join(src)
            with timed(info, "hybrid_join_both_ways_s"):
                got, base = both_ways(q_join(orders, items2), "CI", 2)
                same_rows(base, want, "hybrid join unindexed")
                answers["join_hybrid"] = same_rows(got, want, "hybrid join")
            del got, base
            with timed(info, "refresh_incremental_s"):
                hs.refresh_index("l_idx", C.REFRESH_MODE_INCREMENTAL)
            items2 = hybrid(False)
            served(q_join(orders, items2), "CI", 2)
            got = fe.serve(q_join(orders, items2))
            answers["join_refreshed"] = same_rows(got, want, "join after refresh")
            check(answers["join_refreshed"] == answers["join_hybrid"],
                  "refresh changed the join's answer")
            del got, want
        if n_dev > 1:
            # the Hybrid-Scan join is the not-both-sorted shape: on a mesh
            # it takes the sharded device program
            ran_on_device(phases["c"], "ops.join._sharded_join")

        # ------------------------------------------------------------------
        with phase("d") as info:
            items3 = session.read.parquet(items_dir)
            with timed(info, "build_zorder_s"):
                hs.create_index(
                    items3,
                    ZOrderCoveringIndexConfig(
                        "z_idx", ["l_shipdate", "l_quantity"], ["l_orderkey"]
                    ),
                )
            zlo, zhi = np.datetime64("1995-06-01"), np.datetime64("1995-06-30")

            def q_zrange(df):
                return df.filter(
                    (df["l_shipdate"] >= zlo)
                    & (df["l_shipdate"] <= zhi)
                    & (df["l_quantity"] <= 5)
                ).select("l_shipdate", "l_quantity", "l_orderkey")

            days = src["l_shipdate"].astype(np.int64)  # date32 as days
            m = (
                (days >= zlo.astype(np.int64))
                & (days <= zhi.astype(np.int64))
                & (src["l_quantity"] <= 5)
            )
            want = {c: src[c][m] for c in ("l_shipdate", "l_quantity", "l_orderkey")}
            got, base = both_ways(q_zrange(items3), "ZOCI")
            same_rows(base, want, "z-order range unindexed")
            answers["zorder_range"] = same_rows(got, want, "z-order range")
            hs.delete_index("z_idx")
            hs.vacuum_index("z_idx")
            session.index_manager.clear_cache()

            # Bloom sketch over orders.o_custkey (the sketch abstains on
            # date columns; o_idx cannot serve a predicate on o_custkey).
            # The probed key is one some source file does not hold, so
            # the sketch has a file to prune.
            n_files = len(orders.logical_plan.collect_leaves()[0].relation.files)
            cust = o_src["o_custkey"]
            parts = [
                cust[i * n_orders // n_files : (i + 1) * n_orders // n_files]
                for i in range(n_files)
            ]
            missing = np.setdiff1d(parts[0], parts[-1])
            check(len(missing) > 0, "no o_custkey is missing from the last file")
            probe = int(missing[0])
            with timed(info, "build_dataskipping_s"):
                hs.create_index(
                    orders,
                    DataSkippingIndexConfig(
                        "ds_idx",
                        BloomFilterSketch(
                            "o_custkey", expected_items=max(n_orders // 10, 1)
                        ),
                    ),
                )

            def q_cust(df):
                return df.filter(df["o_custkey"] == probe).select(
                    "o_orderkey", "o_custkey", "o_totalprice"
                )

            m = cust == probe
            want = {c: o_src[c][m] for c in ("o_orderkey", "o_custkey", "o_totalprice")}
            got, base = both_ways(q_cust(orders), "DS")
            same_rows(base, want, "bloom point unindexed")
            answers["bloom_point"] = same_rows(got, want, "bloom point")
            leaves = session.optimize(q_cust(orders).logical_plan).collect_leaves()
            info["bloom_files_scanned"] = [len(leaves[0].relation.files), n_files]
            check(len(leaves[0].relation.files) < n_files,
                  f"bloom sketch pruned nothing: {info['bloom_files_scanned']}")
            hs.delete_index("ds_idx")
            hs.vacuum_index("ds_idx")
            session.index_manager.clear_cache()
            del got, base, want, m, days
        ran_on_device(phases["d"], "ops.zorder._interleave", "ops.bloom._bit_indices")

        # ------------------------------------------------------------------
        with phase("e") as info:
            import jax.numpy as jnp

            from hyperspace_tpu.ops import pad_len

            # device filter kernel, through its documented key
            lo, hi = ranges[0]

            def q_dev_filter(df):
                return df.filter(
                    (df["l_orderkey"] >= lo)
                    & (df["l_orderkey"] < hi)
                    & (df["l_quantity"] <= 5)
                ).select("l_orderkey", "l_quantity", "l_extendedprice")

            items5 = session.read.parquet(items_dir)
            served(q_dev_filter(items5), "CI")
            m = (src["l_orderkey"] >= lo) & (src["l_orderkey"] < hi) & (src["l_quantity"] <= 5)
            want = {c: src[c][m] for c in ("l_orderkey", "l_quantity", "l_extendedprice")}
            # host mask first, pinned there by the same key (the default
            # already sends a batch of 8M rows or more to the device), so
            # that this predicate's first compile is the device run's
            before = filter_ops._run._cache_size()
            session.conf.set(C.EXECUTION_DEVICE_FILTER_MIN_ROWS, 1 << 62)
            same_rows(fe.serve(q_dev_filter(items5)), want, "range filter (host mask)")
            check(filter_ops._run._cache_size() == before,
                  "the host-mask run reached ops/filter._run")
            session.conf.set(C.EXECUTION_DEVICE_FILTER_MIN_ROWS, 1)
            dev_answer = fe.serve(q_dev_filter(items5))
            session.conf.unset(C.EXECUTION_DEVICE_FILTER_MIN_ROWS)
            check(filter_ops._run._cache_size() > before,
                  "deviceFilterMinRows=1 did not reach ops/filter._run")
            answers["device_filter"] = same_rows(dev_answer, want, "range filter (device mask)")
            del dev_answer, want, m

            # device join match: needs the not-both-sorted shape, so put
            # the index back under Hybrid Scan with one more appended file
            append_files("late", max(n_items // 128, 2), 1, args.seed + 2)
            items6 = hybrid(True)
            want = ref_join(src)
            served(q_join(orders, items6), "CI", 2)
            got = fe.serve(q_join(orders, items6))
            answers["join_hybrid_2"] = same_rows(got, want, "second hybrid join")
            if n_dev == 1:  # a mesh already took _sharded_join, in (c)
                before = join_ops._jit_vmapped._cache_size()
                session.conf.set(C.EXECUTION_DEVICE_JOIN_MIN_ROWS, 1)
                got = fe.serve(q_join(orders, items6))
                session.conf.unset(C.EXECUTION_DEVICE_JOIN_MIN_ROWS)
                check(join_ops._jit_vmapped._cache_size() > before,
                      "deviceJoinMinRows=1 did not reach ops/join._jit_vmapped")
                check(same_rows(got, want, "hybrid join (device match)")
                      == answers["join_hybrid_2"], "device match differs from host match")
            hybrid(False)
            del got, want

            # the kernels no key reaches below 2^26 rows, called over the
            # same key column and compared with their host twins
            keys = src["l_orderkey"][None, :]
            n = keys.shape[1]
            n_pad = pad_len(n)
            planes = sort_ops._order_words_np(keys)
            padded = np.concatenate(
                [planes, np.full((2, n_pad - n), np.uint32(0xFFFFFFFF))], axis=1
            )
            dev_perm = np.asarray(
                on_device(
                    sort_ops.lexsort_indices(jnp.asarray(padded)),
                    "ops.sort.lexsort_indices",
                    info,
                )
            )[:n]
            host_perm = native.lexsort_u32(planes)
            check(host_perm is not None, "native lexsort unavailable")
            check(np.array_equal(dev_perm, host_perm), "device lexsort != host lexsort")
            del planes, padded, dev_perm, host_perm

            words = hash_ops.split_words_np(keys)
            padded = np.concatenate(
                [words, np.zeros((2, n_pad - n), dtype=np.uint32)], axis=1
            )
            dev_ids = np.asarray(
                on_device(
                    hash_ops._bucket_ids_words(
                        jnp.asarray(padded), C.INDEX_NUM_BUCKETS_DEFAULT, 42
                    ),
                    "ops.hash._bucket_ids_words",
                    info,
                )
            )[:n]
            check(
                np.array_equal(
                    dev_ids, hash_ops.bucket_ids_host(keys, C.INDEX_NUM_BUCKETS_DEFAULT)
                ),
                "device bucket ids != host bucket ids",
            )
            del words, padded, dev_ids

            # integers only: float reductions never leave the host (the
            # TPU holds no IEEE double; see ops/aggregate.py)
            gid = (src["l_quantity"] - 1).astype(np.int64)
            vals = src["l_orderkey"]
            valid = src["l_quantity"] != 13  # some invalid rows
            segs = 50
            dev_args = (jnp.asarray(gid), jnp.asarray(vals), jnp.asarray(valid), segs)
            s_dev, c_dev = on_device(
                agg_ops._seg_sum_count(*dev_args), "ops.aggregate._seg_sum_count", info
            )
            s_host, c_host = agg_ops._host_sum_count(gid, vals, valid, segs)
            check(np.array_equal(np.asarray(s_dev), s_host), "device segment sums")
            check(np.array_equal(np.asarray(c_dev), c_host), "device segment counts")
            for mode, fn in (("min", agg_ops._seg_min), ("max", agg_ops._seg_max)):
                host = agg_ops._host_minmax(gid, vals, valid, segs, mode)
                dev = on_device(fn(*dev_args), f"ops.aggregate._seg_{mode}", info)
                check(np.array_equal(np.asarray(dev), host), f"device segment {mode}")

        # -- closing checks -------------------------------------------------
        stats = fe.stats()
        frontend = {
            k: stats[k]
            for k in ("admitted", "completed", "failed", "retries",
                      "degraded", "degraded_pins")
        }
        check(
            all(frontend[k] == 0 for k in ("failed", "retries", "degraded", "degraded_pins")),
            f"the frontend's retry/degrade ladder fired: {frontend}",
        )
        check(frontend["completed"] == frontend["admitted"] > 0,
              f"frontend left queries behind: {frontend}")
        thresholds1 = calibrate.thresholds()
        check(thresholds1 == thresholds0,
              f"dispatch thresholds moved during the run: {thresholds0} -> {thresholds1}")
        peak = peaks()
    finally:
        if fe is not None:
            fe.close()
        shutil.rmtree(tmp, ignore_errors=True)

    verdict = {"ok": not rehearsal, "device": device}
    report = {
        **verdict,
        "jax": jax.__version__,
        "rows": n_items,
        "row_cut": (
            None if n_items >= FULL_ROWS
            else f"{n_items} of {FULL_ROWS} lineitem rows (rows only; widths, "
                 "buckets and phases unchanged)"
        ),
        "seed": args.seed,
        "compile_cache_dir": cache_dir,
        "thresholds": dataclasses.asdict(thresholds0),
        "mesh": mesh_info,
        "device_peak_bytes": peak,
        "compiles": {
            "requests": compiles["requests"],
            "cache_hits": compiles["cache_hits"],
            "fresh": compiles["requests"] - compiles["cache_hits"],
            "seconds": round(compiles["seconds"], 1),
        },
        "smoke_timings_note": "seconds of ONE run, set-up and compiles "
                              "included; not benchmark metrics",
        "gen_data_s": gen_s,
        "total_s": round(time.time() - t_start, 1),
        "phases": phases,
        "frontend": frontend,
        "answers": answers,
        "claim": None,
    }
    if rehearsal:
        report["rehearsal"] = "cpu: debugs the script; says nothing about the chip"
    print(json.dumps(report), flush=True)
    # the last line holds exactly these keys: the driver reads it
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
