"""Mesh build-throughput harness — the MULTICHIP_r0N artifact producer.

Earlier MULTICHIP artifacts recorded only rc/ok of the tiny-shape
correctness dryrun; with the sharded build/serve tail the artifact must
record THROUGHPUT: this script forces an ``n``-device CPU mesh (or uses
real devices), runs the full framework dryrun first as a correctness
gate, then times warm covering builds at ``HS_MESH_ROWS`` on 1 device
and on the full mesh — once per exchange strategy in
``HS_MESH_STRATEGIES`` — with the per-stage breakdown (sort/write busy
seconds across the shard tails vs ``tail_wall``) and the exchange
plane's telemetry: chosen strategy, pack/exchange/unpack stage seconds
and the cap/skew numbers. ``mesh_speedup`` compares the single-device
build against the FIRST listed strategy's full-mesh build (default
``auto``, the shipping configuration).

Prints exactly ONE JSON line on stdout (progress to stderr), in the
MULTICHIP artifact shape (n_devices / rc / ok / skipped / tail) plus the
throughput fields.

Usage:  python scripts/bench_mesh.py [n_devices]     (default 8)
Env:    HS_MESH_ROWS (default 64_000_000), HS_MESH_BUCKETS (default 8),
        HS_MESH_SIZES (default "1,<n_devices>"),
        HS_MESH_STRATEGIES (default "auto" — e.g. "auto,compact,host,
        twostage" for a per-strategy A/B artifact)
"""

import io
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import redirect_stdout

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def timed_build(devices, rows, data_dir, num_buckets, strategy="auto"):
    """Warm covering-index build on ``devices`` under ``strategy``: first
    build pays the compiles/caches, the timed second build is steady
    state."""
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.indexes.covering import CoveringIndexConfig
    from hyperspace_tpu.indexes.covering_build import (
        last_build_breakdown,
        last_build_telemetry,
    )
    from hyperspace_tpu.session import HyperspaceSession

    root = tempfile.mkdtemp(prefix=f"hs_meshidx_{len(devices)}_")
    try:
        session = HyperspaceSession(devices=devices)
        session.conf.set(C.INDEX_SYSTEM_PATH, root)
        session.conf.set(C.INDEX_NUM_BUCKETS, num_buckets)
        session.conf.set(C.BUILD_EXCHANGE_STRATEGY, strategy)
        if strategy == "twostage":
            # single-controller simulation: carve the mesh in two hosts
            session.conf.set(C.BUILD_EXCHANGE_TWOSTAGE_HOSTS, 2)
        hs = Hyperspace(session)
        df = session.read.parquet(data_dir)
        cfg = CoveringIndexConfig(
            "mesh_idx",
            ["l_orderkey"],
            ["l_shipdate", "l_quantity", "l_extendedprice"],
        )
        hs.create_index(df, cfg)  # warm compiles/caches
        hs.delete_index("mesh_idx")
        hs.vacuum_index("mesh_idx")
        session.index_manager.clear_cache()
        t0 = time.perf_counter()
        hs.create_index(df, cfg)
        warm = time.perf_counter() - t0
        telem = dict(last_build_telemetry)
        return {
            "devices": len(devices),
            "rows": rows,
            "strategy": strategy,
            "exchange_strategy": telem.get("shuffle_strategy", ""),
            "exchange_stage_seconds": {
                stage: telem.get(f"shuffle_{stage}_s", 0.0)
                for stage in ("plan", "pack", "exchange", "unpack")
            },
            "build_warm_s": round(warm, 3),
            "build_rows_per_sec": round(rows / warm),
            "build_stage_seconds": {
                k: round(v, 3) for k, v in last_build_breakdown.items()
            },
            "shuffle": telem,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main() -> int:
    n_devices = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    rows = int(os.environ.get("HS_MESH_ROWS", 64_000_000))
    num_buckets = int(os.environ.get("HS_MESH_BUCKETS", 8))
    sizes_env = os.environ.get("HS_MESH_SIZES", f"1,{n_devices}")
    strategies = [
        s.strip()
        for s in os.environ.get("HS_MESH_STRATEGIES", "auto").split(",")
        if s.strip()
    ]

    import __graft_entry__ as graft

    jax = graft._ensure_devices(n_devices)

    out = {
        "n_devices": n_devices,
        "rc": 0,
        "ok": False,
        "skipped": False,
        "rows": rows,
        "num_buckets": num_buckets,
        "strategies": strategies,
    }
    # 1. correctness gate: the full tiny-shape framework dryrun (create/
    # join/hybrid/refresh/delete/optimize, differentially checked)
    buf = io.StringIO()
    try:
        with redirect_stdout(buf):
            graft.dryrun_multichip(n_devices)
    except Exception as exc:  # artifact must record the failure, not die
        out["rc"] = 1
        out["tail"] = f"{buf.getvalue()}\nDRYRUN FAILED: {exc!r}"
        print(json.dumps(out))
        return 1
    tail = buf.getvalue().strip().splitlines()
    out["tail"] = tail[-1] if tail else ""
    log(out["tail"])

    # 2. throughput: warm builds per (mesh size, strategy) over one
    # shared dataset; single-device rungs run once (no exchange)
    import bench as _bench

    tmp = tempfile.mkdtemp(prefix="hs_meshbench_")
    try:
        log(f"generating {rows:,}-row dataset ...")
        items_dir, _orders = _bench.gen_data(tmp, rows, max(rows // 8, 1))
        mesh = []
        for d in [int(x) for x in sizes_env.split(",") if x.strip()]:
            if d > len(jax.devices()):
                continue
            for strategy in strategies if d > 1 else strategies[:1]:
                log(f"building on {d} device(s), strategy={strategy} ...")
                rung = timed_build(
                    jax.devices()[:d], rows, items_dir, num_buckets, strategy
                )
                log(
                    f"mesh{d}/{strategy}"
                    f"[{rung['exchange_strategy'] or 'none'}]: "
                    f"{rung['build_warm_s']}s warm "
                    f"({rung['build_rows_per_sec']:,} rows/s); "
                    f"stages: {rung['build_stage_seconds']}; "
                    f"exchange: {rung['exchange_stage_seconds']}"
                )
                mesh.append(rung)
        out["mesh"] = mesh
        base = [r for r in mesh if r["devices"] == 1]
        full = [
            r
            for r in mesh
            if r["devices"] > 1 and r["strategy"] == strategies[0]
        ]
        if base and full:
            out["mesh_speedup"] = round(
                base[0]["build_warm_s"] / full[0]["build_warm_s"], 3
            )
        out["ok"] = True
        print(json.dumps(out))
        return 0
    except MemoryError:
        out["skipped"] = True
        out["tail"] += "\nmesh bench skipped: MemoryError"
        print(json.dumps(out))
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
