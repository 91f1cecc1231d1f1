"""Traffic ``build_loop``: create the configuration's index over and over.

Window: { ``create_index``; ``delete_index``; ``vacuum_index``;
``clear_cache`` } until ``--seconds`` have passed at the end of a build.
``build_rows_per_s`` = source rows indexed by all completed builds over
the seconds from the window's start to the end of the last build; delete
and vacuum are inside that time. The last build is kept, and once the
window has closed it is read back in full through the executor and its
bucket files are opened, both against the plain reference.
"""

from __future__ import annotations

import os
import resource
import time
import traceback

import numpy as np

import datagen
import queries
import reference


def mean_stages(ops: list) -> dict:
    """Mean seconds of each ``last_build_breakdown`` stage per operation."""
    keys = sorted({k for o in ops for k in o["breakdown"]})
    return {k: round(sum(o["breakdown"].get(k, 0.0) for o in ops) / len(ops), 3) for k in keys}


def _cpu_lost_s() -> tuple:
    """Seconds since boot that this machine's cores spent taken away by
    its host (steal) and waiting for the disk (iowait), all cores summed:
    printed per build, to tell a slow build from a host that was busy."""
    try:
        with open("/proc/stat") as f:
            ticks = [int(x) for x in f.readline().split()[1:9]]
        hz = os.sysconf("SC_CLK_TCK")
        return ticks[7] / hz, ticks[4] / hz
    except (OSError, ValueError, IndexError):
        return 0.0, 0.0


def _process_cost() -> tuple:
    """(user seconds, system seconds, major page faults) of this process so
    far: printed per build beside the above. A stalled build that burnt no
    more CPU than its neighbours waited; one with major faults read from
    the disk what the page cache had held."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime, ru.ru_stime, ru.ru_majflt


def _drop(ctx) -> None:
    name = ctx.config["index"]["name"]
    ctx.hs.delete_index(name)
    ctx.hs.vacuum_index(name)
    ctx.session.index_manager.clear_cache()


def setup(ctx) -> None:
    """One build compiles this size band's device programs."""
    ctx.state["items"] = ctx.read_items()
    for _ in range(int(ctx.traffic.get("warm_builds", 1))):
        ctx.hs.create_index(ctx.state["items"], ctx.index_config())
        _drop(ctx)


def window(ctx, seconds: float) -> dict:
    from hyperspace_tpu.indexes import covering_build

    items, cfg = ctx.state["items"], ctx.index_config()
    ops, failed = [], 0
    t0 = time.perf_counter()
    end = 0.0
    while True:
        t = time.perf_counter()
        steal0, iowait0 = _cpu_lost_s()
        user0, sys0, majflt0 = _process_cost()
        try:
            with ctx.span("bench.create_index"):
                ctx.hs.create_index(items, cfg)
        except Exception:
            traceback.print_exc()
            failed += 1
            break
        end = time.perf_counter() - t0
        steal1, iowait1 = _cpu_lost_s()
        user1, sys1, majflt1 = _process_cost()
        ops.append({
            "kind": "build", "wall_s": time.perf_counter() - t,
            "breakdown": dict(covering_build.last_build_breakdown),
            "telemetry": dict(covering_build.last_build_telemetry),
            "cpu_steal_s": steal1 - steal0, "cpu_iowait_s": iowait1 - iowait0,
            "cpu_user_s": user1 - user0, "cpu_sys_s": sys1 - sys0,
            "major_faults": majflt1 - majflt0,
        })
        if end >= seconds:
            break
        t = time.perf_counter()
        with ctx.span("bench.delete_vacuum"):
            _drop(ctx)
        ops[-1]["drop_s"] = time.perf_counter() - t
    strategies = sorted({str(o["telemetry"].get("shuffle_strategy")) for o in ops})
    return {
        "window_s": end, "attempted": len(ops) + failed, "failed": failed,
        "ops": ops,
        "metrics": {"build_rows_per_s": len(ops) * ctx.rows / end if end else 0.0},
        "resolved": {"builds": len(ops), "exchange_strategy": strategies,
                     "build_wall_s": [round(o["wall_s"], 3) for o in ops],
                     "drop_s": [round(o["drop_s"], 3) for o in ops if "drop_s" in o],
                     "cpu_steal_s": [round(o["cpu_steal_s"], 2) for o in ops],
                     "cpu_iowait_s": [round(o["cpu_iowait_s"], 2) for o in ops],
                     "cpu_user_s": [round(o["cpu_user_s"], 2) for o in ops],
                     "cpu_sys_s": [round(o["cpu_sys_s"], 2) for o in ops],
                     "major_faults": [o["major_faults"] for o in ops],
                     "per_build_stage_s": [
                         {k: round(v, 3) for k, v in o["breakdown"].items()} for o in ops],
                     "stage_s": mean_stages(ops)},
    }


def _point_keys(ctx) -> np.ndarray:
    """Keys of orders drawn from the seed, and last a key that the sparse
    key space leaves out (the answer is no row)."""
    rng = np.random.default_rng([ctx.seed, 0xC0FFEE])
    orders = rng.integers(0, ctx.n_orders, int(ctx.traffic.get("check_points", 8)))
    keys = datagen.order_key(orders)
    return np.append(keys[:-1], keys[-1] - keys[-1] % 32 + 9)


def check(ctx, win: dict) -> dict:
    if not win["ops"]:
        return {"builds_completed": {"value": 1, "limit": 0}}
    got = queries.readback(ctx, ctx.items_cols, _point_keys(ctx))
    got.update(queries.structure(ctx))
    return got


def controls(ctx, win: dict) -> dict:
    return {"float32_payload": queries.readback(
        ctx, ctx.items_cols, _point_keys(ctx), transform=reference.lossy)}


def teardown(ctx) -> None:
    pass
