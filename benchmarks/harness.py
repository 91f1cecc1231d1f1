"""The harness: one cell, one process — load, warm, measure, check, print.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name ``BENCHMARK.json`` gives:

    configs/<config>.json          the deployment as it is run; ``index.kind``
                                   names the index (``INDEX_KINDS``), ``indexed``
                                   + ``included`` the reference's columns
    traffic/<traffic>.json         ``driver`` and its parameters
    drivers/<driver>.py            the one general generator of a kind of traffic
    layer_metrics/<metric>.json    ``reader`` and its argument (unit, layer,
                                   moves and cells are BENCHMARK.json's alone)
    readers/<reader>.py            from spans, counters or the trace to a number
    roofline*.py                   a kernel's operations and bytes from shapes and
                                   the configuration; a roofline metric's ``arg``
                                   names module and function (``bytes_from``,
                                   ``bytes_fn``), ``peaks.json`` the chip's rates
    tests/faults/<driver>.py       how that driver's timed path is broken, and
                                   which compared numbers must and may see it

A later PR adds files and edits none.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def find_cell(manifest: dict, workload: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")


def config_of(manifest: dict, cell: dict, root: str = ROOT) -> dict:
    for cfg in manifest["configs"]:
        if cfg["name"] == cell["config"]:
            return load_json(root, cfg["file"])
    raise SystemExit(f"bench: no config {cell['config']!r} in BENCHMARK.json")


def traffic_of(cell: dict) -> dict:
    return load_json(HERE, "traffic", cell["traffic"] + ".json")


def metrics_of(manifest: dict, group: str, workload: str) -> list:
    """The metrics of ``group`` that this cell reports."""
    return [
        m for m in manifest[group]
        if "workloads" not in m or workload in m["workloads"]
    ]


# index.kind of a configuration -> (module, config class, the abbreviation
# ``explain()`` prints for it); each class takes (name, indexed, included)
INDEX_KINDS = {
    "covering": ("hyperspace_tpu.indexes.covering", "CoveringIndexConfig", "CI"),
    "zorder": ("hyperspace_tpu.indexes.zorder", "ZOrderCoveringIndexConfig", "ZOCI"),
}


def index_kind(config: dict) -> tuple:
    kind = config["index"]["kind"]
    if kind not in INDEX_KINDS:
        raise SystemExit(f"bench: config {config['name']!r} states index.kind {kind!r}; "
                         f"the harness builds {sorted(INDEX_KINDS)}")
    return INDEX_KINDS[kind]


def load_driver(name: str):
    return importlib.import_module(f"drivers.{name}")


def load_reader(name: str):
    return importlib.import_module(f"readers.{name}")


class CompileCounter:
    """Backend compiles and persistent-cache hits, by jax's own monitoring
    events (a cache hit is no compile)."""

    def __init__(self):
        import jax

        self.requests = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += duration

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "cache_hits": self.cache_hits,
                "seconds": round(self.seconds, 3)}


@dataclass
class Ctx:
    """What a driver gets: the system under test and the generated inputs."""

    config: dict
    traffic: dict
    seed: int
    rows: int
    n_orders: int
    tmp: str
    trace: bool
    rehearsal: bool = False
    session: object = None
    hs: object = None
    items_dir: str = ""
    index_root: str = ""
    items_cols: dict = None     # the reference's input, never the program's output
    state: dict = field(default_factory=dict)

    def span(self, name: str):
        """A host span in the profiler's own trace; free when not tracing."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def read_items(self):
        return self.session.read.parquet(self.items_dir)

    def index_config(self):
        module, cls, _abbr = index_kind(self.config)
        ix = self.config["index"]
        return getattr(importlib.import_module(module), cls)(
            ix["name"], ix["indexed"], ix["included"])

    def index_abbr(self) -> str:
        return index_kind(self.config)[2]


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peaks() -> list:
    import jax

    return [
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()
    ]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, rehearsal_rows: int = 0, controls: bool = False,
             manifest: dict = None) -> tuple:
    """-> (exit code, result dict or None). ``rehearsal_rows`` > 0 is the
    CPU rehearsal: the whole path at a tiny size, never a chip's name."""
    manifest = manifest or load_manifest()
    cell = find_cell(manifest, workload)
    config = config_of(manifest, cell)
    traffic = traffic_of(cell)
    index_kind(config)      # an unknown kind is refused before anything is made
    rehearsal = rehearsal_rows > 0

    device = device_info()
    if rehearsal:
        if device["platform"] == "tpu":
            log("--cpu-rehearsal is for a machine without a chip")
            return 2, None
        log(f"CPU REHEARSAL on {device}: no number of this run is a device metric")
    elif device["platform"] != "tpu" or device["count"] < cell["chips"]:
        log(f"cell {workload} needs {cell['chips']} TPU chip(s); jax reports {device}")
        return 2, None
    elif device["count"] != cell["chips"]:
        log(f"NOTE: cell asks for {cell['chips']} chip(s), the session's mesh "
            f"will span all {device['count']}")

    import jax

    compiles = CompileCounter()

    import datagen
    from hyperspace_tpu import constants as C
    from hyperspace_tpu import native
    from hyperspace_tpu.hyperspace import Hyperspace
    from hyperspace_tpu.native import calibrate
    from hyperspace_tpu.session import HyperspaceSession

    n_orders = max((rehearsal_rows or int(config["rows"])) // datagen.ITEMS_PER_ORDER, 1)
    rows = n_orders * datagen.ITEMS_PER_ORDER
    if not rehearsal and (rows, n_orders) != (config["rows"], config["orders"]):
        raise RuntimeError(f"config states {config['rows']} rows of {config['orders']} orders")
    log(f"cell {workload}: config {cell['config']} traffic {cell['traffic']} "
        f"seed {seed} rows {rows:,} device {device} jax {jax.__version__} "
        f"compile cache {jax.config.jax_compilation_cache_dir}")

    tmp = tempfile.mkdtemp(prefix="hs_bench_")
    driver = load_driver(traffic["driver"])
    ctx = Ctx(config=config, traffic=traffic, seed=seed, rows=rows,
              n_orders=n_orders, tmp=tmp, trace=trace, rehearsal=rehearsal)
    try:
        # -- set-up: native kernels + probe, data from the seed, warm-up ----
        t0 = time.time()
        if native.load() is None:
            log("native kernels did not build (g++ missing?): numpy twins run")
        thresholds = calibrate.thresholds()
        log(f"set-up: native + probe {time.time() - t0:.1f}s; thresholds {thresholds}")
        t0 = time.time()
        ctx.items_dir, ctx.items_cols = datagen.gen_lineitem(
            tmp, n_orders, int(config["files_per_table"]), seed,
            cols=config["index"]["indexed"] + config["index"]["included"])
        log(f"set-up: data {time.time() - t0:.1f}s")

        session = HyperspaceSession()
        ctx.index_root = os.path.join(tmp, "indexes")
        session.conf.set(C.INDEX_SYSTEM_PATH, ctx.index_root)
        for key, value in config.get("conf", {}).items():
            session.conf.set(key, value)
        stated = config["index"].get("num_buckets")     # a kind without buckets states none
        if stated is not None and session.conf.num_buckets != stated:
            raise RuntimeError(f"config states {stated} buckets, the session "
                               f"runs {session.conf.num_buckets}")
        ctx.session, ctx.hs = session, Hyperspace(session)
        n_dev = int(session.runtime.mesh.devices.size)
        log(f"set-up: session mesh over {n_dev} device(s)")

        t0 = time.time()
        driver.setup(ctx)
        log(f"set-up: warm-up {time.time() - t0:.1f}s; compiles {compiles.snapshot()}")
        setup_s = time.time() - t_start

        # -- the measured window ------------------------------------------
        compiles_before = compiles.requests - compiles.cache_hits
        trace_dir = os.path.join(tmp, "trace")
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_win = time.perf_counter()
        try:
            win = driver.window(ctx, seconds)
        finally:
            traced_s = time.perf_counter() - t_win
            if trace:
                jax.profiler.stop_trace()
        compiles_in_window = compiles.requests - compiles.cache_hits - compiles_before
        peaks = memory_peaks()
        log(f"window: {win['window_s']:.2f}s, attempted {win['attempted']}, "
            f"failed {win['failed']}, compiles in window {compiles_in_window}")

        # -- correct: what the window produced against the plain reference --
        t0 = time.time()
        checks = driver.check(ctx, win)
        log(f"check: {time.time() - t0:.1f}s")
        control_checks = driver.controls(ctx, win) if controls else {}

        record = {
            "cell": cell, "config": config, "rows": rows, "device": device,
            "window_s": win["window_s"], "ops": win.get("ops", []),
            "compiles_in_window": compiles_in_window,
            "memory_peak_bytes": peaks, "trace": None,
        }
        result_device = dict(device, memory_peak_bytes=max(peaks) if peaks else 0)
        if trace:
            import trace_reduce

            t0 = time.time()
            tr = trace_reduce.load_xplane(trace_dir)
            record["trace"], record["traced_ns"] = tr, traced_s * 1e9
            for line in trace_reduce.describe(tr)[:40]:
                log(f"trace: {line}")
            busy = trace_reduce.busy_seconds(tr)
            if busy:
                result_device["busy_s"] = sum(busy) / len(busy)
            result_device["window_s"] = traced_s
            breakdown = {
                "device_ops": trace_reduce.top_device_ops(tr),
                "idle_gaps": trace_reduce.idle_gaps(tr, traced_s * 1e9),
            }
            log(f"trace: read in {time.time() - t0:.1f}s")

        values = dict(win["metrics"], setup_s=setup_s)
        metrics = {}
        if trace:
            for m in metrics_of(manifest, "per_layer", workload):
                spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
                value = load_reader(spec["reader"]).read(record, spec.get("arg", {}))
                if value is not None:
                    metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        else:
            for m in metrics_of(manifest, "end_to_end", workload):
                if m["name"] not in values:
                    raise RuntimeError(f"driver {traffic['driver']} gave no {m['name']}")
                metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

        correct = win["failed"] == 0 and all(c["value"] <= c["limit"] for c in checks.values())
        result = {
            "correct": bool(correct), "attempted": int(win["attempted"]),
            "failed": int(win["failed"]), "metrics": metrics,
            "device": result_device,
        }
        if trace:
            result["breakdown"] = breakdown
            result["end_to_end_traced"] = {k: float(v) for k, v in values.items()}
        if rehearsal:
            result["cpu_rehearsal"] = True
        for name, got in control_checks.items():
            bad = {k: c for k, c in got.items() if c["value"] > c["limit"]}
            log(f"control {name}: correct={not bad} " + json.dumps(got))
            result.setdefault("controls", {})[name] = {"correct": not bad, "checks": got}
        result["resolved"] = {
            "thresholds": {k: getattr(thresholds, k) for k in thresholds.__dataclass_fields__},
            **win.get("resolved", {}),
        }
        result["checks"] = checks   # last: each number compared beside its limit
        log("resolved: " + json.dumps(result["resolved"]))
        for name, c in checks.items():
            log(f"compared {name}: {c['value']} (limit {c['limit']})")
        log(f"correct: {correct}")
        return 0, result
    finally:
        try:
            driver.teardown(ctx)
        except Exception:  # the result, if any, is already made
            traceback.print_exc()
        shutil.rmtree(tmp, ignore_errors=True)
