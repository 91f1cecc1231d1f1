"""From a profiler trace to numbers: device busy time, a module's device
time, and the idle gaps by what the host was doing.

Everything works on a neutral form of the trace, so that it can be
checked on a small recorded one (``tests/data/small_trace.json``):

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

``load_xplane`` makes that form from the ``.xplane.pb`` the JAX profiler
writes, keeping only what is read here: the device planes' lines and the
host events whose name starts with ``bench.`` (the benchmark's own
``TraceAnnotation`` spans) or ``hs.`` (the program's: every span of its
action trace enters ``hs.<name>``).
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "hs.")
NO_SPAN = "_no_bench_span_"


def load_xplane(trace_dir: str) -> dict:
    import jax

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(sorted(files)[-1])
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        lines = []
        for line in plane.lines:
            events = [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events
                if device or e.name.startswith(SPAN_PREFIXES)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def describe(trace: dict) -> list:
    """One line per plane and line: what a person looks at before
    trusting the reduction on a new device."""
    out = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            names = {}
            for name, _s, d in line["events"]:
                names[name] = names.get(name, 0.0) + d
            top = sorted(names.items(), key=lambda kv: -kv[1])[:4]
            out.append(
                f"{plane['name']} | {line['name']} | {len(line['events'])} events | "
                + ", ".join(f"{n[:40]}={d / 1e9:.6f}s" for n, d in top)
            )
    return out


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def complement(intervals, lo: float, hi: float) -> list:
    out, at = [], lo
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PLANE_PREFIX)]


def _line_events(plane: dict, line_name: str) -> list:
    return [e for ln in plane["lines"] if ln["name"] == line_name for e in ln["events"]]


def busy_intervals(plane: dict) -> list:
    """Union of the intervals in which an operation ran on the device."""
    return union([s, s + d] for _n, s, d in _line_events(plane, OPS_LINE))


def busy_seconds(trace: dict) -> list:
    """Busy seconds of each device, in plane order."""
    return [total(busy_intervals(p)) / 1e9 for p in device_planes(trace)]


def module_seconds(trace: dict, module: str):
    """Device seconds of the XLA module ``module`` (its name up to the
    ``(`` of the run id) -> (seconds on the busiest device, runs there);
    (None, 0) when the module never ran."""
    best = (None, 0)
    for plane in device_planes(trace):
        durs = [
            d for n, _s, d in _line_events(plane, MODULES_LINE)
            if n.split("(")[0] == module
        ]
        if durs and (best[0] is None or sum(durs) / 1e9 > best[0]):
            best = (sum(durs) / 1e9, len(durs))
    return best


def short_op_name(name: str) -> str:
    """``%add_xor_fusion = u32[...] fusion(...)`` -> ``add_xor_fusion``; a
    custom call keeps its target (``custom-call:X64SplitHigh``)."""
    short = name.split(" = ")[0].lstrip("%")
    if 'custom_call_target="' in name:
        short += ":" + name.split('custom_call_target="')[1].split('"')[0]
    return short


def top_device_ops(trace: dict, n: int = 10) -> list:
    """[[name, seconds]] of the operations that took most device time,
    summed over the devices."""
    acc = {}
    for plane in device_planes(trace):
        for name, _s, d in _line_events(plane, OPS_LINE):
            name = short_op_name(name)
            acc[name] = acc.get(name, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def host_events(trace: dict) -> list:
    """[[name, start, end]] of the host spans kept (``SPAN_PREFIXES``)."""
    return [
        [name, s, s + d]
        for plane in trace["planes"] if not plane["name"].startswith(DEVICE_PLANE_PREFIX)
        for line in plane["lines"]
        for name, s, d in line["events"] if name.startswith(SPAN_PREFIXES)
    ]


def innermost_cover(gaps: list, events: list) -> dict:
    """{span name: length} of the merged intervals ``gaps``, each piece
    given to the one span that covers it and started last (the innermost
    of nested spans; of two threads' spans, the later); what no span
    covers goes to ``NO_SPAN``. The parts add up to the gaps' length."""
    acc = {}
    for lo, hi in gaps:
        inside = [e for e in events if e[1] < hi and e[2] > lo]
        cuts = sorted({lo, hi} | {min(max(t, lo), hi) for e in inside for t in e[1:]})
        for a, b in zip(cuts, cuts[1:]):
            over = [e for e in inside if e[1] <= a and e[2] >= b]
            name = max(over, key=lambda e: e[1])[0] if over else NO_SPAN
            acc[name] = acc.get(name, 0.0) + (b - a)
    return acc


def idle_gaps(trace: dict, window_ns: float, n: int = 10) -> list:
    """[[what the host was doing, idle seconds]]: the busiest device's idle
    time inside [0, window_ns), each piece given to the innermost host
    span that covers it: a stage of the program (``hs.scan``) before the
    benchmark's span around the whole call (``bench.create_index``), which
    keeps what no stage covers."""
    planes = device_planes(trace)
    busy = max((busy_intervals(p) for p in planes), key=total, default=[])
    gaps = complement(busy, 0.0, window_ns)
    parts = innermost_cover(gaps, host_events(trace))
    parts.setdefault(NO_SPAN, 0.0)
    return [[k, v / 1e9] for k, v in sorted(parts.items(), key=lambda kv: -kv[1])[:n]]
