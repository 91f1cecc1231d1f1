"""Device time of one XLA module, from the profiler's trace.

arg: {"module": <name up to the "(">, "kind": <operation kind>,
"stat": "seconds_per_op"} -> device seconds of the module per operation
of the window; {"stat": "roofline", "bytes_fn": <function>, "bytes_from":
<module under benchmarks/, default ``roofline``>} -> the least time the
chip could take for the bytes of those operations, over the module's
device time, in %. The function is found by name and handed (rows,
configuration), so a new kernel brings its bytes in a ``roofline_*.py``
of its own; the chip's rates stay ``roofline.least_seconds`` and
``peaks.json``. Nothing where the module never ran.
"""

import importlib

import roofline
import trace_reduce


def read(record: dict, arg: dict):
    if record["trace"] is None:
        return None
    seconds, runs = trace_reduce.module_seconds(record["trace"], arg["module"])
    ops = [o for o in record["ops"] if o["kind"] == arg["kind"]]
    if not seconds or not ops:
        return None
    if arg["stat"] == "seconds_per_op":
        return seconds / len(ops)
    bytes_fn = getattr(importlib.import_module(arg.get("bytes_from", "roofline")), arg["bytes_fn"])
    n_bytes = bytes_fn(record["rows"], record["config"]) * len(ops)
    return 100.0 * roofline.least_seconds(n_bytes, record["device"]["kind"]) / seconds
