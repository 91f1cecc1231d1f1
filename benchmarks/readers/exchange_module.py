"""Device time of the mesh exchange's program, from the profiler's trace.

The program is the jitted one of whichever strategy the builds of the
window resolved to (``shuffle_strategy`` in each operation's telemetry);
its time is read on the busiest chip's "XLA Modules" line.

arg: {"kind": <operation kind>, "stat": "seconds_per_op"} -> device
seconds of the program per operation of the window; {"stat":
"roofline"} -> the least time the chips could take for those operations'
exchanges (``roofline_exchange.py``: the interconnect bounds it) over
the program's device time, in %. Nothing where there is no trace, no
exchange with a device program (one chip; the ``host`` strategy; a
program that keeps no telemetry), or the program never ran.
"""

import roofline_exchange
import trace_reduce

MODULES = {
    "compact": "jit__compact_program",
    "twostage": "jit__twostage_program",
}


def read(record: dict, arg: dict):
    if record["trace"] is None:
        return None
    ops = [o for o in record["ops"] if o["kind"] == arg["kind"]]
    strategies = {(o.get("telemetry") or {}).get("shuffle_strategy") for o in ops}
    if len(strategies) != 1 or not strategies <= set(MODULES):
        return None
    seconds, _runs = trace_reduce.module_seconds(record["trace"], MODULES[strategies.pop()])
    if not seconds:
        return None
    if arg["stat"] == "seconds_per_op":
        return seconds / len(ops)
    config, device = record["config"], record["device"]
    n_bytes = len(ops) * roofline_exchange.bytes_out_of_a_chip(
        record["rows"], device["count"], roofline_exchange.index_row_bytes(config))
    return 100.0 * roofline_exchange.least_seconds(n_bytes, device["kind"]) / seconds
