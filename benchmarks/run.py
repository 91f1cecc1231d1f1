"""python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process runs one cell of ``BENCHMARK.json``: data and warm-up from the
seed (set-up), ``--seconds`` of the cell's traffic, the comparison with
the plain reference, then one JSON object as the last line of stdout.
Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result. ``--cpu-rehearsal --rows N`` runs the same path at
a tiny size on the CPU to debug it; its line says so and never carries a
chip's name. ``--controls 1`` also puts the reference's deliberately
broken answers in the program's place and reports that they fail.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--controls", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--rows", type=int, default=0,
                    help="lineitem rows of a --cpu-rehearsal")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a whole number from 0")
    if args.cpu_rehearsal != (args.rows > 0):
        ap.error("--rows goes with --cpu-rehearsal, and only with it")

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness

    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, args.workload)
    if args.cpu_rehearsal:
        # before jax is imported: hold it to the CPU, with as many host
        # devices as the cell has chips
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if cell["chips"] > 1 and "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={cell['chips']}"
            ).strip()
    seconds = args.seconds if args.seconds is not None else manifest["run_seconds"]
    code, result = harness.run_cell(
        args.workload, args.seed, seconds, bool(args.trace), T_START,
        rehearsal_rows=args.rows, controls=bool(args.controls), manifest=manifest,
    )
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
