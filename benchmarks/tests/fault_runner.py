"""Drive one run of a cell with the timed path broken underneath:
``python fault_runner.py <fault> <run.py arguments>``.

Faults (each breaks the program, never the benchmark):
  altered_value   one float payload changed where the build produces its rows
  misbucketed     every seventh row sent to the bucket after its own
  unsorted        the rows of bucket 3 written in reverse order
  none            nothing broken

With ``--cpu-rehearsal --rows N`` among the arguments it runs here at a
tiny size; without them, on the chip at the cell's own size.
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


def plant(fault: str) -> None:
    if fault == "altered_value":
        from hyperspace_tpu.indexes import covering_build as cb

        real = cb._hash_shuffle

        def broken(ctx, batch, indexed_cols, num_buckets):
            buckets, reps, batch, offs = real(ctx, batch, indexed_cols, num_buckets)
            col = batch.columns["l_extendedprice"]
            col.values = col.values.copy()
            col.values[0] += 1.0
            return buckets, reps, batch, offs

        cb._hash_shuffle = broken
    elif fault == "misbucketed":
        from hyperspace_tpu.indexes import covering_build as cb

        real = cb._hash_shuffle

        def broken(ctx, batch, indexed_cols, num_buckets):
            buckets, reps, batch, offs = real(ctx, batch, indexed_cols, num_buckets)
            buckets = buckets.copy()
            buckets[::7] = (buckets[::7] + 1) % num_buckets
            return buckets, reps, batch, offs

        cb._hash_shuffle = broken
    elif fault == "unsorted":
        from hyperspace_tpu.io import parquet as pio

        real = pio.write_bucket_file

        def broken(out_dir, bucket, file_idx_offset, table, idx, use_dictionary):
            if bucket == 3:
                idx = idx[::-1].copy()
            return real(out_dir, bucket, file_idx_offset, table, idx, use_dictionary)

        pio.write_bucket_file = broken
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault, argv = sys.argv[1], sys.argv[2:]
    import run

    # run.main sets the platform before jax is imported; the fault is
    # planted by the harness's first step after that
    import harness

    real_device_info = harness.device_info

    def device_info_then_plant():
        info = real_device_info()
        plant(fault)
        return info

    harness.device_info = device_info_then_plant
    sys.exit(run.main(argv))
