"""The faults of a covering build (driver ``build_loop``): an answer
altered where it is produced, a row in the wrong bucket file, a bucket
file left unsorted."""

from faults import Fault


def altered_value() -> None:
    """One float payload changed where the build produces its rows."""
    from hyperspace_tpu.indexes import covering_build as cb

    real = cb._hash_shuffle

    def broken(ctx, batch, indexed_cols, num_buckets):
        buckets, reps, batch, offs = real(ctx, batch, indexed_cols, num_buckets)
        col = batch.columns["l_extendedprice"]
        col.values = col.values.copy()
        col.values[0] += 1.0
        return buckets, reps, batch, offs

    cb._hash_shuffle = broken


def misbucketed() -> None:
    """Every seventh row sent to the bucket after its own."""
    from hyperspace_tpu.indexes import covering_build as cb

    real = cb._hash_shuffle

    def broken(ctx, batch, indexed_cols, num_buckets):
        buckets, reps, batch, offs = real(ctx, batch, indexed_cols, num_buckets)
        buckets = buckets.copy()
        buckets[::7] = (buckets[::7] + 1) % num_buckets
        return buckets, reps, batch, offs

    cb._hash_shuffle = broken


def unsorted() -> None:
    """The rows of bucket 3 written in reverse order."""
    from hyperspace_tpu.io import parquet as pio

    real = pio.write_bucket_file

    def broken(out_dir, bucket, file_idx_offset, table, idx, use_dictionary):
        if bucket == 3:
            idx = idx[::-1].copy()
        return real(out_dir, bucket, file_idx_offset, table, idx, use_dictionary)

    pio.write_bucket_file = broken


FAULTS = {
    "altered_value": Fault(altered_value, frozenset({"readback_digest_differs"}),
                           frozenset({"point_answers_wrong"})),
    "misbucketed": Fault(misbucketed, frozenset({"misbucketed_rows"})),
    "unsorted": Fault(unsorted, frozenset({"unsorted_bucket_files"})),
}
