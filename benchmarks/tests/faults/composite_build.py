"""The faults of a covering build on a two-column key (driver
``composite_build``): an answer altered where it is produced, and the
SECOND key left out — of one bucket file's order, and of the hash. A
check that looked at the first key alone would pass the last two."""

import threading

from faults import Fault
from faults.build_loop import altered_value  # one float payload changed where the build produces its rows


def _values(column):
    """A table column as numpy, without a copy where it is one chunk."""
    return column.chunk(0).to_numpy() if column.num_chunks == 1 else column.to_numpy()


def second_key_unsorted() -> None:
    """ONE bucket file of every build written in the first key's order
    with each first key's second keys reversed: sorted on ``l_partkey``,
    not on the pair. The file is the first handed to a writer that holds
    a part with two of its suppliers (at a small size most buckets hold
    none); whole rows move, so every answer keeps its rows. A build is
    known by the table its writers share: a version directory's name
    comes again after a vacuum."""
    import weakref

    import numpy as np

    from hyperspace_tpu.io import parquet as pio

    real = pio.write_bucket_file
    lock, last = threading.Lock(), [lambda: None]    # the table planted last, weakly

    def broken(out_dir, bucket, file_idx_offset, table, idx, *rest):
        if last[0]() is not table:
            first, second = (_values(table.column(c))[idx] for c in ("l_partkey", "l_suppkey"))
            if np.any((first[1:] == first[:-1]) & (second[1:] != second[:-1])):
                with lock:
                    mine = last[0]() is not table
                    last[0] = weakref.ref(table)
                if mine:
                    idx = idx[np.lexsort((-second, first))]
        return real(out_dir, bucket, file_idx_offset, table, idx, *rest)

    pio.write_bucket_file = broken


def second_key_unhashed() -> None:
    """Every row sent to the bucket its FIRST key alone hashes to."""
    from hyperspace_tpu.indexes import covering_build as cb
    from hyperspace_tpu.ops.hash import bucket_ids_host

    real = cb._hash_shuffle

    def broken(ctx, batch, indexed_cols, num_buckets):
        _buckets, reps, batch, offs = real(ctx, batch, indexed_cols, num_buckets)
        return bucket_ids_host(reps[:1], num_buckets), reps, batch, offs

    cb._hash_shuffle = broken


FAULTS = {
    "altered_value": Fault(altered_value, frozenset({"readback_digest_differs"}),
                           frozenset({"point_answers_wrong"})),
    "second_key_unsorted": Fault(second_key_unsorted, frozenset({"unsorted_bucket_files"})),
    # hashing 400 parts alone, as a rehearsal's table has them, leaves
    # buckets empty and their files unwritten; 533,333 parts leave none
    "second_key_unhashed": Fault(second_key_unhashed, frozenset({"misbucketed_rows"}),
                                 frozenset({"point_answers_wrong", "bucket_files_gap"})),
}
