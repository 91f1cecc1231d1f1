"""The fault of a covering build on ORDERS (driver ``sketch_orders``): a
string payload altered where the build writes it."""

from faults import Fault


def altered_comment() -> None:
    """The ``o_comment`` of the batch's first row changed in the bucket
    file that holds it: one string a build."""
    import numpy as np
    import pyarrow as pa

    from hyperspace_tpu.io import parquet as pio

    real = pio.write_bucket_file

    def broken(out_dir, bucket, file_idx_offset, table, idx, use_dictionary):
        at = np.flatnonzero(idx == 0)
        if len(at):
            sub = table.take(pa.array(idx))
            i = sub.column_names.index("o_comment")
            comments = sub.column(i).to_pylist()
            comments[int(at[0])] += "!"
            sub = sub.set_column(i, sub.field(i), pa.array(comments, type=sub.field(i).type))
            table, idx = sub, np.arange(len(idx))
        return real(out_dir, bucket, file_idx_offset, table, idx, use_dictionary)

    pio.write_bucket_file = broken


FAULTS = {"altered_comment": Fault(altered_comment, frozenset({"readback_digest_differs"}),
                                   frozenset({"point_answers_wrong"}))}
