"""Device sort — the sort-within-bucket step of the covering index build.

Reference: the bucketed *sorted* write in
``index/DataFrameWriterExtensions.scala:58-67`` (Spark sorts each bucket by
the indexed columns before writing). Here the whole shard is sorted by
``(bucket_id, key_0, key_1, …)`` in one XLA lexsort; the per-bucket runs
are then contiguous and each bucket's parquet file is written from a slice.

Sorting uses int64 key reps (``io/columnar.py``): an arbitrary-but-
consistent total order, which is exactly what bucketed sort-merge joins
need (both sides sort by the same function of the key values;
``JoinIndexRule.scala:619-634``). Like the hash kernel, comparisons run on
32-bit planes (TPU-native): each int64 key becomes (hi ^ signbit as uint32
major, lo uint32 minor), which orders identically to signed int64.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import hyperspace_tpu.ops  # noqa: F401  (enables x64)
from hyperspace_tpu.obs import trace as _obs_trace

_SIGN = np.uint32(0x80000000)

# Below this row count lexsort runs as numpy on host (identical stable
# semantics); the device sort pays transfer + readback for HOST-RESIDENT
# batches. The value was set from a round-5 measurement on a different
# host attachment (4M-row single-key build lexsort: 0.9s host numpy
# radix vs 3.7s device incl. transfer) and has NOT been re-measured on
# this machine; chip_smoke.py prints what the calibration probe reads
# there. The device kernel's home is HBM-resident data on a sharded mesh,
# not host-resident builds.
#
# FALLBACK DEFAULT: the effective threshold comes from the per-machine
# calibration probe (hyperspace_tpu/native/calibrate.py) when available;
# this constant is used when calibration is disabled (HS_CALIBRATE=0),
# has not produced a measurement, or when a test overrides the module
# attribute directly (an override always wins — see _host_sort_max_rows).
_HOST_SORT_MAX_ROWS_DEFAULT = 1 << 26
_HOST_SORT_MAX_ROWS = _HOST_SORT_MAX_ROWS_DEFAULT

# At or above this row count the host path prefers the native C++ radix
# lexsort (hyperspace_tpu/native): one adaptive LSD radix over all planes
# with constant-byte pass skipping, measured 3.3x over np.lexsort at the
# 4M-row bench shape (bit-identical stable output). Below it numpy's
# overhead is already microseconds and a first native call would pay the
# one-time g++ compile for nothing. Fallback default; see above.
_NATIVE_SORT_MIN_ROWS_DEFAULT = 1 << 15
_NATIVE_SORT_MIN_ROWS = _NATIVE_SORT_MIN_ROWS_DEFAULT

# Same idea for the counting-scatter partition kernel. Its crossover is
# NOT the lexsort's: the kernel is O(n) with two sequential passes and
# near-zero per-row work, so ctypes/threading overhead amortizes much
# earlier than for the radix sort. Calibrated separately (see
# native/calibrate.py); fallback default below.
_NATIVE_PARTITION_MIN_ROWS_DEFAULT = 1 << 15
_NATIVE_PARTITION_MIN_ROWS = _NATIVE_PARTITION_MIN_ROWS_DEFAULT


def _host_sort_max_rows() -> int:
    if _HOST_SORT_MAX_ROWS != _HOST_SORT_MAX_ROWS_DEFAULT:
        return _HOST_SORT_MAX_ROWS  # explicit (test/ops) override wins
    from hyperspace_tpu.native import calibrate

    return calibrate.thresholds().host_sort_max_rows or _HOST_SORT_MAX_ROWS


def _native_sort_min_rows() -> int:
    if _NATIVE_SORT_MIN_ROWS != _NATIVE_SORT_MIN_ROWS_DEFAULT:
        return _NATIVE_SORT_MIN_ROWS
    from hyperspace_tpu.native import calibrate

    return (
        calibrate.thresholds().native_sort_min_rows or _NATIVE_SORT_MIN_ROWS
    )


def _native_partition_min_rows() -> int:
    if _NATIVE_PARTITION_MIN_ROWS != _NATIVE_PARTITION_MIN_ROWS_DEFAULT:
        return _NATIVE_PARTITION_MIN_ROWS
    from hyperspace_tpu.native import calibrate

    return (
        calibrate.thresholds().native_partition_min_rows
        or _NATIVE_PARTITION_MIN_ROWS
    )


def _order_words_numpy(key_reps: np.ndarray) -> np.ndarray:
    """The pure-numpy leg of :func:`_order_words_np`, never dispatching
    to the native pass: its bit-exact twin."""
    u = np.ascontiguousarray(key_reps).view(np.uint64)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = ((u >> np.uint64(32)).astype(np.uint32)) ^ _SIGN  # flip sign bit
    return np.stack([w for pair in zip(hi, lo) for w in pair])


def _order_words_np(key_reps: np.ndarray) -> np.ndarray:
    """[k, n] int64 -> [2k, n] uint32 planes whose lexicographic order
    (row 0 major) equals signed-int64 order of the keys.

    At or above the native partition threshold (a plain O(n) pass, like
    the counting scatter it runs beside) one native pass a key column
    writes the planes (``native.split_words_i64``: the hash's word
    split with the sign bit flipped and the high plane first); below it,
    or where the library is not loaded or a key row is not contiguous
    8-byte integers, :func:`_order_words_numpy`. A build's ``partition``
    span, where it is the span live in the caller, gets ``native`` 1 | 0
    for which of the two ran (no other span does: the serve path's
    sorts come through here too)."""
    k, n = key_reps.shape
    ran_native = False
    if k and n >= _native_partition_min_rows():
        from hyperspace_tpu import native

        planes = np.empty((2 * k, n), dtype=np.uint32)
        ran_native = native.split_words_i64(
            key_reps, planes, hi_xor=int(_SIGN), hi_first=True
        )
    if not ran_native:
        planes = _order_words_numpy(key_reps)
    sp = _obs_trace.current()
    if sp is not None and sp.name == "partition":
        sp.set("native", int(ran_native))
    return planes


@jax.jit
def lexsort_indices(word_planes):
    """[m, n] uint32 -> [n] permutation; primary key = row 0.

    ``jnp.lexsort`` treats the *last* row as primary, so reverse.
    """
    return jnp.lexsort(word_planes[::-1])


def lexsort_perm(
    planes: np.ndarray,
    n_valid: int | None = None,
    n_threads: int | None = None,
) -> np.ndarray:
    """Host dispatch of :func:`lexsort_indices` at a padded static shape.

    Pads the row dimension to ``pad_len`` with ``0xFFFFFFFF`` in every
    plane (the ops/__init__ shape policy: one compile per 2x size band).
    Pad slots sort after every real row: their key is the maximum in all
    planes and ``jnp.lexsort`` is stable, so a real row that ties still
    precedes them (its index is smaller). The first ``n_valid`` outputs
    are therefore exactly the sorted real rows.

    ``n_threads`` caps the native kernel's thread count — the partitioned
    build runs many per-bucket sorts concurrently and hands each a slice
    of the core budget instead of letting every sort claim the machine.

    Under a live trace the device arm is three spans — ``h2d``,
    ``kernel`` (dispatch to ``block_until_ready``), ``d2h`` — with the
    bytes each way counted on the root; the host arms open none.
    """
    from hyperspace_tpu.ops import pad_len

    n = planes.shape[1] if n_valid is None else n_valid
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    planes = planes.astype(np.uint32, copy=False)
    if planes.shape[1] <= _host_sort_max_rows():
        # host lexsort: same stable semantics, no device round trip
        # (host-resident serve batches pay transfer + readback otherwise)
        if planes.shape[1] >= _native_sort_min_rows():
            from hyperspace_tpu import native

            perm = native.lexsort_u32(planes, n_threads=n_threads)
            if perm is not None:
                return perm[:n]
        return np.lexsort(planes[::-1])[:n]
    n_pad = pad_len(planes.shape[1])
    if n_pad != planes.shape[1]:
        fill = np.full(
            (planes.shape[0], n_pad - planes.shape[1]),
            np.uint32(0xFFFFFFFF),
        )
        planes = np.concatenate([planes, fill], axis=1)
    with _obs_trace.span("h2d", bytes=int(planes.nbytes)):
        on_device = jax.block_until_ready(jnp.asarray(planes))
    with _obs_trace.span("kernel"):
        order = jax.block_until_ready(lexsort_indices(on_device))
    with _obs_trace.span("d2h", bytes=int(order.nbytes)):
        perm = np.asarray(order)
    _obs_trace.accumulate("h2d_bytes", int(planes.nbytes))
    _obs_trace.accumulate("d2h_bytes", int(perm.nbytes))
    return perm[:n]


def sort_permutation(
    key_reps: np.ndarray, bucket: np.ndarray | None = None
) -> np.ndarray:
    """Host entry: permutation sorting rows by (bucket, key_reps...)."""
    planes = _order_words_np(key_reps.astype(np.int64, copy=False))
    if bucket is not None:
        planes = np.concatenate(
            [bucket.astype(np.uint32)[None, :], planes]
        )
    return lexsort_perm(planes)


# ---------------------------------------------------------------------------
# Partition-first build sort (locality-aware alternative to the global
# (bucket, keys) lexsort — the 64M-row sort collapse fix)
# ---------------------------------------------------------------------------


def partition_by_bucket(
    bucket_ids: np.ndarray, num_buckets: int
) -> tuple[np.ndarray, np.ndarray]:
    """Stable partition of row indices by bucket id: ``(order, offsets)``
    with bucket ``b``'s rows at ``order[offsets[b]:offsets[b+1]]`` in
    original order. Native counting-scatter kernel
    (``hs_partition_by_bucket``: sequential histogram + per-cursor
    sequential writes) above the native dispatch threshold, bit-exact
    numpy twin (stable argsort + bincount prefix sum) below or when the
    kernel is unavailable."""
    bucket_ids = np.ascontiguousarray(bucket_ids, dtype=np.int32)
    n = len(bucket_ids)
    if n >= _native_partition_min_rows():
        from hyperspace_tpu import native

        got = native.partition_by_bucket_i32(bucket_ids, num_buckets)
        if got is not None:
            return got
    return partition_by_bucket_numpy(bucket_ids, num_buckets)


def partition_by_bucket_numpy(
    bucket_ids: np.ndarray, num_buckets: int
) -> tuple[np.ndarray, np.ndarray]:
    """The pure-numpy leg of :func:`partition_by_bucket` (stable argsort
    + bincount prefix sum), never dispatching to the native kernel —
    also the reference the calibration probe times the native
    counting-scatter against."""
    counts = np.bincount(bucket_ids, minlength=num_buckets)
    offsets = np.concatenate(
        [np.zeros(1, dtype=np.int64), np.cumsum(counts, dtype=np.int64)]
    )
    return np.argsort(bucket_ids, kind="stable").astype(np.int64), offsets


def _sort_pool_plan(n_buckets: int) -> tuple[int, int]:
    """(pool workers, native threads per sort) splitting the core budget
    across concurrent per-bucket sorts."""
    from hyperspace_tpu import native

    budget = native.core_budget()
    workers = max(1, min(budget, n_buckets))
    return workers, max(1, budget // workers)


def bucket_key_sort_runs(
    planes: np.ndarray,
    order: np.ndarray,
    offsets: np.ndarray,
    workers: int | None = None,
    n_threads: int | None = None,
    seconds_out: list | None = None,
):
    """Per-bucket stable key sorts over a partitioned order — yields
    ``(bucket, final_indices)`` in ascending bucket id as each bucket's
    sort completes, running the sorts on a thread pool.

    ``planes`` are the key order-words in ORIGINAL row order; bucket
    ``b``'s rows are gathered (``planes[:, idx]``, a working set of ~one
    bucket instead of the whole table) and lexsorted WITHOUT the bucket
    plane (constant within a bucket). Ties keep ``idx`` order, and
    ``idx`` is ascending, so ``idx[perm]`` reproduces exactly the global
    stable lexsort by (bucket, keys...) restricted to bucket ``b``.

    ``workers``/``n_threads`` override the core-budget split — the
    sharded tail runs one of these loops PER SHARD concurrently
    (``workers=1``, the shard thread is the concurrency unit) and hands
    each shard a slice of the native-sort thread budget.

    ``seconds_out``, when given, receives each bucket's (sort seconds,
    CPU seconds of the sorting thread over them), appended from the
    sorting threads — the build's trace keeps their count, sums and max
    on ONE span instead of a span per bucket.
    """
    import time
    from concurrent.futures import ThreadPoolExecutor

    nonempty = [
        b for b in range(len(offsets) - 1) if offsets[b + 1] > offsets[b]
    ]
    if not nonempty:
        return
    if workers is None:
        workers, threads = _sort_pool_plan(len(nonempty))
    else:
        threads = max(1, n_threads or 1)

    def sort_one(b: int) -> np.ndarray:
        t0, cpu0 = time.perf_counter(), time.thread_time_ns()
        idx = order[offsets[b] : offsets[b + 1]]
        perm = lexsort_perm(
            np.ascontiguousarray(planes[:, idx]), n_threads=threads
        )
        out = idx[perm]
        if seconds_out is not None:
            seconds_out.append(
                (
                    time.perf_counter() - t0,
                    (time.thread_time_ns() - cpu0) / 1e9,
                )
            )
        return out

    if workers == 1:
        for b in nonempty:
            yield b, sort_one(b)
        return
    with ThreadPoolExecutor(max_workers=workers) as pool:
        futures = [(b, pool.submit(sort_one, b)) for b in nonempty]
        for b, fut in futures:
            yield b, fut.result()


def partitioned_sort_permutation(
    key_reps: np.ndarray, bucket: np.ndarray, num_buckets: int
) -> np.ndarray:
    """Bit-identical to ``sort_permutation(key_reps, bucket)`` (stable
    lexsort by (bucket, keys...)) computed partition-first: one counting
    scatter groups rows by bucket, then each bucket is key-sorted
    independently on a thread pool with a working set of
    ~rows/num_buckets. The 64M-row global lexsort's permutation gathers
    walk the entire multi-hundred-MB working set per radix pass
    (TLB-bound — BASELINE.md); per-bucket sorts keep each pass resident.
    """
    order, offsets = partition_by_bucket(bucket, num_buckets)
    planes = _order_words_np(key_reps.astype(np.int64, copy=False))
    out = np.empty(len(order), dtype=np.int64)
    for b, final_idx in bucket_key_sort_runs(planes, order, offsets):
        out[offsets[b] : offsets[b + 1]] = final_idx
    return out


def shard_tail_plan(shard_offsets: np.ndarray) -> tuple[list, int]:
    """(non-empty shards, native threads per shard) for the sharded
    build tail: shards are the concurrency unit, each gets an equal
    slice of the core budget for its in-shard native sorts."""
    from hyperspace_tpu import native

    shards = [
        s
        for s in range(len(shard_offsets) - 1)
        if shard_offsets[s + 1] > shard_offsets[s]
    ]
    budget = native.core_budget()
    return shards, max(1, budget // max(len(shards), 1))


def sharded_sort_permutation(
    key_reps: np.ndarray,
    bucket: np.ndarray,
    num_buckets: int,
    shard_offsets: np.ndarray,
) -> np.ndarray:
    """The device-local twin of :func:`partitioned_sort_permutation`:
    each mesh shard's post-exchange slice (``shard_offsets[s] :
    shard_offsets[s+1]``, exactly the buckets that shard owns) runs its
    own counting scatter + per-bucket key sorts CONCURRENTLY with the
    other shards', so sort working set and thread occupancy scale with
    the shard count instead of serializing through one permutation over
    the full batch.

    Output row order is shard-major (shard 0's buckets ascending, then
    shard 1's, …), NOT the globally bucket-ascending order of the
    single-tail sort — but every bucket lives wholly inside one shard
    slice, so each bucket's rows and their stable key-sorted order are
    bit-identical to the global sort restricted to that bucket, which is
    the only order the bucketed writers observe (one file per bucket).
    """
    from concurrent.futures import ThreadPoolExecutor

    planes = _order_words_np(key_reps.astype(np.int64, copy=False))
    n = int(shard_offsets[-1])
    out = np.empty(n, dtype=np.int64)
    shards, threads = shard_tail_plan(shard_offsets)
    if not shards:
        return out

    def run_shard(s: int) -> None:
        lo, hi = int(shard_offsets[s]), int(shard_offsets[s + 1])
        order, offsets = partition_by_bucket(bucket[lo:hi], num_buckets)
        order += lo  # global row coordinates for the planes gather
        pos = lo
        for _b, final_idx in bucket_key_sort_runs(
            planes, order, offsets, workers=1, n_threads=threads
        ):
            out[pos : pos + len(final_idx)] = final_idx
            pos += len(final_idx)

    if len(shards) == 1:
        run_shard(shards[0])
        return out
    with ThreadPoolExecutor(
        max_workers=len(shards), thread_name_prefix="hs-shardsort"
    ) as pool:
        list(pool.map(run_shard, shards))
    return out


# ---------------------------------------------------------------------------
# User-facing ORDER BY (value order, not key-rep order)
# ---------------------------------------------------------------------------


def order_rep(col) -> np.ndarray:
    """int64 rep whose signed order equals the column's VALUE order.

    Unlike ``Column.key_rep`` (arbitrary-but-consistent order, hash for
    strings), this is order-preserving: ints/temporal as-is, uints via
    sign-bit xor, floats via the IEEE-754 total-order trick (NaN sorts
    after +inf, matching numpy/pyarrow), strings via per-batch dictionary
    rank. Null placement is handled by the caller (``ordering_permutation``
    adds a null plane), so nulls here get an arbitrary in-band value.
    """
    if col.kind == "string":
        order = sorted(range(len(col.dictionary)), key=col.dictionary.__getitem__)
        rank = np.empty(max(len(col.dictionary), 1), dtype=np.int64)
        for r, i in enumerate(order):
            rank[i] = r
        return rank[np.maximum(col.codes, 0)].astype(np.int64)
    v = col.values
    if v.dtype.kind == "f":
        # IEEE-754 total order as SIGNED int64: positives keep their bit
        # pattern; negatives complement the magnitude bits (sign bit stays,
        # so they remain negative and larger magnitudes sort lower).
        u = v.astype(np.float64).view(np.uint64)
        rep = np.where(
            u >> np.uint64(63) == 1,
            u ^ np.uint64(0x7FFFFFFFFFFFFFFF),
            u,
        )
        return rep.view(np.int64)
    if v.dtype.kind == "u":
        return (
            v.astype(np.uint64) ^ np.uint64(0x8000000000000000)
        ).view(np.int64)
    if v.dtype.kind == "b":
        return v.astype(np.int64)
    return v.astype(np.int64)


def ordering_permutation(batch, keys) -> np.ndarray:
    """Stable permutation ordering ``batch`` by ``keys`` =
    ((column, ascending), ...). Nulls always sort last (pyarrow's
    ``null_placement="at_end"``), and NaN always sorts after every other
    value but before nulls — in BOTH directions, like pyarrow's sort_by.
    Descending flips values only, never the null/NaN placement."""
    planes = []
    for name, asc in keys:
        col = batch.column(name)
        rep = order_rep(col)
        if not asc:
            rep = ~rep  # bitwise complement reverses signed order
        null = col.null_mask
        null_plane = (
            np.zeros(len(col), dtype=np.uint32)
            if null is None
            else null.astype(np.uint32)
        )
        planes.append(null_plane)
        if col.kind == "numeric" and col.values.dtype.kind == "f":
            # direction-independent NaN plane (pyarrow: NaN after values)
            planes.append(np.isnan(col.values).astype(np.uint32))
        planes.extend(_order_words_np(rep[None, :]))
    return lexsort_perm(np.stack(planes))
