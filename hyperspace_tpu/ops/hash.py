"""Device bucket hashing — MurmurHash3 as an XLA kernel.

TPU-native replacement for Spark's hash-partitioning shuffle key
(``HashPartitioning``/``Murmur3Hash``) used by the covering-index build
(reference: ``index/covering/CoveringIndex.scala:58-61`` —
``repartition(numBuckets, indexedCols)``). Bucket assignment must be a pure
function of the key *values* so that build, incremental refresh and
query-time Hybrid Scan shuffles all agree on the layout
(``CoveringIndexRuleUtils.scala:357-417`` re-shuffles appended data with the
same partitioning).

The kernel is pure 32-bit arithmetic (TPU VPU-native): each int64 key rep
(see ``io/columnar.py``) is split into lo/hi uint32 words and hashed as the
corresponding 8 little-endian bytes; multiple key columns extend the block
stream. The result equals host ``murmur3_32_bytes(b"".join(rep_i 8-byte
LE))`` — tested against the scalar reference implementation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import hyperspace_tpu.ops  # noqa: F401  (enables x64)
from hyperspace_tpu.obs import trace as _obs_trace
from hyperspace_tpu.ops import pad_len

_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)


def _rotl32(x, r):
    return (x << r) | (x >> (32 - r))


def _mix_k1(k1):
    k1 = k1 * _C1
    k1 = _rotl32(k1, 15)
    return k1 * _C2


def _mix_h1(h1, k1):
    h1 = h1 ^ k1
    h1 = _rotl32(h1, 13)
    return h1 * np.uint32(5) + np.uint32(0xE6546B64)


def _fmix(h1, length):
    h1 = h1 ^ length
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * np.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * np.uint32(0xC2B2AE35)
    return h1 ^ (h1 >> 16)


def split_words_np(key_reps: np.ndarray) -> np.ndarray:
    """Host split: [k, n] int64 -> [2k, n] uint32 (lo, hi interleaved)."""
    u = np.ascontiguousarray(key_reps).view(np.uint64)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (u >> np.uint64(32)).astype(np.uint32)
    return np.stack([w for lohi in zip(lo, hi) for w in lohi])


def split_words(key_reps):
    """Device split: [k, n] int64 -> [2k, n] uint32 (lo, hi interleaved)."""
    u = key_reps.astype(jnp.uint64)
    lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
    return jnp.concatenate(
        [jnp.stack([lo[i], hi[i]]) for i in range(key_reps.shape[0])]
    )


def hash_words(words, seed):
    """murmur3-32 over [2k, n] uint32 word blocks -> uint32 [n]."""
    h = jnp.broadcast_to(jnp.uint32(seed), words.shape[1:]).astype(jnp.uint32)
    for i in range(words.shape[0]):
        h = _mix_h1(h, _mix_k1(words[i]))
    return _fmix(h, jnp.uint32(4 * words.shape[0]))


def hash_columns(key_reps, seed: int = 42):
    """[num_keys, n] int64 key reps -> uint32 [n] (splits to words first)."""
    return hash_words(split_words(key_reps), seed)


@functools.partial(jax.jit, static_argnames=("num_buckets", "seed"))
def _bucket_ids_words(words, num_buckets: int, seed: int):
    return (hash_words(words, seed) % jnp.uint32(num_buckets)).astype(jnp.int32)


# Below this row count the hash runs as plain numpy: the mix functions
# are dtype-generic (np.uint32 arithmetic works identically on numpy and
# jnp arrays — bit-exact by construction), and a device dispatch costs a
# host->device->host round trip for HOST-RESIDENT inputs. The value was
# set from a round-5 measurement on a different host attachment (3.4s
# device vs 0.15s host at 4M rows, ~64ms to hash ONE bucket-pruning
# literal) and has NOT been re-measured on this machine; chip_smoke.py
# prints what the calibration probe reads there. The device kernel's
# home is HBM-resident data on a sharded mesh (parallel/shuffle.py), not
# host-resident builds.
#
# FALLBACK DEFAULT: the effective threshold comes from the per-machine
# calibration probe (hyperspace_tpu/native/calibrate.py) when available;
# this constant applies when calibration is disabled (HS_CALIBRATE=0) or
# when a test overrides the module attribute (an override always wins).
_HOST_HASH_MAX_ROWS_DEFAULT = 1 << 26
_HOST_HASH_MAX_ROWS = _HOST_HASH_MAX_ROWS_DEFAULT

# At or above this row count the host hash uses the native single-pass
# murmur3 kernel (hyperspace_tpu/native); below it numpy's vectorized
# mixes are already microseconds. Fallback default; see above.
_NATIVE_HASH_MIN_ROWS_DEFAULT = 1 << 15
_NATIVE_HASH_MIN_ROWS = _NATIVE_HASH_MIN_ROWS_DEFAULT


def _host_hash_max_rows() -> int:
    if _HOST_HASH_MAX_ROWS != _HOST_HASH_MAX_ROWS_DEFAULT:
        return _HOST_HASH_MAX_ROWS  # explicit (test/ops) override wins
    from hyperspace_tpu.native import calibrate

    return calibrate.thresholds().host_hash_max_rows or _HOST_HASH_MAX_ROWS


def _native_hash_min_rows() -> int:
    if _NATIVE_HASH_MIN_ROWS != _NATIVE_HASH_MIN_ROWS_DEFAULT:
        return _NATIVE_HASH_MIN_ROWS
    from hyperspace_tpu.native import calibrate

    return (
        calibrate.thresholds().native_hash_min_rows or _NATIVE_HASH_MIN_ROWS
    )


def bucket_ids_host(
    key_reps: np.ndarray, num_buckets: int, seed: int = 42
) -> np.ndarray:
    """Pure-numpy bucket ids — the bit-exact host twin of the device
    kernel (same mix functions on np.uint32). Used for small inputs and
    for host-side pre-passes that must never touch the device."""
    n = key_reps.shape[1]
    if n == 0:
        return np.zeros(0, dtype=np.int32)
    if n >= _native_hash_min_rows():
        from hyperspace_tpu import native

        # one pass per row vs ~10 vectorized passes; bit-exact twin
        ids = native.bucket_ids_i64(
            key_reps.astype(np.int64, copy=False), num_buckets, seed
        )
        if ids is not None:
            return ids
    return bucket_ids_numpy(key_reps, num_buckets, seed)


def bucket_ids_numpy(
    key_reps: np.ndarray, num_buckets: int, seed: int = 42
) -> np.ndarray:
    """The pure-numpy murmur leg of :func:`bucket_ids_host`, never
    dispatching to the native kernel — also the reference the
    calibration probe (native/calibrate.py) times the native kernel
    against, so the probe always measures exactly the code that runs
    when the native kernel loses or is unavailable."""
    n = key_reps.shape[1]
    if n == 0:
        return np.zeros(0, dtype=np.int32)
    words = split_words_np(key_reps)
    with np.errstate(over="ignore"):
        h = np.full(n, np.uint32(seed))
        for i in range(words.shape[0]):
            h = _mix_h1(h, _mix_k1(words[i]))
        h = _fmix(h, np.uint32(4 * words.shape[0]))
    return (h % np.uint32(num_buckets)).astype(np.int32)


def _padded_words(key_reps: np.ndarray, n_pad: int) -> tuple[np.ndarray, bool]:
    """The device kernel's input: ``split_words_np(key_reps)`` with a
    zero tail up to ``n_pad`` rows, and whether the native pass made it.
    At or above the native hash threshold one native pass a key column
    writes words and tail straight into the block
    (``native.split_words_i64``); below it, or where the library is not
    loaded or a key row is not contiguous 8-byte integers, the numpy
    twin and a ``np.concatenate``. The same shape, dtype and bytes
    either way: one device program."""
    k, n = key_reps.shape
    if k and n >= _native_hash_min_rows():
        from hyperspace_tpu import native

        words = np.empty((2 * k, n_pad), dtype=np.uint32)
        if native.split_words_i64(key_reps, words):
            return words, True
    words = split_words_np(key_reps)
    if n_pad != n:
        tail = np.zeros((words.shape[0], n_pad - n), dtype=np.uint32)
        words = np.concatenate([words, tail], axis=1)
    return words, False


def bucket_ids_np(key_reps: np.ndarray, num_buckets: int, seed: int = 42) -> np.ndarray:
    """Host entry: [k, n] int64 key reps -> int32 bucket ids. Large inputs
    hash on device (padded to a power of two, ops/__init__ shape policy);
    small ones use the same arithmetic directly in numpy.

    Under a live trace the device path is four spans — ``split_words``
    (the host's int64 -> uint32 word split and padding; ``words`` is the
    block's first dimension, two a key column, ``native`` 1 where the
    native pass made the block and 0 where its numpy twin did), ``h2d`` (to
    ``block_until_ready`` of the words on the device), ``kernel``
    (dispatch to ``block_until_ready``), ``d2h`` — with the bytes each
    way counted on the root; the host path is one ``host_hash``."""
    n = key_reps.shape[1]
    if n == 0:
        return np.zeros(0, dtype=np.int32)
    if n <= _host_hash_max_rows():
        with _obs_trace.span("host_hash"):
            return bucket_ids_host(key_reps, num_buckets, seed)
    with _obs_trace.span("split_words") as split_sp:
        words, ran_native = _padded_words(key_reps, pad_len(n))
        split_sp.set("words", int(words.shape[0]))
        split_sp.set("native", int(ran_native))
    with _obs_trace.span("h2d", bytes=int(words.nbytes)):
        on_device = jax.block_until_ready(jnp.asarray(words))
    with _obs_trace.span("kernel"):
        ids = jax.block_until_ready(
            _bucket_ids_words(on_device, num_buckets, seed)
        )
    with _obs_trace.span("d2h", bytes=int(ids.nbytes)):
        out = np.asarray(ids)
    _obs_trace.accumulate("h2d_bytes", int(words.nbytes))
    _obs_trace.accumulate("d2h_bytes", int(out.nbytes))
    return out[:n]
