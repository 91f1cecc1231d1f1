"""The plain reference: numpy over the generated columns.

Imports nothing of ``hyperspace_tpu`` and reads nothing the program
wrote. An answer is compared as an order-independent digest of its rows
— [row count, sum and xor of a 64-bit mix of every value's bit pattern]
— so a float payload has to come back bit for bit.

``bucket_of`` is the bucket a key belongs in, as the configuration states
it: MurmurHash3 (x86, 32 bits, seed 42) over the key's eight little-endian
bytes, modulo the number of buckets. It is written here from the published
algorithm, so that a build whose hash went wrong is seen.

``lossy`` is the control: the reference's own answers with the float
payload passed through float32, the loss a float64 column suffers on a
device that holds no IEEE double (PERF.md, PR 21). A comparison that
cannot tell that from the exact answer decides nothing.

A string's bits are a 64-bit hash of its UTF-8 bytes (``string_bits``),
taken over Arrow's offset and data buffers with no loop over rows: plain,
large and dictionary-encoded arrays of the same strings, and numpy object
arrays of them, hash alike, and a null differs from ``''``. ``clipped`` is
the control of a string payload: every string cut to its first 16 bytes,
the loss a fixed-width device plane would cause.
"""

from __future__ import annotations

import numpy as np

_MIX = np.uint64(0x9E3779B97F4A7C15)
# a null string's bits, and the constants of a string's hash
_NULL_BITS = np.uint64(0x6E756C6C6E756C6C)
_BYTE_SALT = np.uint64(0x5851F42D4C957F2D)
_LEN_SALT = np.uint64(0x14057B7EF767814F)
_CLIP_BYTES = 16
# bytes hashed at a time: bounds the uint64 scratch to 8 x this
_CHUNK_BYTES = 1 << 24


def _fmix64(z: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer, in place on a uint64 array."""
    with np.errstate(over="ignore"):
        z ^= z >> np.uint64(30)
        z *= np.uint64(0xBF58476D1CE4E5B9)
        z ^= z >> np.uint64(27)
        z *= np.uint64(0x94D049BB133111EB)
        z ^= z >> np.uint64(31)
    return z


def _hash_bytes(offsets: np.ndarray, data: np.ndarray) -> np.ndarray:
    """A string's hash from its bytes ``data[offsets[i]:offsets[i + 1]]``:
    the finalizer of (the sum of the finalizer of each byte with its place
    in the string, plus the finalizer of the length)."""
    lengths = np.diff(offsets)
    h = np.zeros(len(lengths), dtype=np.uint64)
    rows = 0
    while rows < len(lengths):
        # the rows whose bytes fit a chunk (at least one row)
        end = max(int(np.searchsorted(offsets, offsets[rows] + _CHUNK_BYTES, side="right")) - 1,
                  rows + 1)
        lo, hi = int(offsets[rows]), int(offsets[end])
        starts, n = offsets[rows:end] - lo, lengths[rows:end]
        z = (np.arange(hi - lo, dtype=np.int64) - np.repeat(starts, n)).view(np.uint64)
        z <<= np.uint64(8)
        z |= data[lo:hi]
        with np.errstate(over="ignore"):
            z += _BYTE_SALT
        _fmix64(z)
        full = n > 0
        if full.any():
            with np.errstate(over="ignore"):
                h[rows:end][full] = np.add.reduceat(z, starts[full])
        rows = end
    with np.errstate(over="ignore"):
        h += _fmix64(lengths.astype(np.uint64) + _LEN_SALT)
    return _fmix64(h)


def string_bits(values) -> np.ndarray:
    """uint64 a value of a string column: a pyarrow (chunked) string,
    large string or dictionary array, or a numpy array of ``str`` (object
    arrays may hold None for null)."""
    import pyarrow as pa

    if isinstance(values, np.ndarray):
        values = pa.array(values.astype(object), type=pa.large_string())
    if isinstance(values, pa.ChunkedArray):
        return np.concatenate([string_bits(c) for c in values.chunks] or [np.zeros(0, np.uint64)])
    if pa.types.is_dictionary(values.type):
        words = string_bits(values.dictionary)
        codes = values.indices.fill_null(0).to_numpy(zero_copy_only=False)
        out = words[codes] if len(words) else np.zeros(len(values), dtype=np.uint64)
    else:
        wide = pa.types.is_large_string(values.type)
        if not (wide or pa.types.is_string(values.type)):
            raise TypeError(f"no string bits of a {values.type} column")
        _validity, offs, data = values.buffers()
        offsets = np.frombuffer(offs, dtype=np.int64 if wide else np.int32)
        offsets = offsets[values.offset:values.offset + len(values) + 1].astype(np.int64)
        data = np.frombuffer(data, dtype=np.uint8) if data is not None else np.zeros(0, np.uint8)
        out = _hash_bytes(offsets, data)
    if values.null_count:
        out = np.where(values.is_null().to_numpy(zero_copy_only=False), _NULL_BITS, out)
    return out


def _bits(values) -> np.ndarray:
    v = values if hasattr(values, "type") else np.asarray(values)    # pyarrow: strings
    if not isinstance(v, np.ndarray) or v.dtype.kind in "OUT":
        return string_bits(v)
    if v.dtype.kind == "f":
        return np.ascontiguousarray(v, dtype=np.float64).view(np.uint64)
    if v.dtype.kind in "mM":
        v = v.astype("datetime64[D]").astype(np.int64)
    return np.ascontiguousarray(v, dtype=np.int64).view(np.uint64)


def row_hashes(cols: dict) -> np.ndarray:
    """One uint64 per row over all columns, taken in name order."""
    names = sorted(cols)
    n = len(cols[names[0]]) if names else 0
    h = np.zeros(n, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for i, name in enumerate(names):
            h = (h ^ (_bits(cols[name]) + np.uint64(i + 1))) * _MIX
            h ^= h >> np.uint64(29)
    return h


def digest(cols: dict) -> tuple:
    """(rows, sum, xor) of the row hashes: equal multisets of rows give
    equal digests."""
    h = row_hashes(cols)
    if not len(h):
        return (0, 0, 0)
    with np.errstate(over="ignore"):
        return (len(h), int(h.sum(dtype=np.uint64)), int(np.bitwise_xor.reduce(h)))


def segment_digests(cols: dict, counts: np.ndarray) -> np.ndarray:
    """Digests of consecutive row groups of ``cols`` of the given sizes
    -> uint64 [len(counts), 3] (rows, sum, xor); an empty group is 0s."""
    counts = np.asarray(counts, dtype=np.int64)
    out = np.zeros((len(counts), 3), dtype=np.uint64)
    out[:, 0] = counts.astype(np.uint64)
    h = row_hashes(cols)
    full = counts > 0
    if len(h) and full.any():
        starts = (np.cumsum(counts) - counts)[full]
        with np.errstate(over="ignore"):
            out[full, 1] = np.add.reduceat(h, starts)
        out[full, 2] = np.bitwise_xor.reduceat(h, starts)
    return out


def table_cols(table) -> dict:
    """pyarrow table -> {name: numpy}, dates as int32 days, strings as
    object arrays of ``str`` (None for null)."""
    import pyarrow as pa

    out = {}
    for name in table.column_names:
        col = table.column(name)
        if pa.types.is_date32(col.type):
            col = col.cast(pa.int32())
        elif pa.types.is_dictionary(col.type):
            col = col.cast(col.type.value_type)
        out[name] = col.combine_chunks().to_numpy(zero_copy_only=False)
    return out


def lossy(cols: dict) -> dict:
    """The control's answer: float columns through float32."""
    return {
        k: v.astype(np.float32).astype(np.float64) if v.dtype.kind == "f" else v
        for k, v in cols.items()
    }


def _clip(s):
    return s if s is None else s.encode()[:_CLIP_BYTES].decode(errors="ignore")


def clipped(cols: dict) -> dict:
    """The control of a string payload: every string cut to its first 16
    bytes; other columns as they are."""
    return {
        k: np.array([_clip(s) for s in v], dtype=object) if v.dtype.kind in "OU" else v
        for k, v in cols.items()
    }


class KeyIndex:
    """Rows of a table by key, by a sort of the key column."""

    def __init__(self, cols: dict, key: str):
        self.cols = cols
        self.key = key
        self.order = np.argsort(cols[key], kind="stable")
        self.sorted_keys = cols[key][self.order]

    def rows_of_ranges(self, lo, hi):
        """Row ids of keys in [lo[i], hi[i]) for each i, concatenated ->
        (row ids, rows per range)."""
        a = np.searchsorted(self.sorted_keys, np.asarray(lo), side="left")
        b = np.searchsorted(self.sorted_keys, np.asarray(hi), side="left")
        counts = (b - a).astype(np.int64)
        total = int(counts.sum())
        if not total:
            return np.zeros(0, dtype=np.int64), counts
        starts = np.repeat(a - (np.cumsum(counts) - counts), counts)
        return self.order[starts + np.arange(total)], counts


# -- the point lookup's plain answer -----------------------------------------

def ref_point(index: KeyIndex, keys, select) -> tuple:
    """Point lookups ``key == k`` -> (columns of all answers' rows
    concatenated in request order, rows per answer)."""
    keys = np.asarray(keys, dtype=np.int64)
    rows, counts = index.rows_of_ranges(keys, keys + 1)
    return {c: index.cols[c][rows] for c in select}, counts


# -- where a key belongs -------------------------------------------------------

def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def murmur3_32_int64(keys, seed: int) -> np.ndarray:
    """MurmurHash3_x86_32 of each int64's eight little-endian bytes (two
    4-byte blocks, low word first; no tail) -> uint32."""
    u = np.ascontiguousarray(keys, dtype=np.int64).view(np.uint64)
    blocks = ((u & np.uint64(0xFFFFFFFF)).astype(np.uint32),
              (u >> np.uint64(32)).astype(np.uint32))
    h = np.full(len(u), seed, dtype=np.uint32)
    with np.errstate(over="ignore"):
        for k in blocks:
            k = _rotl(k * np.uint32(0xCC9E2D51), 15) * np.uint32(0x1B873593)
            h = _rotl(h ^ k, 13) * np.uint32(5) + np.uint32(0xE6546B64)
        h ^= np.uint32(8)                      # the length in bytes
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return h


def bucket_of(keys, num_buckets: int, seed: int = 42) -> np.ndarray:
    return (murmur3_32_int64(keys, seed) % np.uint32(num_buckets)).astype(np.int64)
