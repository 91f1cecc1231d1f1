"""Z-address computation — bit interleaving as an XLA kernel.

Reference: ``zordercovering/ZOrderField.scala:26-569`` (per-type bit
encoding of values into z-address bits) and ``ZOrderUDF.scala:32-100``
(row → z-address via a precomputed bit-index map). The reference computes
z-addresses row-wise in a Spark UDF; here the whole column pipeline is
vectorized 32-bit device arithmetic:

1. per column, an order-preserving uint64 encoding (sign-flip for ints,
   IEEE total-order trick for floats, dictionary ranks for strings);
2. min/max normalization onto ``bits_per_column`` bits (the reference's
   min/max-based ZOrderField encoding; percentile variant = quantile
   normalization, same shape);
3. bit interleaving across columns into a multi-word z-address, ordered
   lexicographically word-major.

**The layout** (min/max encoding, the default; one definition for this
module, ``benchmarks/reference_zorder.py`` and ``docs/range-serve.md``).
For ``k`` indexed columns, 16 bits a column:

* ``enc`` = the value's order-preserving uint64: signed integers and
  ``date32`` days offset by 2^63; float64 in IEEE total order (sign bit
  set → all bits flipped, else the sign bit set);
* ``word = trunc(min((enc − min) as float64 × ((2^16 − 1) ÷ (max − min)
  as float64), 2^16 − 1))`` with min/max the column's own extremes in
  encoding space, 0 where max = min;
* address bit ``t`` (most significant first) = bit ``15 − t div k`` of
  column ``t mod k``, first indexed column first: ``16 k`` bits, packed
  into 32-bit planes from the top (the last plane's low bits are zero).

A float column is scaled in *encoding* space, not value space: on a
column of 0.00..0.10 the value 0.00 maps to word 0 and 0.01..0.10 to the
top ~0.3% of the words, so such a column orders its rows only in the
address's last bits. Rows of one address are in no stated order (the
sort is stable over the input's row order, which nothing relies on).
Upstream's ``ZOrderField`` instead keeps ``value − min`` at the bit
length of ``max − min`` and interleaves through a bit-index map.

From the native hash's row threshold up, a numeric column's encoding
and its words are each one threaded native pass (``native.order_u64``,
``native.zorder_words``), the words written straight into the block the
interleave uploads; :func:`order_u64_numpy` and :func:`range_words_np`
are their bit-exact numpy twins, which every other case takes.

Under a live trace ``planes_from_encodings`` is four spans — ``words``
(the host's words: ``scale_s``, ``stack_s``, ``pad_s``, ``columns`` and
``native`` on it), ``h2d``, ``kernel`` (dispatch to
``block_until_ready``), ``d2h`` — with the bytes each way counted on
the root, as ``ops/hash.bucket_ids_np`` has them; ``ZOrderEncoder.fit``
adds ``order_s``, ``minmax_s``, ``columns`` and ``native`` to the span
it runs under.
"""

from __future__ import annotations

import functools
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import hyperspace_tpu.ops  # noqa: F401  (enables x64)
from hyperspace_tpu.obs import trace as _obs_trace
# the native passes' row threshold is the native hash's: the same
# elementwise shape of pass over a column
from hyperspace_tpu.ops.hash import _native_hash_min_rows


def order_u64_np(col) -> np.ndarray:
    """Order-preserving uint64 of a Column's values (host prep, O(n);
    nulls sort first)."""
    return _order_u64(col)[0]


def _order_u64(col) -> Tuple[np.ndarray, bool]:
    """:func:`order_u64_np` and whether the native pass made it. A
    contiguous int64 or float64 column (dates and timestamps arrive as
    int64) of at least the native hash's row threshold is one threaded
    native pass (``native.order_u64``); every other column, or where the
    library is not loaded, :func:`order_u64_numpy`. The same bytes
    either way."""
    if col.kind != "string" and len(col.values) >= _native_hash_min_rows():
        from hyperspace_tpu import native

        enc = native.order_u64(col.values, col.validity)
        if enc is not None:
            return enc, True
    return order_u64_numpy(col), False


def order_u64_numpy(col) -> np.ndarray:
    """The numpy body of :func:`order_u64_np`, never dispatching to the
    native pass: whole-array passes on one thread."""
    if col.kind == "string":
        order = sorted(range(len(col.dictionary)), key=lambda i: col.dictionary[i])
        rank = np.empty(max(len(col.dictionary), 1), dtype=np.uint64)
        for r, i in enumerate(order):
            rank[i] = r + 1  # 0 reserved for null
        return np.where(
            col.codes < 0, np.uint64(0), rank[np.maximum(col.codes, 0)]
        )
    v = col.values
    if v.dtype.kind == "f":
        bits = v.astype(np.float64).view(np.uint64)
        sign = bits >> np.uint64(63)
        enc = np.where(
            sign == 1, ~bits, bits | np.uint64(1) << np.uint64(63)
        )
    elif v.dtype.kind == "b":
        enc = v.astype(np.uint64) + np.uint64(1)
    elif v.dtype.kind == "u":
        enc = v.astype(np.uint64)
    else:
        enc = (v.astype(np.int64) ^ np.int64(-(2**63))).view(np.uint64)
    if col.validity is not None:
        enc = np.where(col.validity, np.maximum(enc, np.uint64(1)), np.uint64(0))
    return enc


@functools.partial(jax.jit, static_argnames=("bits",))
def _interleave(words, bits: int):
    """[k, n] uint32 (each < 2^bits) -> [ceil(k*bits/32), n] uint32 planes,
    most-significant plane first; lexsort over planes == z-order."""
    k, n = words.shape
    total = k * bits
    nplanes = (total + 31) // 32
    planes = jnp.zeros((nplanes, n), dtype=jnp.uint32)
    # z-bit t (from most significant) = bit (bits-1 - t//k) of column t%k
    for t in range(total):
        src_col = t % k
        src_bit = bits - 1 - (t // k)
        bit = (words[src_col] >> np.uint32(src_bit)) & jnp.uint32(1)
        dst_plane = t // 32
        dst_bit = 31 - (t % 32)
        planes = planes.at[dst_plane].add(bit << np.uint32(dst_bit))
    return planes


class ZOrderEncoder:
    """FIXED per-column encoding spec -> z-address planes.

    Freezing the spec and making plane computation a pure function of it
    is what lets the streamed z-order build work: every wave, the spill
    partitioner and the per-partition merge sort all encode IDENTICALLY,
    so local sorted order equals global order. Spec kinds per column:

    * ``("range", min_u64, max_u64)`` — min/max scaling of the numeric
      order encoding;
    * ``("quantile", sorted_bounds)`` — rank via binary search over
      sampled boundaries (skew-resistant);
    * ``("dict", sorted_strings)`` — GLOBAL lexicographic rank for string
      columns. Batch-local dictionary ranks are NOT stable across waves,
      so string encoding must always go through a frozen global
      dictionary (rank normalization doubles as quantile normalization).
    """

    def __init__(self, bits: int, specs: List):
        self.bits = bits
        self.specs = specs

    # -- construction -------------------------------------------------------
    @staticmethod
    def fit(
        columns: List, bits: int, quantile: bool, relative_error: float
    ):
        """(encoder, per-column encodings) from in-memory Columns — the
        encodings are returned so the caller never encodes twice.

        What the seconds went to is added to the span live in the
        caller's context (the ``zorder_encode`` stage of a build):
        ``order_s``, the order encodings, and ``minmax_s``, each
        encoding's min and max (or its quantile sample), summed over the
        columns. Outside an action nothing is recorded."""
        now = time.perf_counter_ns
        order_ns = minmax_ns = 0
        specs = []
        encs = []
        ran_native = 0
        for col in columns:
            t0 = now()
            if col.kind == "string":
                spec = ("dict", sorted(set(col.dictionary)))
                specs.append(spec)
                encs.append(_dict_encode(col, spec[1]))
                order_ns += now() - t0
                continue
            e, by_native = _order_u64(col)
            ran_native += by_native
            encs.append(e)
            t1 = now()
            order_ns += t1 - t0
            if quantile:
                max_sample = max(
                    int(1.0 / max(relative_error, 1e-4) ** 2), 1024
                )
                sample = (
                    e if len(e) <= max_sample else e[:: max(1, len(e) // max_sample)]
                )
                specs.append(("quantile", np.sort(sample)))
            else:
                specs.append(
                    (
                        "range",
                        e.min() if len(e) else np.uint64(0),
                        e.max() if len(e) else np.uint64(0),
                    )
                )
            minmax_ns += now() - t1
        sp = _obs_trace.current()
        if sp is not None:
            for key, ns in (("order_s", order_ns), ("minmax_s", minmax_ns)):
                sp.set(key, round(sp.attrs.get(key, 0.0) + ns / 1e9, 6))
            for key, count in (("columns", len(columns)), ("native", ran_native)):
                sp.set(key, sp.attrs.get(key, 0) + count)
        return ZOrderEncoder(bits, specs), encs

    # -- encoding -----------------------------------------------------------
    def encode(self, col, j: int) -> np.ndarray:
        """Per-row uint64 order encoding of a Column under spec j."""
        spec = self.specs[j]
        if spec[0] == "dict":
            return _dict_encode(col, spec[1])
        return order_u64_np(col)

    def _range(self, spec) -> Tuple[np.uint64, float]:
        """(min, scale) of a "range" or "dict" spec's min/max scaling."""
        if spec[0] == "dict":
            # global ranks in [0, len]: plain range scaling over the rank
            # space (rank IS the quantile of the unique-value distribution)
            mn, mx = np.uint64(0), np.uint64(len(spec[1]))
        else:
            _tag, mn, mx = spec
        rng = float(int(mx) - int(mn))
        return mn, ((2.0**self.bits) - 1) / rng if rng > 0 else 0.0

    def _words(self, enc: np.ndarray, spec) -> np.ndarray:
        bits = self.bits
        top = (1 << bits) - 1
        if spec[0] == "quantile":
            bounds = spec[1]
            pos = np.searchsorted(bounds, enc, side="right").astype(np.float64)
            return ((pos / max(len(bounds), 1)) * np.float64(top)).astype(
                np.uint32
            )
        # min/max scaling on host (per-wave word computation is O(n)
        # elementwise; device dispatch pays transfers)
        return range_words_np(enc, *self._range(spec), top)

    def planes_from_encodings(self, encs: List[np.ndarray]) -> np.ndarray:
        """[nplanes, n] uint32 planes (most-significant first) from
        per-column encodings produced by :meth:`encode`.

        The interleave's input is one ``uint32[k, pad_len(n)]`` block,
        each column's words in its row and the fill ``top`` past ``n``.
        From the native hash's row threshold up a "range" or "dict"
        column's row is one native pass (``native.zorder_words``) that
        writes words and fill straight into the block; a quantile
        column, a smaller input, or a library not loaded takes
        :meth:`_words` and copies its row in. ``native`` on the
        ``words`` span counts the rows the pass made, of ``columns``."""
        from hyperspace_tpu import native
        from hyperspace_tpu.ops import pad_len

        n = len(encs[0]) if encs else 0
        top = (1 << self.bits) - 1
        big = n >= _native_hash_min_rows()
        with _obs_trace.span("words") as sp:
            now = time.perf_counter_ns
            scale_ns = stack_ns = pad_ns = 0
            ran_native = 0
            words = np.empty((len(encs), pad_len(max(n, 1))), dtype=np.uint32)
            for j, (enc, spec) in enumerate(zip(encs, self.specs)):
                t0 = now()
                if (
                    big
                    and spec[0] != "quantile"
                    and native.zorder_words(enc, *self._range(spec), top, words[j])
                ):
                    ran_native += 1
                    scale_ns += now() - t0
                    continue
                row = self._words(enc, spec)
                t1 = now()
                words[j, :n] = row
                t2 = now()
                words[j, n:] = top
                scale_ns += t1 - t0
                stack_ns += t2 - t1
                pad_ns += now() - t2
            # what the span's seconds went to: the columns' words (the
            # native pass writes its row and fill whole), the twin's rows
            # copied into the block, and their fill
            sp.set("columns", len(encs))
            sp.set("native", ran_native)
            sp.set("scale_s", round(scale_ns / 1e9, 6))
            sp.set("stack_s", round(stack_ns / 1e9, 6))
            sp.set("pad_s", round(pad_ns / 1e9, 6))
        with _obs_trace.span("h2d", bytes=int(words.nbytes)):
            on_device = jax.block_until_ready(jnp.asarray(words))
        with _obs_trace.span("kernel"):
            planes = jax.block_until_ready(_interleave(on_device, self.bits))
        with _obs_trace.span("d2h", bytes=int(planes.nbytes)):
            out = np.asarray(planes)
        _obs_trace.accumulate("h2d_bytes", int(words.nbytes))
        _obs_trace.accumulate("d2h_bytes", int(out.nbytes))
        return out[:, :n]

    def planes(self, columns: List) -> np.ndarray:
        return self.planes_from_encodings(
            [self.encode(c, j) for j, c in enumerate(columns)]
        )


def range_words_np(enc: np.ndarray, mn, scale: float, top: int) -> np.ndarray:
    """uint32 z-words of one column's encodings under min/max scaling:
    ``trunc(clip(float64(enc - mn) * scale, 0, top))`` — the numpy twin
    of ``native.zorder_words`` (without its fill past the rows)."""
    off = (enc - mn).astype(np.float64)
    return np.clip(off * scale, 0, top).astype(np.uint32)


def _dict_encode(col, sorted_global: List[str]) -> np.ndarray:
    """uint64 global lexicographic rank (+1; 0 = null) of a string
    Column's values under a frozen sorted dictionary."""
    local = col.dictionary
    rank_of = np.searchsorted(np.array(sorted_global, dtype=object), local)
    lut = np.asarray(rank_of, dtype=np.uint64) + np.uint64(1)
    if len(lut) == 0:
        lut = np.zeros(1, dtype=np.uint64)
    enc = lut[np.maximum(col.codes, 0)]
    return np.where(col.codes < 0, np.uint64(0), enc)


# ---------------------------------------------------------------------------
# Z-address range decomposition (serve-side pruning; docs/range-serve.md)
# ---------------------------------------------------------------------------
#
# A z-laid-out index file is a contiguous run of the z-sorted order, so its
# rows span a narrow interval of z-addresses even when each COLUMN's
# per-file min/max is wide. Pruning therefore works in z-space: the query
# box (per-column word intervals under the file set's frozen encoder spec)
# decomposes into a small set of z-address keep-ranges, and a file/row
# group whose captured z-span misses every range cannot hold a matching
# row. Per-column min/max alone cannot reconstruct the spans (the interval
# [z(mins), z(maxs)] always intersects the box whenever every column
# overlaps it), which is why capture (indexes/zonemaps.py) records the
# actual spans at build time and the serve path falls back to per-column
# pruning when they are absent.


def order_u64_scalar(value, kind: str) -> int:
    """Order-preserving uint64 of ONE engine-domain value — the scalar
    twin of :func:`order_u64_np` (same branches, same bit tricks) for
    encoding query-box bounds. ``kind`` is the numpy dtype kind of the
    column's storage ("f"/"b"/"u"/else-int). ``value`` must already be
    in the column's storage domain — callers convert non-integral or
    out-of-range bounds outward (floor/ceil, ±inf → unbounded side)
    before encoding."""
    if kind == "f":
        bits = int(np.float64(value).view(np.uint64))
        if bits >> 63:
            return (~bits) & 0xFFFFFFFFFFFFFFFF
        return bits | (1 << 63)
    if kind == "b":
        return int(bool(value)) + 1
    v = int(value)
    if kind == "u":
        return v & 0xFFFFFFFFFFFFFFFF
    return (v ^ -(1 << 63)) & 0xFFFFFFFFFFFFFFFF


def spec_word_bounds(spec, enc_lo: int, enc_hi: int, bits: int):
    """[word_lo, word_hi] of an encoded-value interval under one frozen
    spec — the scalar twin of :meth:`ZOrderEncoder._words`, rounded
    OUTWARD (floor the low end, ceil the high end) so the word box is a
    superset of the value box. Only "range" and "dict" specs appear in
    captured zone-map metadata; quantile specs abstain (None)."""
    top = (1 << bits) - 1
    if spec[0] == "dict":
        mn, mx = 0, len(spec[1])
    elif spec[0] == "range":
        mn, mx = int(spec[1]), int(spec[2])
    else:
        return None
    rng = mx - mn
    if rng <= 0:
        return 0, top
    scale = ((2.0**bits) - 1) / float(rng)

    def word(enc, up):
        off = float(max(min(enc, mx), mn) - mn) * scale
        w = int(np.ceil(off)) if up else int(np.floor(off))
        return max(0, min(top, w))

    return word(enc_lo, False), word(enc_hi, True)


def z_box_ranges(word_lo, word_hi, bits: int, max_ranges: int = 64):
    """Decompose a per-column word box into z-address keep-ranges.

    Returns a sorted list of inclusive ``(z_lo, z_hi)`` python-int ranges
    (in k*bits-bit z-space, MSB = column 0's top bit — the
    :func:`_interleave` layout) whose union COVERS every z-address inside
    the box; a bounded recursion emits partially-covered cells whole when
    the budget runs out, so the union may over-cover (superset-safe) but
    never under-covers. Standard prefix-tree (BIGMIN-family) walk: a cell
    disjoint from the box in any column is dropped, a fully-contained
    cell emits its whole z-interval, anything else splits on the next
    z-bit."""
    k = len(word_lo)
    total = k * bits
    out = []
    budget = [max(4, int(max_ranges)) * 4]

    def rec(depth, zpref, col_pref):
        nfixed = [depth // k + (1 if j < depth % k else 0) for j in range(k)]
        for j in range(k):
            free = bits - nfixed[j]
            clo = col_pref[j] << free
            chi = clo + (1 << free) - 1
            if chi < word_lo[j] or clo > word_hi[j]:
                return
        inside = True
        for j in range(k):
            free = bits - nfixed[j]
            clo = col_pref[j] << free
            chi = clo + (1 << free) - 1
            if clo < word_lo[j] or chi > word_hi[j]:
                inside = False
                break
        span = total - depth
        if inside or depth == total or budget[0] <= 0:
            lo = zpref << span
            out.append((lo, lo + (1 << span) - 1))
            return
        budget[0] -= 1
        j = depth % k
        for b in (0, 1):
            child = list(col_pref)
            child[j] = (col_pref[j] << 1) | b
            rec(depth + 1, (zpref << 1) | b, child)

    rec(0, 0, [0] * k)
    out.sort()
    merged = []
    for lo, hi in out:
        if merged and lo <= merged[-1][1] + 1:
            merged[-1] = (merged[-1][0], max(merged[-1][1], hi))
        else:
            merged.append((lo, hi))
    return merged


def _pack_z(words) -> int:
    """One row's planes, most-significant first, as one python int: the
    PACKED (32 bits a plane) z-space every captured span is stored in."""
    z = 0
    for w in words:
        z = (z << 32) | int(w)
    return z


def planes_z_at(planes: np.ndarray, rows) -> List[int]:
    """Packed z-addresses (as :func:`planes_z_minmax` packs them) of the
    given row positions of ``planes`` — a gather of ``len(rows)``
    columns, whatever the planes' length."""
    picked = planes[:, np.asarray(rows, dtype=np.int64)]
    return [_pack_z(picked[:, i]) for i in range(picked.shape[1])]


def planes_z_minmax(planes: np.ndarray, start: int, end: int):
    """(z_lo, z_hi) python ints of rows [start, end) of ``planes``
    ([nplanes, n] uint32, most-significant plane first), in PACKED
    (32*nplanes-bit) z-space — the capture-side reader of per-row-group
    z-spans. None for an empty slice. Single-plane layouts (k*bits ≤ 32,
    the common 1-2 column case) reduce to a vectorized min/max; wider
    addresses pay one lexsort of the slice."""
    sub = planes[:, start:end]
    n = sub.shape[1]
    if n == 0:
        return None
    if sub.shape[0] == 1:
        return int(sub[0].min()), int(sub[0].max())
    order = np.lexsort(sub[::-1])
    return _pack_z(sub[:, order[0]]), _pack_z(sub[:, order[-1]])


def pack_box_ranges(ranges, bits: int, k: int, nplanes: int):
    """Shift keep-ranges from k*bits-bit z-space into the PACKED
    32*nplanes-bit space :func:`planes_z_minmax` reports spans in (the
    last plane's low bits are zero padding)."""
    pad = 32 * nplanes - k * bits
    if pad <= 0:
        return list(ranges)
    return [
        ((lo << pad), ((hi << pad) | ((1 << pad) - 1))) for lo, hi in ranges
    ]


def z_order_permutation(
    columns: List,
    bits: int = 16,
    quantile: bool = False,
    relative_error: float = 0.01,
) -> np.ndarray:
    """Sort permutation by z-address over the given Columns
    (the build-side replacement for repartitionByRange on ``_zaddr``,
    ZOrderCoveringIndex.scala:97-154). ``quantile=True`` switches from
    min/max scaling to quantile-bucket encoding (skewed columns keep
    using all address bits instead of collapsing onto a few)."""
    from hyperspace_tpu.ops.sort import lexsort_perm

    enc, encs = ZOrderEncoder.fit(columns, bits, quantile, relative_error)
    return lexsort_perm(enc.planes_from_encodings(encs))
