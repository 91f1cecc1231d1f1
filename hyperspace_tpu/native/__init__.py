"""Optional native (C++) host kernels.

The TPU compute path is JAX/XLA; this package accelerates the HOST side
of the pipeline, where the dispatch policy (see ``ops/sort.py``) keeps
host-resident batches because host<->device transfer dwarfed the
compute when it was measured. Three hot host ops live here (round-5
measurements at 4M rows, not re-measured on this machine): the stable
multi-plane radix lexsort behind the bucketed sorted write (3.3x over
np.lexsort; reference:
``index/DataFrameWriterExtensions.scala:58-67``), the murmur3 bucket-id
hash (8.6x over the vectorized numpy mix), and the linear merge-join
behind the co-bucketed serve join (O(n+m+pairs) with biased emit
straight into preallocated pair buffers).

The kernels are compiled from ``hs_native.cpp`` on first use with
``g++`` and cached next to the source, keyed by a hash of the source so
edits rebuild automatically. Everything degrades gracefully: no
compiler, a failed build (negative-cached via a ``.failed`` marker
holding the compiler stderr), or ``HS_NATIVE=0`` all fall back to the
numpy twins with identical (stable) semantics — callers treat ``None``
from the wrappers as "use numpy".
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
import time as _time
from typing import Optional, Tuple

import numpy as np

from hyperspace_tpu.testing import faults as _faults

_log = logging.getLogger("hyperspace_tpu.native")

_SRC = os.path.join(os.path.dirname(__file__), "hs_native.cpp")
_lock = threading.Lock()
_lib = None
_load_failed = False

# Machine-checked parity registry (hslint HS1xx, hyperspace_tpu/analysis):
# every extern "C" export in hs_native.cpp maps to (ctypes wrapper defined
# in this module, numpy twin the differential tests compare against).
# Adding a kernel without registering it here — or without a test in
# tests/ referencing it — fails `python -m hyperspace_tpu.analysis`.
KERNEL_TWINS = {
    "hs_lexsort_u32": ("lexsort_u32", "numpy.lexsort"),
    "hs_partition_by_bucket": (
        "partition_by_bucket_i32",
        "hyperspace_tpu.ops.sort.partition_by_bucket_numpy",
    ),
    "hs_merge_join_count_i64": (
        "merge_join_count_i64",
        "hyperspace_tpu.execution.join_exec.merge_join_indices",
    ),
    "hs_merge_join_emit_i64": (
        "merge_join_emit_into",
        "hyperspace_tpu.execution.join_exec.merge_join_indices",
    ),
    "hs_bucket_ids_i64": (
        "bucket_ids_i64",
        "hyperspace_tpu.ops.hash.bucket_ids_numpy",
    ),
    "hs_expand_match_ranges_i64": (
        "expand_match_ranges_i64",
        "hyperspace_tpu.ops.join.expand_match_ranges_numpy",
    ),
    "hs_split_words_i64": (
        "split_words_i64",
        "hyperspace_tpu.ops.hash.split_words_np",
    ),
    "hs_order_u64": (
        "order_u64",
        "hyperspace_tpu.ops.zorder.order_u64_numpy",
    ),
    "hs_zorder_words": (
        "zorder_words",
        "hyperspace_tpu.ops.zorder.range_words_np",
    ),
    "hs_gather_i64": ("gather_i64", "numpy.take"),
    "hs_gather_f64": ("gather_f64", "numpy.take"),
    "hs_range_mask": (
        "range_mask_u8",
        "hyperspace_tpu.ops.filter.range_mask_numpy",
    ),
    # Fused-pipeline exports (docs/serve-compiler.md): the registered
    # twin is the INTERPRETED CHAIN the kernel replaces, not a single
    # numpy op — hslint HS105 enforces an in-package pipeline twin for
    # every hs_fused_* export, so whole-pipeline parity is what the
    # differential tests witness.
    "hs_fused_filter_select": (
        "fused_filter_select",
        "hyperspace_tpu.execution.pipeline_compiler.filter_select_interpreted",
    ),
    "hs_fused_filter_agg": (
        "fused_filter_agg",
        "hyperspace_tpu.execution.pipeline_compiler.interpreted_filter_aggregate",
    ),
}


def _cache_dir() -> str:
    """Directory for the compiled .so: next to the source when writable
    (shared across users/processes, survives with the checkout), else a
    per-user cache dir (read-only site-packages installs — root-owned
    images, zipapp-adjacent layouts — must still get native kernels AND
    a persistable .failed marker)."""
    pkg = os.path.dirname(__file__)
    if os.access(pkg, os.W_OK):
        return pkg
    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache"
    )
    path = os.path.join(base, "hyperspace_tpu", "native")
    os.makedirs(path, exist_ok=True)
    return path


def _cache_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_cache_dir(), f"_hs_native_{digest}.so")


# How long another source revision's .so/.failed artifacts survive in a
# shared cache dir before cleanup removes them. Deleting them eagerly
# made two checkouts sharing one XDG cache recompile on every
# alternating process start (each start destroyed the other's .so); the
# age gate keeps every ACTIVE revision's artifacts while still
# reclaiming truly-stale ones. "Active" is tracked via mtime: load()
# touches the .so on every successful CDLL load (atime is unreliable —
# relatime/noatime mounts), so a revision some process still uses never
# ages past the threshold, while a genuinely abandoned one does.
_SUPERSEDED_TTL_S = 7 * 24 * 3600.0


def _cleanup_superseded(keep: str) -> None:
    """Drop STALE artifacts of other source revisions (the cache is keyed
    by a source hash, so every edit would otherwise strand one .so
    forever — a real leak on shared filesystems and baked images) and
    ORPHANED ``.tmp.<pid>`` compile scratch files (a SIGKILLed g++ leaves
    one behind; nothing else ever reclaims it). Only artifacts older
    than ``_SUPERSEDED_TTL_S`` are removed: a younger .so likely belongs
    to another live checkout sharing this cache dir (two checkouts
    deleting each other's .so recompile forever), and a younger tmp may
    be another process mid-compile — unlinking its tmp would fail its
    ``os.replace`` and latch a bogus .failed marker. A week-old tmp is
    unambiguously an orphan, whatever revision it belongs to."""
    pattern = os.path.join(os.path.dirname(keep), "_hs_native_*")
    now = _time.time()
    for old in glob.glob(pattern):
        # tmp files are swept even for the CURRENT revision (orphans of
        # this .so's own past compiles); live artifacts of the current
        # revision (.so, .failed) are never touched
        if ".tmp." not in os.path.basename(old) and old.startswith(keep):
            continue
        try:
            if now - os.path.getmtime(old) >= _SUPERSEDED_TTL_S:
                os.unlink(old)
        except OSError:
            pass


def _compile(path: str) -> bool:
    """Build the shared library; atomic publish via rename so concurrent
    processes never load a half-written file. A failure writes a
    ``.failed`` marker with the compiler's stderr next to the source —
    later processes skip the doomed ~2s retry and operators get a
    diagnostic instead of a silent numpy fallback."""
    tmp = f"{path}.tmp.{os.getpid()}"
    # No -march=native: the kernel is scalar counting-sort (memory-bound,
    # nothing to vectorize), and a cached .so may outlive the machine it
    # was built on (baked image, shared filesystem) — ISA-specific code
    # would then SIGILL with no chance for the numpy fallback to engage.
    cmd = [
        "g++",
        "-O3",
        "-std=c++17",
        "-shared",
        "-fPIC",
        "-pthread",
        _SRC,
        "-o",
        tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        os.replace(tmp, path)
        _cleanup_superseded(path)
        return True
    except (OSError, subprocess.SubprocessError) as exc:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        stderr = getattr(exc, "stderr", b"") or b""
        detail = stderr.decode("utf-8", "replace")[-2000:] or str(exc)
        # Transient failures (compiler timed out on a loaded machine,
        # ENOSPC, OOM-killed g++) must NOT latch the machine-wide negative
        # cache: this process falls back to numpy, the next one retries.
        # Only a deterministic failure — a real compile error, or no g++
        # on PATH at all (FileNotFoundError) — earns the marker; without
        # it a toolchain-less machine would retry and warn in every
        # process forever.
        transient = isinstance(
            exc, (OSError, subprocess.TimeoutExpired)
        ) and not isinstance(exc, FileNotFoundError)
        if isinstance(exc, subprocess.CalledProcessError):
            # g++ killed by a signal (negative returncode: OOM killer on
            # a loaded machine) or out of disk mid-write is transient
            # too, even though both surface as CalledProcessError.
            transient = exc.returncode < 0 or b"No space left" in stderr
        _log.warning(
            "native kernel build failed; falling back to numpy twins%s: %s",
            "" if transient else " (delete %s.failed to retry)" % path,
            detail,
        )
        if not transient:
            # temp + atomic rename (the docs/static-analysis.md pattern):
            # _failed_marker_fresh in another process must never read a
            # half-written marker or see its mtime before the content.
            marker_tmp = f"{path}.failed.tmp.{os.getpid()}"
            try:
                with open(marker_tmp, "w") as f:
                    f.write(detail)
                os.replace(marker_tmp, path + ".failed")
            except OSError:
                try:
                    os.unlink(marker_tmp)
                except OSError:
                    pass
        return False


# How long a .failed negative-cache marker disables native kernels. A
# marker older than this is treated as stale and the compile retried:
# machines change (toolchain upgrades, freed disk), and a day-old latch
# silently costing 3x on every sort is worse than one ~2s retry per day.
_FAILED_MARKER_TTL_S = 24 * 3600.0


def _failed_marker_fresh(marker: str) -> bool:
    """True when the negative-cache marker exists and is young enough to
    honor. Stale markers are removed (best effort) so the caller retries
    the compile. TTL override: HS_NATIVE_FAILED_TTL (seconds)."""
    try:
        age = _time.time() - os.path.getmtime(marker)
    except OSError:
        return False
    try:
        ttl = float(
            os.environ.get("HS_NATIVE_FAILED_TTL", _FAILED_MARKER_TTL_S)
        )
    except ValueError:
        # a malformed override must not crash load() out of a query path
        ttl = _FAILED_MARKER_TTL_S
    if age <= ttl:
        return True
    try:
        os.unlink(marker)
    except OSError:
        pass
    return False


def load(wait: bool = True):
    """The loaded CDLL, or None when native kernels are unavailable.

    ``wait=False`` returns None instead of blocking when another thread
    is mid-compile — hot paths fall back to numpy for the couple of
    seconds a background pre-warm (``HyperspaceSession`` startup) needs,
    rather than stalling a query on the one-time g++ run."""
    global _lib, _load_failed
    # Fault-injection seam (testing/faults.py, "kernel_dispatch"): every
    # kernel wrapper begins with load(wait=False), and None from a
    # wrapper IS the registered degrade path — the numpy/interpreted
    # twin (KERNEL_TWINS) with identical output. One choke point covers
    # every native dispatch, generalizing the lexsort rc-2 fallback.
    if _faults.degraded("kernel_dispatch"):
        return None
    if _lib is not None or _load_failed:
        return _lib
    # Lock-held I/O is the point here: the one-time g++ compile and CDLL
    # load are deliberately serialized so exactly one thread builds;
    # everyone else either waits (wait=True) or falls back to numpy.
    if not _lock.acquire(blocking=wait):  # hslint: disable=HS502
        return None
    try:
        if _lib is not None or _load_failed:
            return _lib
        if os.environ.get("HS_NATIVE", "1") == "0":
            _load_failed = True
            return None
        try:
            path = _cache_path()
        except OSError as exc:
            # stripped install (no .cpp) or unusable cache dir: numpy
            # fallback, never a crash on a query path
            _log.warning("native kernels unavailable: %s", exc)
            _load_failed = True
            return None
        if not os.path.exists(path):
            if _failed_marker_fresh(path + ".failed"):
                _log.warning(
                    "native kernel disabled: previous build failed "
                    "(see %s.failed; delete it to retry)",
                    path,
                )
                _load_failed = True
                return None
            if not _compile(path):
                _load_failed = True
                return None
        try:
            lib = ctypes.CDLL(path)
            lib.hs_lexsort_u32.restype = ctypes.c_int
            lib.hs_lexsort_u32.argtypes = [
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.c_int32,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int32,
            ]
            _i64p = ctypes.POINTER(ctypes.c_int64)
            lib.hs_merge_join_count_i64.restype = ctypes.c_int64
            lib.hs_merge_join_count_i64.argtypes = [
                _i64p,
                ctypes.c_int64,
                _i64p,
                ctypes.c_int64,
            ]
            lib.hs_merge_join_emit_i64.restype = ctypes.c_int64
            lib.hs_merge_join_emit_i64.argtypes = [
                _i64p,
                ctypes.c_int64,
                _i64p,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_int64,
                _i64p,
                _i64p,
            ]
            lib.hs_bucket_ids_i64.restype = ctypes.c_int
            lib.hs_bucket_ids_i64.argtypes = [
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.c_int32,
                ctypes.c_int64,
                ctypes.c_uint32,
                ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_int32),
            ]
            lib.hs_partition_by_bucket.restype = ctypes.c_int
            lib.hs_partition_by_bucket.argtypes = [
                ctypes.POINTER(ctypes.c_int32),
                ctypes.c_int64,
                ctypes.c_int32,
                _i64p,
                _i64p,
                ctypes.c_int32,
            ]
            lib.hs_split_words_i64.restype = ctypes.c_int
            lib.hs_split_words_i64.argtypes = [
                _i64p,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.c_int64,
                ctypes.c_uint32,
                ctypes.c_uint32,
                ctypes.c_int32,
            ]
            lib.hs_order_u64.restype = ctypes.c_int
            lib.hs_order_u64.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int32,
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_int32,
            ]
            lib.hs_zorder_words.restype = ctypes.c_int
            lib.hs_zorder_words.argtypes = [
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_int64,
                ctypes.c_uint64,
                ctypes.c_double,
                ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.c_int64,
                ctypes.c_int32,
            ]
            lib.hs_expand_match_ranges_i64.restype = ctypes.c_int64
            lib.hs_expand_match_ranges_i64.argtypes = [
                _i64p,
                _i64p,
                ctypes.c_int64,
                _i64p,
                ctypes.c_int64,
                _i64p,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_int64,
                _i64p,
                _i64p,
                ctypes.c_int64,
                ctypes.c_int32,
            ]
            _u8p = ctypes.POINTER(ctypes.c_uint8)
            lib.hs_range_mask.restype = ctypes.c_int
            lib.hs_range_mask.argtypes = [
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_void_p),
                _u8p,
                _i64p,
                _i64p,
                ctypes.POINTER(ctypes.c_double),
                ctypes.POINTER(ctypes.c_double),
                _u8p,
                _u8p,
                _u8p,
                _u8p,
                ctypes.c_int32,
                ctypes.c_int64,
                _u8p,
                ctypes.c_int32,
            ]
            _vpp = ctypes.POINTER(ctypes.c_void_p)
            _dp = ctypes.POINTER(ctypes.c_double)
            lib.hs_fused_filter_select.restype = ctypes.c_int64
            lib.hs_fused_filter_select.argtypes = [
                _vpp, _vpp, _u8p, _i64p, _i64p, _dp, _dp,
                _u8p, _u8p, _u8p, _u8p,
                ctypes.c_int32, ctypes.c_int64, _i64p, ctypes.c_int32,
            ]
            lib.hs_fused_filter_agg.restype = ctypes.c_int64
            lib.hs_fused_filter_agg.argtypes = [
                # filter terms
                _vpp, _vpp, _u8p, _i64p, _i64p, _dp, _dp,
                _u8p, _u8p, _u8p, _u8p, ctypes.c_int32,
                # group keys
                _vpp, _vpp, _u8p, ctypes.c_int32,
                # aggs
                _vpp, _vpp, _u8p, ctypes.c_int32,
                # rows
                ctypes.c_int64, ctypes.c_int64,
                # state
                _i64p, ctypes.c_int64,
                _i64p, _i64p, _u8p, _i64p, _u8p,
                _i64p, _dp, _i64p, _i64p,
                ctypes.c_int64, _i64p, _i64p, ctypes.c_int32,
            ]
            _f64p = ctypes.POINTER(ctypes.c_double)
            lib.hs_gather_i64.restype = ctypes.c_int
            lib.hs_gather_i64.argtypes = [
                _i64p,
                ctypes.c_int64,
                _i64p,
                ctypes.c_int64,
                _i64p,
                ctypes.c_int32,
            ]
            lib.hs_gather_f64.restype = ctypes.c_int
            lib.hs_gather_f64.argtypes = [
                _f64p,
                ctypes.c_int64,
                _i64p,
                ctypes.c_int64,
                _f64p,
                ctypes.c_int32,
            ]
        except (OSError, AttributeError):
            _load_failed = True
            return None
        try:
            # refresh the liveness timestamp _cleanup_superseded gates
            # on: a revision that only ever LOADS its cached .so must
            # not age past the TTL and get reaped by a sibling checkout
            os.utime(path)
        except OSError:
            pass
        # sweep stale artifacts on every successful load, not only after
        # a compile: a steady-state process never compiles, so orphaned
        # .tmp.<pid> files and superseded revisions would otherwise
        # outlive every producer
        _cleanup_superseded(path)
        _lib = lib
        return _lib
    finally:
        _lock.release()


def _cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def core_budget() -> int:
    """Threads one stage of a build's tail may keep busy at once: the
    cores this process may run on, at most 16 — THE budget the per-bucket
    sorts (``ops/sort._sort_pool_plan``), the shard tails
    (``ops/sort.shard_tail_plan``), the bucket-file writers
    (``indexes/covering_build``) and the aggregate capture
    (``indexes/aggindex._map_tasks``) each split or take."""
    return min(_cores(), 16)


def _n_threads(n: int) -> int:
    """Thread count scaled to the input: one thread per ~64k rows, capped
    by cores and 16. Just-above-threshold inputs (32k rows) would
    otherwise pay 15 thread spawn/joins per byte pass for ~2k-row chunks
    — more overhead than the whole numpy sort."""
    return max(1, min(core_budget(), n >> 16))


def lexsort_u32(
    planes: np.ndarray, n_threads: Optional[int] = None
) -> Optional[np.ndarray]:
    """Stable ascending lexsort permutation by uint32 ``planes`` [k, n]
    (plane 0 major) — bit-identical to ``np.lexsort(planes[::-1])``.
    Returns None when the native kernel is unavailable, so callers fall
    back to numpy. ``n_threads`` overrides the size-scaled default — the
    partitioned build runs many per-bucket sorts on its own pool and
    gives each sort a slice of the core budget."""
    lib = load(wait=False)
    if lib is None:
        return None
    planes = np.ascontiguousarray(planes, dtype=np.uint32)
    k, n = planes.shape
    out = np.empty(n, dtype=np.int64)
    ptrs = (ctypes.c_void_p * k)(
        *(planes[i].ctypes.data for i in range(k))
    )
    rc = lib.hs_lexsort_u32(
        ptrs,
        ctypes.c_int32(k),
        ctypes.c_int64(n),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int32(n_threads if n_threads else _n_threads(n)),
    )
    if rc != 0:
        return None
    return out


def partition_by_bucket_i32(
    bucket_ids: np.ndarray, num_buckets: int
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Stable counting scatter of row indices by int32 bucket id:
    ``(order, offsets)`` where ``order[offsets[b]:offsets[b+1]]`` holds
    bucket ``b``'s row indices in original order — bit-identical to
    ``np.argsort(bucket_ids, kind="stable")`` plus a bincount prefix sum
    (the numpy twin, ``ops/sort.partition_by_bucket``). Returns None when
    the native kernel is unavailable or the ids are malformed."""
    lib = load(wait=False)
    if lib is None:
        return None
    bucket_ids = np.ascontiguousarray(bucket_ids, dtype=np.int32)
    n = len(bucket_ids)
    order = np.empty(n, dtype=np.int64)
    offsets = np.empty(num_buckets + 1, dtype=np.int64)
    _i64p = ctypes.POINTER(ctypes.c_int64)
    rc = lib.hs_partition_by_bucket(
        bucket_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_int64(n),
        ctypes.c_int32(num_buckets),
        order.ctypes.data_as(_i64p),
        offsets.ctypes.data_as(_i64p),
        ctypes.c_int32(_n_threads(n)),
    )
    if rc != 0:
        return None
    return order, offsets


def split_words_i64(
    key_reps: np.ndarray,
    out: np.ndarray,
    hi_xor: int = 0,
    hi_first: bool = False,
    pad: int = 0,
) -> bool:
    """The int64 -> uint32 word split of ``[k, n]`` key reps, one
    threaded pass a key column, written straight into the caller's
    C-contiguous ``uint32[2k, row_len >= n]`` block: key ``j``'s low
    words into row ``2j`` and its high words, XORed with ``hi_xor``, into
    row ``2j + 1`` (the other way round under ``hi_first``), and every
    row's slots past ``n`` set to ``pad``. Bit-exact twin of
    ``ops/hash.split_words_np`` plus its zero tail (``hi_xor`` 0) and of
    ``ops/sort._order_words_numpy`` (``hi_xor`` 0x80000000,
    ``hi_first``). False — nothing of ``out`` is then to be relied on,
    the caller runs the numpy twin — when the kernel is unavailable or a
    key row is not 8-byte integers laid out contiguously."""
    if (
        out.dtype != np.uint32
        or not out.flags.c_contiguous
        or not out.flags.writeable
        or out.ndim != 2
        or key_reps.ndim != 2
        or out.shape[0] != 2 * key_reps.shape[0]
        or out.shape[1] < key_reps.shape[1]
    ):
        # the kernel writes through raw row pointers: a contract
        # violation must be loud, never a clobbered neighbour
        raise ValueError(
            "split_words_i64 requires a writeable C-contiguous "
            "uint32[2k, >= n] output"
        )
    lib = load(wait=False)
    if lib is None:
        return False
    k, n = key_reps.shape
    if key_reps.dtype not in (np.int64, np.uint64) or (
        n > 1 and key_reps.strides[1] != 8
    ):
        return False
    _i64p = ctypes.POINTER(ctypes.c_int64)
    _u32p = ctypes.POINTER(ctypes.c_uint32)
    row_len = out.shape[1]
    threads = _n_threads(row_len)
    for j in range(k):
        lo_row, hi_row = (2 * j + 1, 2 * j) if hi_first else (2 * j, 2 * j + 1)
        rc = lib.hs_split_words_i64(
            key_reps[j].ctypes.data_as(_i64p),
            ctypes.c_int64(n),
            out[lo_row].ctypes.data_as(_u32p),
            out[hi_row].ctypes.data_as(_u32p),
            ctypes.c_int64(row_len),
            ctypes.c_uint32(hi_xor),
            ctypes.c_uint32(pad),
            ctypes.c_int32(threads),
        )
        if rc != 0:
            return False
    return True


def order_u64(
    values: np.ndarray, validity: Optional[np.ndarray] = None
) -> Optional[np.ndarray]:
    """The order-preserving uint64 of one numeric column, one threaded
    pass into a fresh array: int64 (dates and timestamps as their int64
    view) with the sign bit flipped, float64 bits in IEEE total order,
    and under a bool ``validity`` mask ``max(enc, 1)`` on valid rows and
    0 on nulls. Bit-exact twin of ``ops/zorder.order_u64_numpy``. None —
    the caller runs the twin — when the kernel is unavailable or the
    column is not contiguous little-endian int64/float64 with a
    contiguous mask of its length."""
    lib = load(wait=False)
    if lib is None:
        return None
    if (
        values.ndim != 1
        or values.dtype not in (np.int64, np.float64)
        or not values.flags.c_contiguous
    ):
        return None
    n = len(values)
    if validity is not None and (
        validity.dtype != np.bool_
        or validity.shape != (n,)
        or not validity.flags.c_contiguous
    ):
        return None
    out = np.empty(n, dtype=np.uint64)
    rc = lib.hs_order_u64(
        ctypes.c_void_p(values.ctypes.data),
        ctypes.c_int64(n),
        ctypes.c_int32(values.dtype == np.float64),
        None
        if validity is None
        else validity.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        ctypes.c_int32(_n_threads(n)),
    )
    return out if rc == 0 else None


def zorder_words(
    enc: np.ndarray, mn: int, scale: float, top: int, out: np.ndarray
) -> bool:
    """One column's z-words under a range spec, one threaded pass
    written straight into ``out`` (a C-contiguous ``uint32`` row of at
    least ``len(enc)`` slots, a row of the block the interleave
    uploads): ``trunc(clip(float64(enc - mn) * scale, 0, top))``, and
    ``top`` in every slot past ``len(enc)``. ``scale`` is the caller's
    float64, used as given. Bit-exact twin of
    ``ops/zorder.range_words_np`` plus that fill. False — nothing of
    ``out`` is then to be relied on, the caller runs the twin — when the
    kernel is unavailable or ``enc`` is not contiguous uint64."""
    if (
        out.dtype != np.uint32
        or out.ndim != 1
        or not out.flags.c_contiguous
        or not out.flags.writeable
        or enc.ndim != 1
        or len(out) < len(enc)
    ):
        # the kernel writes through a raw pointer: a contract violation
        # must be loud, never a clobbered neighbour
        raise ValueError(
            "zorder_words requires a writeable C-contiguous uint32[>= n] "
            "output"
        )
    lib = load(wait=False)
    if lib is None:
        return False
    if enc.dtype != np.uint64 or not enc.flags.c_contiguous:
        return False
    row_len = len(out)
    rc = lib.hs_zorder_words(
        enc.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        ctypes.c_int64(len(enc)),
        ctypes.c_uint64(int(mn)),
        ctypes.c_double(float(scale)),
        ctypes.c_uint32(int(top)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        ctypes.c_int64(row_len),
        ctypes.c_int32(_n_threads(row_len)),
    )
    return rc == 0


def merge_join_count_i64(
    l_sorted: np.ndarray, r_sorted: np.ndarray
) -> Optional[int]:
    """Pair count of the inner join of two ASCENDING-sorted int64 key
    arrays (one linear merge, no allocation), or None when the native
    kernel is unavailable."""
    lib = load(wait=False)
    if lib is None:
        return None
    l_sorted = np.ascontiguousarray(l_sorted, dtype=np.int64)
    r_sorted = np.ascontiguousarray(r_sorted, dtype=np.int64)
    _i64p = ctypes.POINTER(ctypes.c_int64)
    return lib.hs_merge_join_count_i64(
        l_sorted.ctypes.data_as(_i64p),
        len(l_sorted),
        r_sorted.ctypes.data_as(_i64p),
        len(r_sorted),
    )


def merge_join_emit_into(
    l_sorted: np.ndarray,
    r_sorted: np.ndarray,
    li_out: np.ndarray,
    ri_out: np.ndarray,
    l_bias: int = 0,
    r_bias: int = 0,
) -> bool:
    """Emit the join pairs (biased by l_bias/r_bias) into the caller's
    preallocated CONTIGUOUS int64 slices, whose length must equal
    ``merge_join_count_i64``'s result. Returns False when the native
    kernel is unavailable or the emitted count mismatches."""
    for out in (li_out, ri_out):
        # the kernel writes int64 through the raw base pointer — a
        # strided view or other dtype would be silently clobbered, so
        # make the contract violation loud (programming error, not a
        # fall-back condition)
        if out.dtype != np.int64 or not out.flags.c_contiguous:
            raise ValueError(
                "merge_join_emit_into requires C-contiguous int64 outputs"
            )
    lib = load(wait=False)
    if lib is None:
        return False
    l_sorted = np.ascontiguousarray(l_sorted, dtype=np.int64)
    r_sorted = np.ascontiguousarray(r_sorted, dtype=np.int64)
    _i64p = ctypes.POINTER(ctypes.c_int64)
    emitted = lib.hs_merge_join_emit_i64(
        l_sorted.ctypes.data_as(_i64p),
        len(l_sorted),
        r_sorted.ctypes.data_as(_i64p),
        len(r_sorted),
        ctypes.c_int64(l_bias),
        ctypes.c_int64(r_bias),
        li_out.ctypes.data_as(_i64p),
        ri_out.ctypes.data_as(_i64p),
    )
    return emitted == len(li_out)


def merge_join_i64(
    l_sorted: np.ndarray, r_sorted: np.ndarray
) -> Optional[tuple]:
    """Inner-join pair indices (li, ri) of two ASCENDING-sorted int64 key
    arrays (duplicates allowed): one linear merge per pass, pairs ordered
    by left index then right index — identical to the numpy
    searchsorted + repeat expansion it replaces. Returns None when the
    native kernel is unavailable."""
    total = merge_join_count_i64(l_sorted, r_sorted)
    if total is None:
        return None
    li = np.empty(total, dtype=np.int64)
    ri = np.empty(total, dtype=np.int64)
    if total and not merge_join_emit_into(l_sorted, r_sorted, li, ri):
        return None  # pragma: no cover — would be a kernel bug
    return li, ri


def expand_match_ranges_i64(
    lo: np.ndarray,
    cnt: np.ndarray,
    total: int,
    l_map: Optional[np.ndarray] = None,
    r_map: Optional[np.ndarray] = None,
    l_bias: int = 0,
    r_bias: int = 0,
    n_threads: Optional[int] = None,
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Expand per-left-row match ranges ``(lo, cnt)`` into (li, ri) pairs
    with optional index maps and biases — bit-identical to the numpy
    repeat/cumsum chain (``ops/join.expand_match_ranges_numpy``, the
    registered twin). ``total`` must equal ``cnt.sum()`` (callers already
    have it from the count pass); the kernel re-validates it against its
    own prefix sum BEFORE writing, and bounds-checks the maps, so a
    malformed call can never overrun the buffers — it returns None and
    the numpy fallback raises the appropriate error instead."""
    lib = load(wait=False)
    if lib is None:
        return None
    lo = np.ascontiguousarray(lo, dtype=np.int64)
    cnt = np.ascontiguousarray(cnt, dtype=np.int64)
    _i64p = ctypes.POINTER(ctypes.c_int64)

    def p(a):
        if a is None:
            return ctypes.cast(None, _i64p)
        return a.ctypes.data_as(_i64p)

    if l_map is not None:
        l_map = np.ascontiguousarray(l_map, dtype=np.int64)
    if r_map is not None:
        r_map = np.ascontiguousarray(r_map, dtype=np.int64)
    li = np.empty(total, dtype=np.int64)
    ri = np.empty(total, dtype=np.int64)
    emitted = lib.hs_expand_match_ranges_i64(
        lo.ctypes.data_as(_i64p),
        cnt.ctypes.data_as(_i64p),
        ctypes.c_int64(len(lo)),
        p(l_map),
        ctypes.c_int64(0 if l_map is None else len(l_map)),
        p(r_map),
        ctypes.c_int64(0 if r_map is None else len(r_map)),
        ctypes.c_int64(l_bias),
        ctypes.c_int64(r_bias),
        li.ctypes.data_as(_i64p),
        ri.ctypes.data_as(_i64p),
        ctypes.c_int64(total),
        ctypes.c_int32(n_threads if n_threads else _n_threads(total)),
    )
    if emitted != total:
        return None
    return li, ri


def _gather_64(values: np.ndarray, idx: np.ndarray) -> Optional[np.ndarray]:
    """Shared driver of the 8-byte gathers; ``values`` dtype picks the
    export. Returns None (numpy fallback) when the kernel is unavailable
    or any index is out of range — numpy's negative-index wrapping and
    IndexError semantics are preserved by falling back, never emulated."""
    lib = load(wait=False)
    if lib is None:
        return None
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    out = np.empty(len(idx), dtype=values.dtype)
    _i64p = ctypes.POINTER(ctypes.c_int64)
    if values.dtype == np.float64:
        _f64p = ctypes.POINTER(ctypes.c_double)
        rc = lib.hs_gather_f64(
            values.ctypes.data_as(_f64p),
            ctypes.c_int64(len(values)),
            idx.ctypes.data_as(_i64p),
            ctypes.c_int64(len(idx)),
            out.ctypes.data_as(_f64p),
            ctypes.c_int32(_n_threads(len(idx))),
        )
    else:
        rc = lib.hs_gather_i64(
            values.ctypes.data_as(_i64p),
            ctypes.c_int64(len(values)),
            idx.ctypes.data_as(_i64p),
            ctypes.c_int64(len(idx)),
            out.ctypes.data_as(_i64p),
            ctypes.c_int32(_n_threads(len(idx))),
        )
    if rc != 0:
        return None
    return out


def gather_i64(
    values: np.ndarray, idx: np.ndarray
) -> Optional[np.ndarray]:
    """Threaded bounds-checked ``values[idx]`` for contiguous int64
    arrays — bit-exact twin of ``numpy.take`` on in-range indices. None
    on unavailability or out-of-range indices (numpy fallback)."""
    values = np.ascontiguousarray(values, dtype=np.int64)
    return _gather_64(values, idx)


def gather_f64(
    values: np.ndarray, idx: np.ndarray
) -> Optional[np.ndarray]:
    """Threaded bounds-checked ``values[idx]`` for contiguous float64
    arrays — bit-exact twin of ``numpy.take`` (bitwise moves: NaN
    payloads survive). None on unavailability or out-of-range indices."""
    values = np.ascontiguousarray(values, dtype=np.float64)
    return _gather_64(values, idx)


def _u8_flags(xs) -> np.ndarray:
    return np.asarray([1 if x else 0 for x in xs], dtype=np.uint8)


def _term_args(cols, valids, is_f64, lo_i, hi_i, lo_f, hi_f, flags):
    """The 11 leading ctypes arguments every range-term kernel takes
    (hs_range_mask / hs_fused_filter_select / hs_fused_filter_agg's
    filter section). Returns (args, keepalive): ``keepalive`` pins the
    temporary numpy arrays for the duration of the call."""
    k = len(cols)
    _u8p = ctypes.POINTER(ctypes.c_uint8)
    _i64p = ctypes.POINTER(ctypes.c_int64)
    _f64p = ctypes.POINTER(ctypes.c_double)
    col_ptrs = (ctypes.c_void_p * k)(*(c.ctypes.data for c in cols))
    valid_arrs = [
        None if v is None else np.ascontiguousarray(v, dtype=np.uint8)
        for v in valids
    ]
    valid_ptrs = (ctypes.c_void_p * k)(
        *(None if v is None else v.ctypes.data for v in valid_arrs)
    )
    is_f64_a = _u8_flags(is_f64)
    has_lo = _u8_flags(f[0] for f in flags)
    has_hi = _u8_flags(f[1] for f in flags)
    lo_strict = _u8_flags(f[2] for f in flags)
    hi_strict = _u8_flags(f[3] for f in flags)
    lo_i_a = np.asarray(lo_i, dtype=np.int64)
    hi_i_a = np.asarray(hi_i, dtype=np.int64)
    lo_f_a = np.asarray(lo_f, dtype=np.float64)
    hi_f_a = np.asarray(hi_f, dtype=np.float64)
    keep = (
        cols, valid_arrs, is_f64_a, has_lo, has_hi, lo_strict, hi_strict,
        lo_i_a, hi_i_a, lo_f_a, hi_f_a,
    )
    args = [
        col_ptrs,
        valid_ptrs,
        is_f64_a.ctypes.data_as(_u8p),
        lo_i_a.ctypes.data_as(_i64p),
        hi_i_a.ctypes.data_as(_i64p),
        lo_f_a.ctypes.data_as(_f64p),
        hi_f_a.ctypes.data_as(_f64p),
        has_lo.ctypes.data_as(_u8p),
        has_hi.ctypes.data_as(_u8p),
        lo_strict.ctypes.data_as(_u8p),
        hi_strict.ctypes.data_as(_u8p),
    ]
    return args, keep


def range_mask_u8(
    cols,
    valids,
    is_f64,
    lo_i,
    hi_i,
    lo_f,
    hi_f,
    flags,
    n: int,
) -> Optional[np.ndarray]:
    """Fused range mask over ``k`` terms: per term a contiguous 8-byte
    column array (int64 view or float64), optional bool validity, and
    lo/hi bounds with ``flags`` = (has_lo, has_hi, lo_strict, hi_strict)
    — the single-pass twin of ``ops/filter.range_mask_numpy`` (the
    registered KERNEL_TWINS reference). Returns a bool mask, or None when
    the native kernel is unavailable (caller runs the numpy twin)."""
    lib = load(wait=False)
    if lib is None:
        return None
    k = len(cols)
    if k == 0 or n == 0:
        return np.ones(n, dtype=bool)
    args, _keep = _term_args(cols, valids, is_f64, lo_i, hi_i, lo_f, hi_f, flags)
    out = np.empty(n, dtype=np.uint8)
    _u8p = ctypes.POINTER(ctypes.c_uint8)
    rc = lib.hs_range_mask(
        *args,
        ctypes.c_int32(k),
        ctypes.c_int64(n),
        out.ctypes.data_as(_u8p),
        ctypes.c_int32(_n_threads(n)),
    )
    if rc != 0:
        return None
    return out.view(np.bool_)


def fused_filter_select(
    cols,
    valids,
    is_f64,
    lo_i,
    hi_i,
    lo_f,
    hi_f,
    flags,
    n: int,
) -> Optional[np.ndarray]:
    """Passing row indices (ascending int64) of the fused range-term
    conjunction — one pass computing AND compacting, replacing the
    interpreted chain's materialized mask + ``np.nonzero`` (the
    registered twin: ``pipeline_compiler.filter_select_interpreted``).
    Same term layout as :func:`range_mask_u8`. Returns None when the
    native kernel is unavailable (caller runs the interpreted chain)."""
    lib = load(wait=False)
    if lib is None:
        return None
    k = len(cols)
    if k == 0 or n == 0:
        return np.arange(n, dtype=np.int64)
    args, _keep = _term_args(cols, valids, is_f64, lo_i, hi_i, lo_f, hi_f, flags)
    out = np.empty(n, dtype=np.int64)
    _i64p = ctypes.POINTER(ctypes.c_int64)
    got = lib.hs_fused_filter_select(
        *args,
        ctypes.c_int32(k),
        ctypes.c_int64(n),
        out.ctypes.data_as(_i64p),
        ctypes.c_int32(_n_threads(n)),
    )
    if got < 0:
        return None
    # copy: the n-capacity scratch must not stay pinned behind a small view
    return out[:got].copy()


def fused_filter_agg(
    f_cols,
    f_valids,
    f_is_f64,
    f_lo_i,
    f_hi_i,
    f_lo_f,
    f_hi_f,
    f_flags,
    k_cols,
    k_valids,
    k_is_f64,
    a_cols,
    a_valids,
    a_ops,
    n: int,
    row_start: int,
    ht: np.ndarray,
    g_hash: np.ndarray,
    g_reps: np.ndarray,
    g_nulls: np.ndarray,
    g_kvals: np.ndarray,
    g_kvalid: np.ndarray,
    acc_i: np.ndarray,
    acc_f: np.ndarray,
    acc_cnt: np.ndarray,
    acc_aux: np.ndarray,
    n_groups: int,
    rows_passed: int,
    rebuild: bool,
) -> Optional[Tuple[int, int, int]]:
    """One chunk through the fused filter→group→aggregate pass
    (``hs_fused_filter_agg``; state contract documented on the kernel).
    Returns ``(rows_consumed, n_groups, rows_passed)`` — consumed <
    ``n - row_start`` means the group table filled and the caller must
    grow the state and re-call at the new offset — or None when the
    native kernel is unavailable or rejects the arguments (caller runs
    the interpreted twin, ``pipeline_compiler.interpreted_filter_aggregate``)."""
    lib = load(wait=False)
    if lib is None:
        return None
    targs, _keep = _term_args(
        f_cols, f_valids, f_is_f64, f_lo_i, f_hi_i, f_lo_f, f_hi_f, f_flags
    )
    n_keys = len(k_cols)
    n_aggs = len(a_ops)
    key_ptrs = (ctypes.c_void_p * max(n_keys, 1))(
        *(c.ctypes.data for c in k_cols) if n_keys else (None,)
    )
    kvalid_arrs = [
        None if v is None else np.ascontiguousarray(v, dtype=np.uint8)
        for v in k_valids
    ]
    kvalid_ptrs = (ctypes.c_void_p * max(n_keys, 1))(
        *(None if v is None else v.ctypes.data for v in kvalid_arrs)
        if n_keys
        else (None,)
    )
    k_is_f64_a = _u8_flags(k_is_f64)
    agg_ptrs = (ctypes.c_void_p * max(n_aggs, 1))(
        *(None if c is None else c.ctypes.data for c in a_cols)
        if n_aggs
        else (None,)
    )
    avalid_arrs = [
        None if v is None else np.ascontiguousarray(v, dtype=np.uint8)
        for v in a_valids
    ]
    avalid_ptrs = (ctypes.c_void_p * max(n_aggs, 1))(
        *(None if v is None else v.ctypes.data for v in avalid_arrs)
        if n_aggs
        else (None,)
    )
    a_ops_a = np.asarray(a_ops, dtype=np.uint8)
    _u8p = ctypes.POINTER(ctypes.c_uint8)
    _i64p = ctypes.POINTER(ctypes.c_int64)
    _f64p = ctypes.POINTER(ctypes.c_double)
    ng = ctypes.c_int64(n_groups)
    rp = ctypes.c_int64(rows_passed)
    consumed = lib.hs_fused_filter_agg(
        *targs,
        ctypes.c_int32(len(f_cols)),
        key_ptrs,
        kvalid_ptrs,
        k_is_f64_a.ctypes.data_as(_u8p),
        ctypes.c_int32(n_keys),
        agg_ptrs,
        avalid_ptrs,
        a_ops_a.ctypes.data_as(_u8p),
        ctypes.c_int32(n_aggs),
        ctypes.c_int64(n),
        ctypes.c_int64(row_start),
        ht.ctypes.data_as(_i64p),
        ctypes.c_int64(len(ht)),
        g_hash.ctypes.data_as(_i64p),
        g_reps.ctypes.data_as(_i64p),
        g_nulls.ctypes.data_as(_u8p),
        g_kvals.ctypes.data_as(_i64p),
        g_kvalid.ctypes.data_as(_u8p),
        acc_i.ctypes.data_as(_i64p),
        acc_f.ctypes.data_as(_f64p),
        acc_cnt.ctypes.data_as(_i64p),
        acc_aux.ctypes.data_as(_i64p),
        ctypes.c_int64(g_reps.shape[1] if g_reps.ndim == 2 else len(g_hash)),
        ctypes.byref(ng),
        ctypes.byref(rp),
        ctypes.c_int32(1 if rebuild else 0),
    )
    if consumed < 0:
        return None
    return int(consumed), int(ng.value), int(rp.value)


def bucket_ids_i64(
    key_reps: np.ndarray, num_buckets: int, seed: int = 42
) -> Optional[np.ndarray]:
    """Murmur3-32 bucket ids over [k, n] int64 key reps in one pass per
    row — bit-exact twin of ``ops/hash.bucket_ids_host``. Returns None
    when the native kernel is unavailable."""
    lib = load(wait=False)
    if lib is None:
        return None
    key_reps = np.ascontiguousarray(key_reps, dtype=np.int64)
    k, n = key_reps.shape
    out = np.empty(n, dtype=np.int32)
    ptrs = (ctypes.c_void_p * k)(
        *(key_reps[i].ctypes.data for i in range(k))
    )
    rc = lib.hs_bucket_ids_i64(
        ptrs,
        ctypes.c_int32(k),
        ctypes.c_int64(n),
        ctypes.c_uint32(seed),
        ctypes.c_uint32(num_buckets),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    if rc != 0:
        return None
    return out
