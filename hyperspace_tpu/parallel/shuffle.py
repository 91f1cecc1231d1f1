"""Exchange-strategy plane: pluggable bucketing shuffles over the mesh.

TPU-native replacement for the Spark hash-partition shuffle at the heart
of the covering-index build (reference: ``index/covering/CoveringIndex.
scala:58-61`` ``repartition(numBuckets, indexedCols)``), rebuilt as a
*library of exchange strategies* behind one interface — the Exoshuffle
doctrine (PAPERS.md): shuffle belongs to the application as composable
strategies, not one engine-baked implementation. The strategy is chosen
per build by ``hyperspace.build.exchange.strategy`` (default ``auto``:
per-machine/topology resolution, see :func:`resolve_strategy`):

``compact``
    The single-host accelerator default. Host-packed variable-length
    exchange, its host side driven by ONE
    counting partition an exchange, keyed ``(source shard, owner,
    bucket)``: the host bucket ids (hashed once, natively) become the
    int32 key ``source * num_buckets + rank of the bucket in (owner,
    bucket) order``, and the plan, the pack and the unpack are all read
    off that partition's ``order`` and ``offsets``. The plan's
    ``[D, D]`` peer counts are sums of its run lengths (cap = max count
    at three significant bits — no power-of-two blowup); the pack is
    one gather a payload by ``order`` (which lists every (source,
    owner) slot's rows contiguously, grouped by bucket, original order
    inside a bucket) and ``D*D`` contiguous copies into the zeroed
    ``[D*D, cap]`` send buffer; the device program is ONE
    ``all_to_all`` per payload with no on-device hashing, scatter or
    argsort; the unpack copies at most ``D * num_buckets`` contiguous
    runs a payload from the received slots straight into canonical
    order — no ranks, no position arrays, no scatter and no random
    gather of received rows. Moves only the payload bytes (no
    bucket/validity planes).
``host``
    No device round-trip at all: rows are reordered in host RAM with the
    canonical post-exchange permutation (threaded native/numpy gathers).
    The CPU-simulation default — an emulated ICI exchange on a CPU mesh
    pays real pack/argsort/copy costs to move rows between host buffers
    that live in the same RAM — the numpy reference the other
    strategies are differential-tested against, and the per-host leg of
    a multi-host decomposition.
``twostage``
    The DCN/ICI decomposition from docs/MULTIHOST.md: the intra-host leg
    runs host-side (each host re-groups its rows in RAM by destination
    lane), and the cross-host leg is one ``ppermute`` round per peer
    host over the ``dcn`` mesh axis with **per-peer slot caps** sized
    from the per-(shard, peer) count matrix — the skew telemetry is the
    slot-sizing input, not only a warning (one hot destination host
    inflates only the rounds that target it, not every slot).

Every strategy produces BIT-IDENTICAL output: the rows stable-sorted by
``(bucket % D, bucket)`` with ties in original row order (shard ``s``
holds the buckets it owns ascending; inside a bucket the received rows
concatenate source-major, sources hold local row order), which
:func:`canonical_order` computes host-side from the bucket ids alone.
``tests/test_exchange_strategies.py`` holds each strategy to ``host``
and ``canonical_order`` to a numpy lexsort across mesh sizes, payload
types and skews.

(For >HBM datasets the same exchange runs once per wave over chunked
host batches — the wave loop is ``indexes/covering_build.
_write_bucketed_streaming``, driven by
``hyperspace.index.build.memoryBudgetBytes``.)

Every device program here that issues a collective
(``_compact_program``, ``_twostage_program``, and the
``process_allgather`` in ``_twostage_exchange_mp``) is registered in
``COLLECTIVE_SITES`` (``parallel/collectives.py``) with its symmetry
contract — add a collective without registering it and hslint HS802
goes red; the multi-host dryrun's collective witness then has to
exercise it (HS703/HS804).
"""

from __future__ import annotations

import contextlib
import functools
import logging
import time as _time
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from hyperspace_tpu.obs import trace as _obs_trace

_log = logging.getLogger("hyperspace_tpu.shuffle")

# Telemetry of the most recent ``bucket_shuffle`` (host-observed):
# strategy name, the seconds of its stages (``plan_s`` / ``pack_s`` /
# ``exchange_s`` / ``unpack_s`` — the SAME measurements as the
# ``exchange_plan`` / ``pack`` / ``exchange`` / ``unpack`` spans, see
# :func:`_timed`), exchange capacity, the bytes that had to cross chips
# (``wire_bytes``) beside the bytes that were sent (``slot_bytes``) and
# the per-(shard, peer) send-count skew. The padded-buffer strategies
# size slots from the MAX count, so one hot bucket inflates exchange
# memory by ~skew× silently — the build copies this into its telemetry
# (accumulating per-wave skew as max/mean) and the bench publishes it.
last_shuffle_stats: Dict[str, float] = {}

# Once-per-build latch for the shuffle-skew warning: the streaming build
# runs one exchange per wave and the same skew would otherwise log every
# wave. ``covering_build.reset_build_breakdown`` rearms it at each data
# op via :func:`reset_skew_warning`; telemetry records the ratio for
# every wave regardless.
_skew_warned: bool = False

from hyperspace_tpu.ops.hash import bucket_ids_host
from hyperspace_tpu.ops.sort import partition_by_bucket
from hyperspace_tpu.parallel.mesh import (
    DCN_AXIS,
    ICI_AXIS,
    SHARD_AXIS,
    mesh_dispatch_lock,
    put_sharded,
)

STRATEGY_AUTO = "auto"
STRATEGY_COMPACT = "compact"
STRATEGY_HOST = "host"
STRATEGY_TWOSTAGE = "twostage"
STRATEGIES = (STRATEGY_COMPACT, STRATEGY_HOST, STRATEGY_TWOSTAGE)

# ``last_shuffle_stats`` key -> the span whose seconds it holds
STAGE_SECONDS_KEYS = {
    "plan_s": "exchange_plan",
    "pack_s": "pack",
    "exchange_s": "exchange",
    "unpack_s": "unpack",
}
# ``last_shuffle_stats`` keys that are bytes: each also a root counter
# ``exchange_<key>`` of the running action, summed over waves
BYTES_KEYS = ("h2d_bytes", "d2h_bytes", "wire_bytes", "slot_bytes")


def reset_skew_warning() -> None:
    """Rearm the once-per-build skew warning (called by
    ``covering_build.reset_build_breakdown`` at every data-op entry)."""
    global _skew_warned
    _skew_warned = False


# ---------------------------------------------------------------------------
# Shared host-side planning: bucket ids, counts, canonical order
# ---------------------------------------------------------------------------


def _host_bucket_ids(
    key_reps: np.ndarray, num_buckets: int, seed: int, chunk: int = 1 << 18
) -> np.ndarray:
    """Chunked host murmur3 bucket ids — bit-identical to the device
    hash (``ops/hash.py`` twins) and computed ONCE per exchange: every
    strategy reuses these ids for capacity planning, packing and
    ordering instead of re-hashing on device."""
    n = key_reps.shape[1]
    out = np.empty(n, dtype=np.int32)
    for start in range(0, n, chunk):
        end = min(start + chunk, n)
        out[start:end] = bucket_ids_host(
            key_reps[:, start:end], num_buckets, seed
        )
    return out


def _peer_counts(owner: np.ndarray, n_local: int, D: int) -> np.ndarray:
    """``[D, D]`` count of rows each source shard (contiguous
    ``n_local``-row blocks) sends to each owner shard — the
    skew-telemetry input of ``host`` and ``twostage``."""
    src = (np.arange(len(owner)) // n_local).astype(np.int64)
    return np.bincount(src * D + owner, minlength=D * D).reshape(D, D)


@contextlib.contextmanager
def _timed(acct: Dict, key: str):
    """One measurement, two views (as ``covering_build.stage`` does for
    the build's named stages): enter the span ``STAGE_SECONDS_KEYS``
    names for ``key`` — a ``trace.span`` of the running action, with its
    interval on the one clock and its ``hs.<name>`` profiler annotation —
    and add that span's OWN seconds to ``acct[key]``, the exchange's
    account that ``_publish_stats`` turns into ``last_shuffle_stats``.
    Outside an action (a bare ``bucket_shuffle``) the span is the no-op
    singleton and the block's own clock feeds the account."""
    t0 = _time.perf_counter_ns()
    sp = _obs_trace.NOOP
    try:
        with _obs_trace.span(STAGE_SECONDS_KEYS[key]) as sp:
            yield sp
    finally:
        seconds = sp.duration_s
        if seconds is None:
            seconds = (_time.perf_counter_ns() - t0) / 1e9
        acct[key] = acct.get(key, 0.0) + seconds


def _skew_ratio(counts: np.ndarray) -> float:
    """Hottest (source, peer) slot over the mean slot."""
    mean_count = float(counts.mean()) if counts.size else 0.0
    return float(counts.max()) / mean_count if mean_count > 0 else 1.0


def _plan_attrs(sp, strategy: str, D: int, cap: int, counts: np.ndarray) -> None:
    """What the ``exchange_plan`` span decided, on the span."""
    sp.set("strategy", strategy)
    sp.set("devices", D)
    sp.set("cap", int(cap))
    sp.set("skew_ratio", round(_skew_ratio(counts), 2))


def _nbytes(arrays) -> int:
    return int(sum(a.nbytes for a in jax.tree_util.tree_leaves(arrays)))


def _transferred(sp, acct: Dict, key: str, arrays) -> None:
    """The bytes of one transfer: on its span and into the account."""
    n = _nbytes(arrays)
    sp.set("bytes", n)
    acct[key] = acct.get(key, 0) + n


def _off_chip_rows(counts: np.ndarray) -> int:
    """Rows of the ``[D, D]`` (source, owner) matrix whose owner is
    another chip than their source."""
    return int(counts.sum() - np.trace(counts))


def _fetch_shards(out) -> Tuple[List[List[np.ndarray]], Dict[str, int]]:
    """The outputs of an exchange program back on the host, shard by
    shard: per output, its addressable shards in index order, each the
    flat view of the host buffer the runtime copied it into.

    Every output's ``copy_to_host_async()`` is called before any shard
    is read, so all payloads x devices copies are in flight at once;
    ``np.asarray`` of a whole sharded output would start that output's
    copies only, wait, and copy every shard a second time into a fresh
    array of the full shape — the unpacks read inside one shard at a
    time and never need it. Any dtype an exchange carries comes back as
    it went (8-byte values and key reps, 4-byte codes, validity planes).

    Also the fetch's account, for the ``d2h`` span: ``shards`` (buffers
    read in place), ``started`` (copies started before the first read)
    and ``assembled_bytes`` (bytes copied a second time on the host:
    none — nothing here concatenates or allocates)."""
    started = 0
    for o in out:
        o.copy_to_host_async()
        started += len(o.addressable_shards)
    host = [
        [
            np.asarray(shard.data).reshape(-1)
            for shard in sorted(
                o.addressable_shards,
                key=lambda shard: tuple(sl.start or 0 for sl in shard.index),
            )
        ]
        for o in out
    ]
    account = {
        "shards": sum(len(shards) for shards in host),
        "started": started,
        "assembled_bytes": 0,
    }
    return host, account


def _device_leg(acct: Dict, put: Callable, run: Callable):
    """The device leg of an exchange, as the host sees it — the
    ``exchange`` span and its three children: ``h2d`` (operands placed
    shard by shard, to ``block_until_ready``), ``kernel`` (the program's
    launch under ``mesh_dispatch_lock`` to ``block_until_ready`` of its
    outputs) and ``d2h`` (:func:`_fetch_shards`: every output's shards
    as flat host arrays, with the fetch's account as attrs). The bytes
    of both transfers go on their spans and into the account."""
    with _timed(acct, "exchange_s"):
        with _obs_trace.span("h2d") as sp:
            operands = jax.block_until_ready(put())
            _transferred(sp, acct, "h2d_bytes", operands)
        with _obs_trace.span("kernel"):
            with mesh_dispatch_lock:
                out = run(operands)
            out = jax.block_until_ready(out)
        with _obs_trace.span("d2h") as sp:
            host, account = _fetch_shards(out)
            _transferred(sp, acct, "d2h_bytes", host)
            for key, value in account.items():
                sp.set(key, value)
    return host


def _publish_stats(
    strategy: str,
    D: int,
    cap: int,
    counts: np.ndarray,
    acct: Dict,
    wire_bytes: int = 0,
    slot_bytes: int = 0,
    extra: Optional[Dict] = None,
) -> None:
    """Build the telemetry snapshot + once-per-build skew warning, and
    add this exchange's bytes to the running action's root counters
    (``exchange_*``: summed over a streaming build's waves).

    ``wire_bytes`` is what any implementation must move — the payload
    bytes of the rows whose owner is another chip than their source —
    and ``slot_bytes`` what this strategy sent, padding and extra planes
    included; both 0 for ``host``, which has no device leg.

    Publishes as ONE atomic rebind, never clear()+update(): a concurrent
    build copying the snapshot (covering_build telemetry) must see a
    whole dict, old or new — never the empty window between the two
    mutations (SHARED_STATE policy: rebind-only)."""
    from hyperspace_tpu.constants import (
        BUILD_SHUFFLE_SKEW_WARN_MIN_ROWS,
        BUILD_SHUFFLE_SKEW_WARN_RATIO,
    )

    max_count = int(counts.max()) if counts.size else 0
    mean_count = float(counts.mean()) if counts.size else 0.0
    skew = _skew_ratio(counts)
    stats: Dict = {
        "strategy": strategy,
        "devices": float(D),
        "cap": float(cap),
        "wire_bytes": float(wire_bytes),
        "slot_bytes": float(slot_bytes),
        "h2d_bytes": float(acct.get("h2d_bytes", 0)),
        "d2h_bytes": float(acct.get("d2h_bytes", 0)),
        "max_peer_count": float(max_count),
        "mean_peer_count": round(mean_count, 1),
        "skew_ratio": round(skew, 2),
    }
    stats.update({k: acct.get(k, 0.0) for k in STAGE_SECONDS_KEYS})
    stats.update(extra or {})
    for key in BYTES_KEYS:
        _obs_trace.accumulate("exchange_" + key, int(stats[key]))
    _obs_trace.accumulate("exchange_waves", 1)
    global last_shuffle_stats, _skew_warned
    last_shuffle_stats = stats
    if (
        skew > BUILD_SHUFFLE_SKEW_WARN_RATIO
        and max_count >= BUILD_SHUFFLE_SKEW_WARN_MIN_ROWS
        and not _skew_warned
    ):
        _skew_warned = True
        _log.warning(
            "bucket shuffle skew: hottest (shard, peer) slot carries "
            "%.1fx the mean row count (max=%d, mean=%.0f, D=%d, "
            "strategy=%s) — padded exchange slots inflate accordingly; "
            "consider more buckets or less skewed key columns "
            "(warned once per build; telemetry records every wave)",
            skew,
            max_count,
            mean_count,
            D,
            strategy,
        )


def _owner_ranked(num_buckets: int, D: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(ranked, rank_of)``: the bucket ids in ``(owner = bucket % D,
    bucket)`` order, and each bucket's rank in that order (both int32)."""
    b = np.arange(num_buckets, dtype=np.int64)
    ranked = np.lexsort((b, b % D)).astype(np.int32)
    rank_of = np.empty(num_buckets, dtype=np.int32)
    rank_of[ranked] = np.arange(num_buckets, dtype=np.int32)
    return ranked, rank_of


def canonical_order(
    bucket_ids: np.ndarray, num_buckets: int, D: int
) -> Tuple[np.ndarray, np.ndarray]:
    """THE post-exchange row order, host-side: a stable permutation
    sorting rows by ``(owner = bucket % D, bucket)`` (ties keep original
    row order), plus the ``[D+1]`` per-owner-shard row extents.

    What an ``all_to_all`` of per-owner slots delivers: shard ``s``
    holds the buckets it owns in ascending bucket order, and within a
    bucket the received rows concatenate source-shard-major with each
    source's rows in local (= original) order — i.e. ascending original
    row index. Computed as a counting scatter over owner-major-remapped
    bucket ids (native ``hs_partition_by_bucket`` above its dispatch
    threshold), O(n)."""
    owner_rank, remap = _owner_ranked(num_buckets, D)
    order, offsets = partition_by_bucket(remap[bucket_ids], num_buckets)
    per_bucket = np.diff(offsets)
    per_owner = np.bincount(
        owner_rank % D, weights=per_bucket, minlength=D
    ).astype(np.int64)
    shard_offsets = np.concatenate(
        [np.zeros(1, dtype=np.int64), np.cumsum(per_owner)]
    )
    return order, shard_offsets


def _shape_cap(exact: int) -> int:
    """Slot capacity rounded up to 3 significant bits (next multiple of
    ``2^(floor(log2 n) - 2)``): a streaming build's waves have slightly
    different max peer counts, and an EXACT cap would re-trace the
    exchange program once per wave. Three significant bits bound the
    padding at <25% (a power-of-two cap pads up to 2x) while keeping
    the number of distinct compile shapes per octave at 4.
    Correctness never depends on it — the unpack reads exact per-peer
    extents from the count matrix either way."""
    exact = max(int(exact), 1)
    if exact <= 8:
        return exact
    step = 1 << (exact.bit_length() - 3)
    return -(-exact // step) * step


def _pair_ranks(slot_ids: np.ndarray, num_slots: int) -> np.ndarray:
    """Rank of each row within its (source, destination) slot, in
    original row order — the within-slot position the host pack and the
    closed-form receive positions share."""
    order, offsets = partition_by_bucket(slot_ids, num_slots)
    within = np.arange(len(order), dtype=np.int64) - np.repeat(
        offsets[:-1], np.diff(offsets)
    )
    rank = np.empty(len(order), dtype=np.int64)
    rank[order] = within
    return rank


def _per_payload(fn: Callable, arrays: Sequence[np.ndarray], n_rows: int) -> List:
    """``[fn(a) for a in arrays]``, one pool thread a payload above 64k
    rows: the native gathers and numpy's large copies release the GIL,
    so the payloads of an exchange move side by side."""
    from concurrent.futures import ThreadPoolExecutor

    from hyperspace_tpu import native

    workers = min(len(arrays), max(1, min(native._cores(), 8)))
    if workers <= 1 or n_rows < (1 << 16):
        return [fn(a) for a in arrays]
    with ThreadPoolExecutor(
        max_workers=workers, thread_name_prefix="hs-exchange"
    ) as pool:
        return list(pool.map(fn, arrays))


def _threaded_gather(
    arrays: Sequence[np.ndarray], idx: np.ndarray
) -> List[np.ndarray]:
    """``[a[idx] for a in arrays]`` with per-column threading: 8-byte
    dtypes ride the threaded native gather (``hs_gather_*``, releases
    the GIL), the rest plain numpy. The "threaded numpy slicing" leg of
    the host-side exchange."""
    from hyperspace_tpu.io.columnar import _gather

    return _per_payload(lambda a: _gather(a, idx), arrays, len(idx))


def _process_local_operand(hmesh, local_block: np.ndarray):
    """This process's ``[1, L, B]`` send block -> the globally-sharded
    ``[H, L, B]`` device operand, built via
    ``make_array_from_process_local_data`` so the feed never round-trips
    through process 0 (docs/MULTIHOST.md; exercised by
    ``scripts/dryrun_multihost.py``)."""
    from jax.sharding import NamedSharding

    return jax.make_array_from_process_local_data(
        NamedSharding(hmesh, P(DCN_AXIS, ICI_AXIS)),
        np.ascontiguousarray(local_block),
    )


# ---------------------------------------------------------------------------
# Strategy: host-side exchange (no device round trip)
# ---------------------------------------------------------------------------


def _host_exchange(mesh, key_reps, payloads, num_buckets, seed):
    """Strategy ``host`` — the exchange as a pure host reorder.

    On a CPU mesh the "exchange" moves rows between buffers that live in
    the same RAM; emulating ICI (pad, scatter, collective, device
    argsorts, host↔device copies) is pure overhead. The canonical
    permutation is computed once from the host bucket ids and applied
    with threaded native/numpy gathers. Also the per-host leg of a
    multi-host decomposition (each host regrouping its local rows).

    No device leg: ``exchange`` is the gathers and has no ``h2d`` /
    ``kernel`` / ``d2h`` children, nothing crosses a wire
    (``wire_bytes`` = ``slot_bytes`` = 0) and there is no ``unpack``."""
    D = mesh.devices.size
    n = key_reps.shape[1]
    acct: Dict = {}
    with _timed(acct, "plan_s") as sp:
        bucket_ids = _host_bucket_ids(key_reps, num_buckets, seed)
        n_local = -(-n // D) if n else 1
        counts = _peer_counts(bucket_ids % D, n_local, D)
        cap = int(counts.max()) if counts.size else 0
        _plan_attrs(sp, STRATEGY_HOST, D, cap, counts)
    with _timed(acct, "pack_s") as sp:
        perm, shard_offsets = canonical_order(bucket_ids, num_buckets, D)
        sp.set("bytes", int(perm.nbytes))
    with _timed(acct, "exchange_s"):
        out_cols = _threaded_gather(payloads, perm)
        out_bucket = bucket_ids[perm]
    _publish_stats(STRATEGY_HOST, D, cap, counts, acct)
    return out_bucket, out_cols, shard_offsets


# ---------------------------------------------------------------------------
# Strategy: compact variable-length exchange
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mesh",))
def _compact_program(mesh, payloads):
    """ONE tiled all_to_all per payload — no on-device hashing, scatter
    or argsort; the host packed exact (source, peer) extents and unpacks
    by contiguous run copies."""

    def local(cols):
        return tuple(
            lax.all_to_all(c, SHARD_AXIS, 0, 0, tiled=True) for c in cols
        )

    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(SHARD_AXIS),), out_specs=P(SHARD_AXIS)
    )(payloads)


class _CompactPlan(NamedTuple):
    """What ONE counting partition of the rows by ``(source shard,
    owner, bucket)`` says — everything the compact exchange's plan, pack
    and unpack need. ``R = num_buckets`` below; a bucket's *rank* is its
    place in ``(owner = bucket % D, bucket)`` order, so owner ``o``'s
    buckets are the ranks ``owner_lo[o]:owner_lo[o + 1]``."""

    order: np.ndarray  # [n] int64: rows by (source, rank), stable
    starts: np.ndarray  # [D, R + 1] int64: starts[s, r] = where source
    # s's rows of rank r begin in ``order``; starts[s, R] = its end
    ranked: np.ndarray  # [R] int32: bucket ids in rank order
    owner_lo: np.ndarray  # [D + 1] int64: first rank of each owner
    counts: np.ndarray  # [D, D] int64: rows source s sends owner o
    cap: int  # slot capacity (``_shape_cap`` of the max count)


def _compact_plan(
    key_reps: np.ndarray, num_buckets: int, seed: int, D: int
) -> _CompactPlan:
    """Hash once, partition once. The key of a row is ``source *
    num_buckets + rank(bucket)``: source-major, then owner-major, then
    bucket — so the stable partition's ``order`` lists slot ``(source,
    owner)``'s rows contiguously, grouped by ascending bucket, in
    original row order inside a bucket, and its ``offsets`` are the run
    table: the peer counts, the cap, the skew and the shard extents
    follow from ``D * num_buckets`` run lengths with no further pass
    over the rows."""
    n = key_reps.shape[1]
    R = int(num_buckets)
    bucket_ids = bucket_ids_host(key_reps, R, seed)
    ranked, rank_of = _owner_ranked(R, D)
    key = rank_of[bucket_ids]
    n_local = -(-n // D) if n else 1
    for s in range(1, D):  # the n_local-row source blocks, in place
        key[s * n_local : (s + 1) * n_local] += s * R
    order, offsets = partition_by_bucket(key, D * R)
    starts = np.empty((D, R + 1), dtype=np.int64)
    starts[:, :R] = offsets[:-1].reshape(D, R)
    starts[:, R] = offsets[R::R]
    owner_lo = np.searchsorted(ranked % D, np.arange(D + 1)).astype(np.int64)
    counts = np.diff(starts[:, owner_lo], axis=1)
    return _CompactPlan(
        order, starts, ranked, owner_lo, counts, _shape_cap(counts.max())
    )


def _compact_pack(
    plan: _CompactPlan, payloads: Sequence[np.ndarray]
) -> Tuple[List[np.ndarray], int]:
    """The ``[D*D, cap]`` send buffer of every payload — slot ``s*D +
    o`` holds the rows source ``s`` sends owner ``o``, zeros behind them
    — and how many payloads took the threaded native gather (8-byte
    dtypes; the rest fall to numpy ``take``). A payload is gathered once
    by ``plan.order`` and lands by ``D*D`` contiguous copies: no ranks,
    no position array, no scatter."""
    from hyperspace_tpu.io.columnar import _gather_native

    D = plan.counts.shape[0]
    slot_lo = plan.starts[:, plan.owner_lo[:-1]]

    def pack(p: np.ndarray) -> Tuple[np.ndarray, bool]:
        rows = _gather_native(p, plan.order)
        native_gather = rows is not None
        if rows is None:
            rows = np.take(p, plan.order)
        buf = np.zeros((D * D, plan.cap), dtype=p.dtype)
        for s in range(D):
            for o in range(D):
                cnt = plan.counts[s, o]
                lo = slot_lo[s, o]
                buf[s * D + o, :cnt] = rows[lo : lo + cnt]
        return buf, native_gather

    packed = _per_payload(pack, payloads, len(plan.order))
    return [buf for buf, _ in packed], sum(nat for _, nat in packed)


def _compact_unpack(
    plan: _CompactPlan, received: Sequence[Sequence[np.ndarray]]
) -> Tuple[np.ndarray, List[np.ndarray], np.ndarray, int]:
    """Received slots -> canonical order by contiguous run copies:
    ``(bucket ids, payload columns, [D+1] shard extents, runs)``.
    ``received`` holds, per payload, owner ``o``'s flat ``[D*cap]``
    shard at index ``o`` (:func:`_fetch_shards`).

    The rows of the bucket of rank ``r`` (owner ``o``) that source ``s``
    sent sit contiguously in shard ``o`` at ``s*cap + (rows of s's
    lower-ranked buckets owned by o)`` — every run lies inside one
    shard; canonical order is, for each rank ascending (owner-major,
    bucket ascending), for each source ascending, that run — within a
    bucket source-major with original order inside a source, i.e.
    ascending original row index. No permutation, no index array, no
    gather."""
    D = plan.counts.shape[0]
    R = len(plan.ranked)
    owner_of_rank = plan.ranked.astype(np.int64) % D
    src = np.arange(D, dtype=np.int64)[:, None]
    within_slot = plan.starts[:, :R] - plan.starts[src, plan.owner_lo[owner_of_rank]]
    lo = (src * plan.cap + within_slot).T.ravel()
    run_len = np.diff(plan.starts, axis=1)
    hi = lo + run_len.T.ravel()
    owner = np.repeat(owner_of_rank, D)
    runs = [
        (o, a, b)
        for o, a, b in zip(owner.tolist(), lo.tolist(), hi.tolist())
        if b > a
    ]

    def unpack(shards: Sequence[np.ndarray]) -> np.ndarray:
        if not runs:
            return np.zeros(0, dtype=shards[0].dtype)
        return np.concatenate([shards[o][a:b] for o, a, b in runs])

    out_cols = _per_payload(unpack, received, len(plan.order))
    out_bucket = np.repeat(plan.ranked, run_len.sum(axis=0))
    shard_offsets = np.concatenate(
        [np.zeros(1, dtype=np.int64), np.cumsum(plan.counts.sum(axis=0))]
    )
    return out_bucket, out_cols, shard_offsets, len(runs)


def _compact_exchange(mesh, key_reps, payloads, num_buckets, seed):
    """Strategy ``compact`` — host-packed exact-extent device exchange.

    ONE counting partition of the rows by ``(source shard, owner,
    bucket)`` (:func:`_compact_plan`) drives the whole host side:
    ``exchange_plan`` is the native one-pass hash, the key remap and
    that partition, whose run lengths give the peer counts and the cap
    (the exact max count at three significant bits — not power-of-two
    padded); ``pack`` is one gather a payload by the partition's order
    plus ``D*D`` contiguous copies into ``[D*D, cap]`` send buffers
    (attrs ``gathers_native`` / ``gathers_numpy``: payloads that took
    the threaded native 8-byte gather against those that fell to numpy
    ``take``); each payload rides one ``all_to_all``; ``unpack`` copies
    at most ``D * num_buckets`` contiguous runs a payload (attr
    ``runs``) from the received slots straight into canonical order.
    Rows travel grouped by bucket inside a slot; the ``all_to_all`` does
    not look inside one: no device hash or argsort, no bucket or
    validity plane on the wire; the exchanged bytes are exactly
    ``D*D*cap`` slots per payload."""
    D = mesh.devices.size
    acct: Dict = {}
    with _timed(acct, "plan_s") as sp:
        plan = _compact_plan(key_reps, num_buckets, seed, D)
        _plan_attrs(sp, STRATEGY_COMPACT, D, plan.cap, plan.counts)
    with _timed(acct, "pack_s") as sp:
        sends, gathers_native = _compact_pack(plan, payloads)
        sp.set("bytes", _nbytes(sends))
        sp.set("gathers_native", gathers_native)
        sp.set("gathers_numpy", len(sends) - gathers_native)
    received = _device_leg(
        acct,
        lambda: tuple(put_sharded(mesh, s) for s in sends),
        lambda ops: _compact_program(mesh, ops),
    )
    with _timed(acct, "unpack_s") as sp:
        out_bucket, out_cols, shard_offsets, runs = _compact_unpack(plan, received)
        sp.set("bytes", _nbytes(out_cols))
        sp.set("runs", runs)
    row_bytes = sum(p.dtype.itemsize for p in payloads)
    _publish_stats(
        STRATEGY_COMPACT,
        D,
        plan.cap,
        plan.counts,
        acct,
        wire_bytes=_off_chip_rows(plan.counts) * row_bytes,
        slot_bytes=_nbytes(sends),
    )
    return out_bucket, out_cols, shard_offsets


# ---------------------------------------------------------------------------
# Strategy: two-stage DCN/ICI decomposition
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("hmesh", "caps"))
def _twostage_program(hmesh, payloads, caps):
    """The cross-host leg: one ``ppermute`` per peer host over the
    ``dcn`` axis, each round's slot sized to ITS (source host, peer
    host) max — skew-aware per-peer caps, not one global max. The
    intra-host leg already ran host-side (rows were packed into their
    destination ici lane's buffer), so no ``ici`` collective is needed
    — lane l's buffer lands on device (dest_host, l) directly."""
    H = hmesh.shape[DCN_AXIS]
    offs = [0]  # static slice offsets from the static per-round caps
    for c in caps:
        offs.append(offs[-1] + c)

    def local(cols):
        def route(x):
            b = x.reshape(-1)
            parts = [b[offs[0] : offs[1]]]  # round 0: rows staying on-host
            for r in range(1, H):
                seg = b[offs[r] : offs[r + 1]]
                parts.append(
                    lax.ppermute(
                        seg,
                        DCN_AXIS,
                        [(h, (h + r) % H) for h in range(H)],
                    )
                )
            return jnp.concatenate(parts).reshape(x.shape)

        return tuple(route(c) for c in cols)

    return jax.shard_map(
        local,
        mesh=hmesh,
        in_specs=(P(DCN_AXIS, ICI_AXIS),),
        out_specs=P(DCN_AXIS, ICI_AXIS),
    )(payloads)


def hierarchical_view(mesh, hosts: int):
    """(H, L) (dcn, ici) mesh over the SAME devices as the flat build
    mesh — process-major device order makes row h the h-th host's
    devices on a real multi-host job; on a single-controller simulation
    ``hosts`` carves the flat mesh into simulated hosts."""
    D = mesh.devices.size
    if D % hosts:
        raise ValueError(
            f"twostage exchange: {hosts} hosts do not divide the "
            f"{D}-device mesh"
        )
    return jax.sharding.Mesh(
        mesh.devices.reshape(hosts, D // hosts), (DCN_AXIS, ICI_AXIS)
    )


def _twostage_exchange_mp(mesh, key_reps, payloads, num_buckets, seed):
    """The REAL multi-host leg of the twostage strategy: every process
    passes only ITS rows (the per-host scan feed — global row order is
    process-major) and receives back only the rows of the buckets its
    local devices own, in canonical order, plus ``[D+1]`` shard extents
    in which non-local shards are empty.

    Same slot layout as the single-controller simulation, built
    per-process: the host-side ici leg packs local rows into their
    destination lane's buffer, caps come from a ``process_allgather`` of
    the per-(host, lane) count matrix (every process must compile the
    same SPMD shapes), the send block feeds the global array via
    ``make_array_from_process_local_data`` (no round-trip through
    process 0), bucket ids ride as one extra int32 payload (the receiver
    cannot re-derive them without re-hashing), and the local unpack
    stable-sorts each lane's received rows by (bucket, source host,
    slot rank) — exactly the canonical (bucket, global row) order.
    Exercised cross-process by ``scripts/dryrun_multihost.py``."""
    from jax.experimental import multihost_utils as mhu

    H = jax.process_count()
    pid = jax.process_index()
    D = mesh.devices.size
    L = D // H
    n = key_reps.shape[1]
    acct: Dict = {}
    with _timed(acct, "plan_s") as sp:
        bucket_ids = _host_bucket_ids(key_reps, num_buckets, seed)
        owner = bucket_ids % D
        dst_h = owner // L
        lane = owner % L
        rnd = (dst_h - pid) % H
        hl_local = np.bincount(
            dst_h * L + lane, minlength=H * L
        ).reshape(H, L)
        hl_all = np.asarray(mhu.process_allgather(hl_local))  # [H, H, L]
        caps = tuple(
            _shape_cap(hl_all[np.arange(H), (np.arange(H) + r) % H, :].max())
            for r in range(H)
        )
        offs = np.concatenate([[0], np.cumsum(caps)]).astype(np.int64)
        B = int(offs[-1])
        _plan_attrs(sp, STRATEGY_TWOSTAGE, D, int(max(caps)), hl_all[pid])
    with _timed(acct, "pack_s") as sp:
        rank = _pair_ranks(owner.astype(np.int32), D)
        send_pos = lane * B + offs[rnd] + rank
        sends = []
        for p in [bucket_ids] + list(payloads):
            buf = np.zeros(L * B, dtype=p.dtype)
            buf[send_pos] = p
            sends.append(buf.reshape(1, L, B))
        sp.set("bytes", _nbytes(sends))
    hmesh = hierarchical_view(mesh, H)
    # per payload, this process's L lanes: each a flat [B] shard
    local = _device_leg(
        acct,
        lambda: tuple(_process_local_operand(hmesh, s) for s in sends),
        lambda ops: _twostage_program(hmesh, ops, caps),
    )
    with _timed(acct, "unpack_s") as sp:
        recv_ids, recv_cols = local[0], local[1:]
        # valid extents per (lane, round) from the global count matrix;
        # round r of lane l carries hl_all[(pid - r) % H, pid, l] rows —
        # reorder rounds by SOURCE HOST so concatenation follows global
        # row order
        out_bucket_parts: List[np.ndarray] = []
        out_col_parts: List[List[np.ndarray]] = [[] for _ in recv_cols]
        per_shard = np.zeros(D, dtype=np.int64)
        for l in range(L):
            ids_parts, col_parts = [], [[] for _ in recv_cols]
            for src_h in range(H):
                r = (pid - src_h) % H
                cnt = int(hl_all[src_h, pid, l])
                lo = int(offs[r])
                ids_parts.append(recv_ids[l][lo : lo + cnt])
                for i, c in enumerate(recv_cols):
                    col_parts[i].append(c[l][lo : lo + cnt])
            ids_l = np.concatenate(ids_parts)
            order = np.argsort(ids_l, kind="stable")
            out_bucket_parts.append(ids_l[order])
            for i in range(len(recv_cols)):
                out_col_parts[i].append(np.concatenate(col_parts[i])[order])
            per_shard[pid * L + l] = len(ids_l)
        out_bucket = (
            np.concatenate(out_bucket_parts)
            if out_bucket_parts
            else np.zeros(0, dtype=np.int32)
        )
        out_cols = [
            np.concatenate(parts)
            if parts
            else np.zeros(0, dtype=c[0].dtype)
            for parts, c in zip(out_col_parts, recv_cols)
        ]
        shard_offsets = np.concatenate(
            [np.zeros(1, dtype=np.int64), np.cumsum(per_shard)]
        )
        expect = int(hl_all[:, pid, :].sum())
        if len(out_bucket) != expect:
            raise RuntimeError(
                f"multi-host bucket shuffle lost rows on process {pid}: "
                f"expected {expect}, received {len(out_bucket)}"
            )
        sp.set("bytes", _nbytes(out_cols))
    # bucket ids ride as one more int32 payload; rows bound for this
    # process's own lanes never leave the host
    row_bytes = 4 + sum(p.dtype.itemsize for p in payloads)
    _publish_stats(
        STRATEGY_TWOSTAGE,
        D,
        int(max(caps)),
        hl_all[pid],  # this process's per-(peer host, lane) send counts
        acct,
        wire_bytes=int(n - hl_local[pid].sum()) * row_bytes,
        slot_bytes=_nbytes(sends),
        extra={
            "hosts": float(H),
            "process_local": 1.0,
            "round_cap_max": float(max(caps)),
            "round_cap_min": float(min(caps)),
        },
    )
    return out_bucket, out_cols, shard_offsets


def _twostage_exchange(mesh, key_reps, payloads, num_buckets, seed, hosts):
    """Strategy ``twostage`` — docs/MULTIHOST.md's DCN/ICI decomposition.

    Intra-host leg on the host: each host's rows are packed (in RAM) into
    per-(peer-host, destination-lane) slots, aggregating its L devices'
    sends into one buffer per peer host. Cross-host leg on the device:
    H-1 ``ppermute`` rounds over ``dcn``, round r's slot sized to
    ``max(count[src_host → (src_host+r) % H host, lane])`` — the
    per-(shard, peer) count matrix (the skew telemetry) IS the slot
    sizing, so a hot destination host inflates only the rounds that
    target it. Row volume over DCN is that of one all_to_all; message
    count per host drops to one buffer per peer host and no row pays a second
    device hash or argsort.

    On a REAL multi-process job the per-process variant runs instead
    (:func:`_twostage_exchange_mp`): per-host inputs, per-host outputs,
    ``make_array_from_process_local_data`` feed. The single-controller
    body below simulates the same decomposition by carving the flat mesh
    into ``hosts`` groups of contiguous devices."""
    if jax.process_count() > 1:
        return _twostage_exchange_mp(mesh, key_reps, payloads, num_buckets, seed)
    D = mesh.devices.size
    H = int(hosts) if hosts and hosts > 0 else max(jax.process_count(), 1)
    H = min(H, D)
    while D % H:
        H -= 1
    L = D // H
    n = key_reps.shape[1]
    acct: Dict = {}
    with _timed(acct, "plan_s") as sp:
        bucket_ids = _host_bucket_ids(key_reps, num_buckets, seed)
        owner = bucket_ids % D
        n_local = -(-n // D) if n else 1
        counts = _peer_counts(owner, n_local, D)
        src_dev = (np.arange(n, dtype=np.int64) // n_local).astype(np.int64)
        src_h = src_dev // L
        dst_h = owner // L
        lane = owner % L
        rnd = (dst_h - src_h) % H
        # per-round slot caps from the count matrix, uniform over (host,
        # lane) senders of that round (SPMD shapes must agree) but NOT
        # over rounds — the skew-aware sizing
        hl_counts = np.bincount(
            (src_h * H + dst_h) * L + lane, minlength=H * H * L
        ).reshape(H, H, L)
        caps = tuple(
            _shape_cap(
                hl_counts[np.arange(H), (np.arange(H) + r) % H, :].max()
            )
            for r in range(H)
        )
        offs = np.concatenate([[0], np.cumsum(caps)]).astype(np.int64)
        B = int(offs[-1])
        _plan_attrs(sp, STRATEGY_TWOSTAGE, D, int(max(caps)), counts)
    with _timed(acct, "pack_s") as sp:
        slot = ((src_h * H + dst_h) * L + lane).astype(np.int32)
        rank = _pair_ranks(slot, H * H * L)
        # sender of a row is device (src_h, lane): the host already moved
        # it to its destination lane's buffer (the RAM ici leg)
        send_pos = (src_h * L + lane) * B + offs[rnd] + rank
        recv_pos = offs[rnd] + rank  # inside the owner's [B] shard
        sends = []
        for p in payloads:
            buf = np.zeros(D * B, dtype=p.dtype)
            buf[send_pos] = p
            sends.append(buf.reshape(H, L, B))
        sp.set("bytes", _nbytes(sends))
    hmesh = hierarchical_view(mesh, H)
    received = _device_leg(
        acct,
        lambda: tuple(
            put_sharded(hmesh, s, P(DCN_AXIS, ICI_AXIS)) for s in sends
        ),
        lambda ops: _twostage_program(hmesh, ops, caps),
    )
    with _timed(acct, "unpack_s") as sp:
        # canonical order is owner-major and device (dst_h, lane) IS the
        # owner: owner o's rows are one gather inside shard o
        out_perm, shard_offsets = canonical_order(bucket_ids, num_buckets, D)
        gather_idx = recv_pos[out_perm]
        extents = list(zip(shard_offsets[:-1].tolist(), shard_offsets[1:].tolist()))

        def unpack(shards: Sequence[np.ndarray]) -> np.ndarray:
            col = np.empty(n, dtype=shards[0].dtype)
            for shard, (lo, hi) in zip(shards, extents):
                np.take(shard, gather_idx[lo:hi], out=col[lo:hi])
            return col

        out_cols = _per_payload(unpack, received, n)
        out_bucket = bucket_ids[out_perm]
        sp.set("bytes", _nbytes(out_cols))
    row_bytes = sum(p.dtype.itemsize for p in payloads)
    _publish_stats(
        STRATEGY_TWOSTAGE,
        D,
        int(max(caps)),
        counts,
        acct,
        # rows that left their source chip: between the chips of one
        # simulated host they ride its RAM, the rest the dcn rounds
        wire_bytes=_off_chip_rows(counts) * row_bytes,
        slot_bytes=_nbytes(sends),
        extra={
            "hosts": float(H),
            "round_cap_max": float(max(caps)),
            "round_cap_min": float(min(caps)),
        },
    )
    return out_bucket, out_cols, shard_offsets


# ---------------------------------------------------------------------------
# Resolution + host entry
# ---------------------------------------------------------------------------


def resolve_strategy(strategy: str, mesh) -> str:
    """Map the configured strategy (``hyperspace.build.exchange.
    strategy``) to a concrete one. ``auto`` is a function of the
    platform alone:

    * multi-process job → ``twostage`` (the DCN leg is the bottleneck;
      docs/MULTIHOST.md);
    * CPU mesh → ``host`` (the simulation must never pay ICI-emulation
      costs);
    * single-host accelerator → ``compact``.
    """
    s = (strategy or STRATEGY_AUTO).strip().lower()
    if s != STRATEGY_AUTO and s not in STRATEGIES:
        raise ValueError(
            f"unknown exchange strategy {strategy!r}; expected one of "
            f"{(STRATEGY_AUTO,) + STRATEGIES}"
        )
    if jax.process_count() > 1:
        # a multi-process job has per-host inputs; only the twostage
        # decomposition moves rows across the process boundary
        if s not in (STRATEGY_AUTO, STRATEGY_TWOSTAGE):
            _log.debug(
                "exchange strategy %r coerced to twostage on a "
                "multi-process job",
                s,
            )
        return STRATEGY_TWOSTAGE
    if s != STRATEGY_AUTO:
        return s
    if mesh.devices.flat[0].platform == "cpu":
        return STRATEGY_HOST
    return STRATEGY_COMPACT


def bucket_shuffle(
    mesh,
    key_reps: np.ndarray,
    payloads: Sequence[np.ndarray],
    num_buckets: int,
    seed: int = 42,
    with_shard_offsets: bool = False,
    strategy: str = STRATEGY_AUTO,
    twostage_hosts: int = 0,
):
    """Host entry: shuffle rows into bucket-contiguous order across the
    mesh, via the selected exchange strategy (see module docstring).

    Returns ``(bucket_ids, payload_cols)`` with all rows grouped by
    bucket (global order: all rows of buckets owned by shard 0, then
    shard 1, …; within a shard, ascending bucket id; within a bucket,
    original row order). Every strategy produces bit-identical output.
    The caller does the final within-bucket key sort (``ops/sort.py``)
    before writing.

    ``with_shard_offsets=True`` additionally returns the ``[D+1]`` row
    offsets of each shard's slice — rows ``offsets[s]:offsets[s+1]`` are
    exactly the buckets shard ``s`` owns (``bucket % D == s``), the
    handle the sharded build/serve tail needs to keep bucket ownership
    device-local past the exchange. A peer that owns no rows gets an
    empty extent.
    """
    # Floats cross the exchange as integers of their own width. The
    # exchange moves bytes and must never interpret them: the TPU has no
    # IEEE double (a float64 it holds keeps float32's exponent range and
    # fewer mantissa bits — chip run, PR 21), while 64-bit integers are
    # carried exactly.
    dtypes = [p.dtype for p in payloads]
    payloads = [
        p.view(f"i{p.dtype.itemsize}") if p.dtype.kind == "f" else p
        for p in payloads
    ]
    name = resolve_strategy(strategy, mesh)
    if name == STRATEGY_HOST:
        bucket, cols, offsets = _host_exchange(
            mesh, key_reps, payloads, num_buckets, seed
        )
    elif name == STRATEGY_COMPACT:
        bucket, cols, offsets = _compact_exchange(
            mesh, key_reps, payloads, num_buckets, seed
        )
    else:
        bucket, cols, offsets = _twostage_exchange(
            mesh, key_reps, payloads, num_buckets, seed, twostage_hosts
        )
    cols = [c.view(dt) for c, dt in zip(cols, dtypes)]
    if with_shard_offsets:
        return bucket, cols, offsets
    return bucket, cols
