"""Device grouped reductions — the engine's aggregate kernel.

The reference delegates group-by to Spark's hash aggregate; here the
engine is the serve path, so grouped reductions run as XLA segment ops
(``jax.ops.segment_sum``/``min``/``max``): group ids are computed on host
(O(rows) factorize over int64 key reps), the O(rows·aggs) reduction work
runs compiled on device. Null semantics match SQL/Spark: sum/min/max/avg
ignore nulls (an all-null group yields null), count(col) counts non-null
rows, count(*) counts rows.

Integers only on the device. The TPU has no IEEE double: a float64 it
holds keeps float32's exponent range and fewer mantissa bits (chip run,
PR 21: 1e300 arrives as inf, segment_min returns values that are no
input, sums are off by 2e-13), so float reductions always run the numpy
twins, whose answers are exact.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import hyperspace_tpu.ops  # noqa: F401  (enables x64)


@functools.partial(jax.jit, static_argnames=("num_segments",))
def _seg_sum_count(gid, vals, valid, num_segments):
    """(per-group sum over valid rows, per-group count of valid rows)."""
    v = jnp.where(valid, vals, jnp.zeros((), dtype=vals.dtype))
    sums = jax.ops.segment_sum(v, gid, num_segments=num_segments)
    counts = jax.ops.segment_sum(
        valid.astype(jnp.int64), gid, num_segments=num_segments
    )
    return sums, counts


@functools.partial(jax.jit, static_argnames=("num_segments",))
def _seg_min(gid, vals, valid, num_segments):
    v = jnp.where(valid, vals, jnp.iinfo(vals.dtype).max)
    return jax.ops.segment_min(v, gid, num_segments=num_segments)


@functools.partial(jax.jit, static_argnames=("num_segments",))
def _seg_max(gid, vals, valid, num_segments):
    v = jnp.where(valid, vals, jnp.iinfo(vals.dtype).min)
    return jax.ops.segment_max(v, gid, num_segments=num_segments)


def _as_device(vals: np.ndarray) -> jnp.ndarray:
    if vals.dtype.kind == "b":
        return jnp.asarray(vals.astype(np.int64))
    if vals.dtype.kind == "u":
        # keep unsigned (x64 enabled): min/max order and modular sums stay
        # correct; the executor casts back to the output type
        return jnp.asarray(vals.astype(np.uint64))
    return jnp.asarray(vals)


# Below this row count the reductions run as plain numpy: the device
# segment ops pay a host->device->host round trip AND recompile per
# (row count, group count) pair, while the numpy twins (same null/NaN
# semantics, exact int64 sums via ufunc.at) finish in milliseconds on
# host-resident serve batches.
_HOST_AGG_MAX_ROWS = 1 << 20


def _on_host(vals: np.ndarray) -> bool:
    """Floats of any length (see module docstring), everything else up
    to ``_HOST_AGG_MAX_ROWS``."""
    return vals.dtype.kind == "f" or len(vals) <= _HOST_AGG_MAX_ROWS


def _host_sum_count(gid, vals, valid, num_segments):
    # accumulate in the same widened dtype the device path uses
    # (_as_device): unsigned -> uint64, bool/ints -> int64, floats as-is —
    # narrow-dtype accumulation would wrap (uint8 sums mod 256)
    if vals.dtype.kind == "u":
        acc = np.uint64
    elif vals.dtype.kind in "bi":
        acc = np.int64
    else:
        acc = vals.dtype
    v = np.where(valid, vals, np.zeros((), dtype=vals.dtype)).astype(
        acc, copy=False
    )
    sums = np.zeros(num_segments, dtype=acc)
    np.add.at(sums, gid, v)
    counts = np.bincount(gid[valid], minlength=num_segments)
    return sums, counts.astype(np.int64)


def _host_minmax(gid, vals, valid, num_segments, mode):
    if np.issubdtype(vals.dtype, np.floating):
        isn = np.isnan(vals)
        clean_mask = valid & ~isn
        fill = np.inf if mode == "min" else -np.inf
        clean = np.where(clean_mask, vals, fill)
        out = np.full(num_segments, fill, dtype=vals.dtype)
        (np.minimum if mode == "min" else np.maximum).at(out, gid, clean)
        has_clean = np.bincount(gid[clean_mask], minlength=num_segments) > 0
        if mode == "min":
            # NaN wins only when the group has no non-NaN valid values
            return np.where(has_clean, out, np.asarray(np.nan, vals.dtype))
        has_nan = np.bincount(gid[valid & isn], minlength=num_segments) > 0
        return np.where(has_nan, np.asarray(np.nan, vals.dtype), out)
    fill = (
        np.iinfo(vals.dtype).max if mode == "min" else np.iinfo(vals.dtype).min
    ) if vals.dtype.kind in "iu" else (True if mode == "min" else False)
    v = np.where(valid, vals, np.asarray(fill, dtype=vals.dtype))
    out = np.full(num_segments, fill, dtype=vals.dtype)
    (np.minimum if mode == "min" else np.maximum).at(out, gid, v)
    return out


def segment_sum_count(
    gid: np.ndarray,
    vals: np.ndarray,
    valid: Optional[np.ndarray],
    num_segments: int,
) -> Tuple[np.ndarray, np.ndarray]:
    valid = (
        np.ones(len(vals), dtype=bool) if valid is None else valid
    )
    if _on_host(vals):
        return _host_sum_count(gid, vals, valid, num_segments)
    s, c = _seg_sum_count(
        jnp.asarray(gid), _as_device(vals), jnp.asarray(valid), num_segments
    )
    return np.asarray(s), np.asarray(c)


def segment_minmax(
    gid: np.ndarray,
    vals: np.ndarray,
    valid: Optional[np.ndarray],
    num_segments: int,
    mode: str,
) -> np.ndarray:
    valid = np.ones(len(vals), dtype=bool) if valid is None else valid
    if _on_host(vals):
        return _host_minmax(gid, vals, valid, num_segments, mode)
    fn = _seg_min if mode == "min" else _seg_max
    out = fn(jnp.asarray(gid), _as_device(vals), jnp.asarray(valid), num_segments)
    return np.asarray(out)


def segment_count(
    gid: np.ndarray, valid: Optional[np.ndarray], n: int, num_segments: int
) -> np.ndarray:
    valid = np.ones(n, dtype=bool) if valid is None else valid
    if n <= _HOST_AGG_MAX_ROWS:
        return np.bincount(
            gid[valid], minlength=num_segments
        ).astype(np.int64)
    counts = jax.ops.segment_sum(
        jnp.asarray(valid).astype(jnp.int64),
        jnp.asarray(gid),
        num_segments=num_segments,
    )
    return np.asarray(counts)
