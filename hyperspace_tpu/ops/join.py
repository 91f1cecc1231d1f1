"""Device merge-join kernel for co-bucketed index scans.

The payoff of the JoinIndexRule (reference
``covering/JoinIndexRule.scala:619-634``): both sides are bucketed by the
join keys, so the join runs per bucket pair with NO shuffle. Here the
per-bucket matching — combine-rep, argsort, binary-search match ranges —
is one compiled XLA program ``vmap``-ed over buckets and, on a >1-device
mesh, ``shard_map``-ed so each shard joins its own slice of buckets in
parallel (replacing the reference's executor-parallel SMJ tasks).

Static-shape contract: buckets are padded to the max bucket length per
side; pad slots carry +INT64_MAX reps and are excluded via the per-bucket
valid lengths. The kernel returns, per left row, the [lo, hi) range of
matching rows in the right side's sorted order; the host expands ranges
into index pairs (O(matches), vectorized) and re-verifies the actual key
columns, so a 64-bit combine collision can only cost work, never
correctness.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import hyperspace_tpu.ops  # noqa: F401  (enables x64)
from hyperspace_tpu.parallel.mesh import (
    SHARD_AXIS,
    mesh_dispatch_lock,
    put_sharded,
)

# numpy, not jnp: a module-level jnp scalar initializes the JAX backend —
# and so takes the chip — when this module is merely imported
_PAD = np.int64(0x7FFFFFFFFFFFFFFF)


def combine_reps_np(reps: np.ndarray) -> np.ndarray:
    """[k, n] int64 -> [n] int64: splitmix64 mix of the composite key
    (identity copy for k == 1, where reps are already exact). Host numpy:
    the combine is O(k·n) bit arithmetic — cheaper than a device roundtrip
    on the serve path, and the kernel itself is key-agnostic."""
    if reps.shape[0] == 1:
        return reps[0].copy()
    with np.errstate(over="ignore"):
        h = np.zeros(reps.shape[1], dtype=np.uint64)
        m1 = np.uint64(0xBF58476D1CE4E5B9)
        m2 = np.uint64(0x94D049BB133111EB)
        gold = np.uint64(0x9E3779B97F4A7C15)
        for i in range(reps.shape[0]):
            x = h ^ (reps[i].view(np.uint64) + gold)
            x = x * m1
            x ^= x >> np.uint64(27)
            x = x * m2
            x ^= x >> np.uint64(31)
            h = x
    return h.view(np.int64)


# At or above this PAIR count the range expansion uses the native
# single-pass kernel (hyperspace_tpu/native); below it numpy's vectorized
# repeat/cumsum passes are already microseconds. FALLBACK DEFAULT: the
# effective threshold comes from the per-machine calibration probe
# (native/calibrate.py); this constant applies when calibration is
# disabled or a test overrides the module attribute (an override wins).
_NATIVE_EXPAND_MIN_ROWS_DEFAULT = 1 << 14
_NATIVE_EXPAND_MIN_ROWS = _NATIVE_EXPAND_MIN_ROWS_DEFAULT


def _native_expand_min_rows() -> int:
    if _NATIVE_EXPAND_MIN_ROWS != _NATIVE_EXPAND_MIN_ROWS_DEFAULT:
        return _NATIVE_EXPAND_MIN_ROWS  # explicit (test/ops) override wins
    from hyperspace_tpu.native import calibrate

    return (
        calibrate.thresholds().native_expand_min_rows
        or _NATIVE_EXPAND_MIN_ROWS
    )


def expand_match_ranges_numpy(
    lo: np.ndarray,
    cnt: np.ndarray,
    l_map: np.ndarray = None,
    r_map: np.ndarray = None,
    l_bias: int = 0,
    r_bias: int = 0,
):
    """Expand per-left-row match ranges into (li, ri) pairs, pure numpy —
    the registered twin of ``hs_expand_match_ranges_i64`` and the
    repeat/cumsum chain the serve path always ran. Left row ``i`` with
    ``cnt[i]`` matches starting at sorted-right position ``lo[i]`` emits
    pairs ``(l_map[i] + l_bias, r_map[lo[i]+j] + r_bias)`` for j in
    [0, cnt[i]); a None map is the identity. Pair order: left row
    ascending, right position ascending within each left row."""
    n = len(lo)
    cnt = cnt.astype(np.int64, copy=False)
    total = int(cnt.sum())
    if total == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z
    li = np.repeat(np.arange(n, dtype=np.int64), cnt)
    starts = np.concatenate([[0], np.cumsum(cnt)[:-1]])
    within = np.arange(total, dtype=np.int64) - np.repeat(starts, cnt)
    ri = np.repeat(lo.astype(np.int64, copy=False), cnt) + within
    if l_map is not None:
        li = l_map[li]
    if r_map is not None:
        ri = r_map[ri]
    if l_bias:
        li = li + np.int64(l_bias)
    if r_bias:
        ri = ri + np.int64(r_bias)
    return li, ri


def expand_match_ranges(
    lo: np.ndarray,
    cnt: np.ndarray,
    l_map: np.ndarray = None,
    r_map: np.ndarray = None,
    l_bias: int = 0,
    r_bias: int = 0,
):
    """Host dispatch of the match-range expansion: the native single-pass
    kernel at or above the calibrated pair-count crossover, else the
    numpy twin — identical output either way."""
    total = int(cnt.sum())
    if total >= _native_expand_min_rows():
        from hyperspace_tpu import native

        pair = native.expand_match_ranges_i64(
            lo, cnt, total, l_map, r_map, l_bias, r_bias
        )
        if pair is not None:
            return pair
    return expand_match_ranges_numpy(lo, cnt, l_map, r_map, l_bias, r_bias)


def _bucket_join(l_rep, l_len, r_rep, r_len):
    """One padded bucket pair -> (perm_l, perm_r, lo, cnt) in sorted space.

    Pad handling relies on a stability invariant, NOT on the pad value
    being unrepresentable (a real int64 key CAN equal ``_PAD``): real rows
    occupy indices < len, pads occupy indices >= len, and jnp.argsort is
    stable — so among equal keys real rows sort before pads, which means
    sorted positions [0, len) are exactly the real rows. Validity is
    therefore positional; a real key equal to ``_PAD`` still matches.
    """
    n = l_rep.shape[0]
    m = r_rep.shape[0]
    l_key = jnp.where(jnp.arange(n) < l_len, l_rep, _PAD)
    r_key = jnp.where(jnp.arange(m) < r_len, r_rep, _PAD)
    perm_l = jnp.argsort(l_key)
    perm_r = jnp.argsort(r_key)
    ls = l_key[perm_l]
    rs = r_key[perm_r]
    lo = jnp.searchsorted(rs, ls, side="left")
    hi = jnp.searchsorted(rs, ls, side="right")
    # clip pads out of the match range: real right rows (even those whose
    # key equals _PAD) all live at sorted positions < r_len
    hi = jnp.minimum(hi, r_len)
    valid_l_sorted = jnp.arange(n) < l_len  # positional (see docstring)
    cnt = jnp.where(valid_l_sorted, jnp.maximum(hi - lo, 0), 0)
    return perm_l, perm_r, lo, cnt


_vmapped = jax.vmap(_bucket_join, in_axes=(0, 0, 0, 0))


@functools.partial(jax.jit, static_argnames=("mesh",))
def _sharded_join(mesh, l_rep, l_len, r_rep, r_len):
    return jax.shard_map(
        _vmapped,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
    )(l_rep, l_len, r_rep, r_len)


_jit_vmapped = jax.jit(_vmapped)


def _match_ranges_host(l_rep, l_len, r_rep, r_len):
    """Numpy twin of ``_bucket_join`` (identical algorithm and positional-
    validity contract) for workloads too small to amortize device dispatch
    and transfer latency."""
    B, n = l_rep.shape
    m = r_rep.shape[1]
    col_l = np.arange(n)[None, :]
    col_r = np.arange(m)[None, :]
    l_key = np.where(col_l < l_len[:, None], l_rep, _PAD)
    r_key = np.where(col_r < r_len[:, None], r_rep, _PAD)
    perm_l = np.argsort(l_key, axis=1, kind="stable")
    perm_r = np.argsort(r_key, axis=1, kind="stable")
    ls = np.take_along_axis(l_key, perm_l, axis=1)
    rs = np.take_along_axis(r_key, perm_r, axis=1)
    lo = np.empty((B, n), dtype=np.int64)
    hi = np.empty((B, n), dtype=np.int64)
    for b in range(B):
        lo[b] = np.searchsorted(rs[b], ls[b], side="left")
        hi[b] = np.searchsorted(rs[b], ls[b], side="right")
    hi = np.minimum(hi, r_len[:, None])
    cnt = np.where(col_l < l_len[:, None], np.maximum(hi - lo, 0), 0)
    return perm_l, perm_r, lo, cnt


def bucketed_match_ranges(
    mesh,
    l_rep: np.ndarray,
    l_len: np.ndarray,
    r_rep: np.ndarray,
    r_len: np.ndarray,
    device_min_rows: int = 0,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host entry. [B, n_max]/[B] per side; B padded to a multiple of the
    mesh size when sharded. Returns per-bucket (perm_l, perm_r, lo, cnt).

    Dispatches to the device program when total rows reach
    ``device_min_rows`` (or a >1-device mesh is available — sharded
    buckets amortize immediately); otherwise runs the numpy twin.
    """
    total = int(l_len.sum() + r_len.sum())
    use_mesh = (
        mesh is not None
        and mesh.devices.size > 1
        and l_rep.shape[0] % mesh.devices.size == 0
    )
    if not use_mesh and total < device_min_rows:
        return _match_ranges_host(l_rep, l_len, r_rep, r_len)
    host_args = (l_rep, l_len, r_rep, r_len)
    if use_mesh:
        # each device receives only its own bucket rows, straight from
        # the host — jnp.asarray would land every operand whole on
        # device 0 before the program reshards it
        args = tuple(put_sharded(mesh, a) for a in host_args)
        with mesh_dispatch_lock:
            out = _sharded_join(mesh, *args)
    else:
        out = _jit_vmapped(*(jnp.asarray(a) for a in host_args))
    return tuple(np.asarray(o) for o in out)
