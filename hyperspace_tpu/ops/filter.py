"""XLA-compiled columnar predicate evaluation (the device twin of
``plan/expressions.evaluate``).

This is the scan-side filter kernel of the serve path (SURVEY §7 Phase 2:
"XLA-compiled columnar filter kernel over index files"). The host prepares
device-friendly inputs per batch:

* numeric columns → their value arrays (+ validity);
* string columns → per-row dictionary *rank* arrays (order-preserving
  integers, host-computed O(unique) — see ``plan/expressions._StringRef``),
  with string literals lowered to ``(bisect_left, bisect_right)`` rank
  bounds. Every string predicate (=, <, IN, …) is thereby pure integer
  arithmetic on device.

The expression tree is lowered to a hashable *spec* (nested tuples) used as
the jit static argument, so each predicate shape compiles once; literals
and column arrays flow in as dynamic args (changing a literal or reading a
different file does not recompile).
"""

from __future__ import annotations

import functools
import math
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import hyperspace_tpu.ops  # noqa: F401  (enables x64)
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.plan import expressions as E


class Unsupported(HyperspaceException):
    """Expression not device-compilable; caller falls back to host eval."""


class _Prep:
    """Lowers an Expr over a given batch into (spec, args)."""

    def __init__(self, batch):
        self.batch = batch
        self.args: List[Any] = []
        self.row_slots: set = set()  # arg indices holding per-row arrays
        self._col_slots = {}

    def _arg(self, v, per_row: bool = False) -> int:
        self.args.append(v)
        if per_row:
            self.row_slots.add(len(self.args) - 1)
        return len(self.args) - 1

    def _col(self, name: str):
        """-> ("col", values_slot, valid_slot|-1, kind)"""
        if name in self._col_slots:
            return self._col_slots[name]
        col = self.batch.column(name)
        if col.kind == "string":
            ref = E._StringRef(col.codes, col.dictionary)
            vals = self._arg(ref.rank_values().astype(np.int64), per_row=True)
            valid = self._arg(ref.valid, per_row=True)
            spec = ("col", vals, valid, "string", name)
            self._col_slots[name] = (spec, ref)
            return self._col_slots[name]
        if col.values.dtype == np.float64:
            # The TPU has no IEEE double: a float64 it holds keeps
            # float32's exponent range and fewer mantissa bits (1e300
            # arrives as inf, 3.0000000000000004 as 3.0 — chip run, PR
            # 21), so a compare against such a column cannot give the
            # host's mask. The host evaluates it.
            raise Unsupported(f"float64 column on device: {name!r}")
        vals = self._arg(col.values, per_row=True)
        valid = (
            -1 if col.validity is None else self._arg(col.validity, per_row=True)
        )
        spec = ("col", vals, valid, "numeric", name)
        self._col_slots[name] = (spec, None)
        return self._col_slots[name]

    def lower(self, e: E.Expr):
        if isinstance(e, E.Lit):
            if e.value is None:
                return ("null",)
            if not isinstance(e.value, (bool, np.bool_)):
                raise Unsupported(f"Bare non-bool literal: {e!r}")
            return ("const", bool(e.value))
        if isinstance(e, (E.Eq, E.Ne, E.Lt, E.Le, E.Gt, E.Ge)):
            op = e.op
            left, right = e.left, e.right
            flipped = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "=", "!=": "!="}
            if isinstance(left, E.Lit) and not isinstance(right, E.Lit):
                left, right = right, left
                op = flipped[op]
            if isinstance(left, E.Col) and isinstance(right, E.Lit):
                if right.value is None:
                    return ("null",)
                cspec, ref = self._col(left.name)
                if ref is not None:  # string: literal -> rank bounds
                    lo, hi = ref.rank_bounds(str(right.value))
                    return (
                        "cmp_str",
                        op,
                        cspec,
                        self._arg(np.int64(lo)),
                        self._arg(np.int64(hi)),
                    )
                lit = E.lower_literal(
                    right.value, self.batch.column(left.name).arrow_type, op
                )
                if lit is None:
                    # unrepresentable literal: constant truth value but
                    # UNKNOWN on null rows — mirrors the host path's
                    # (vals, column-validity) exactly so NOT composes the
                    # same on both paths
                    return ("unrep", op == "!=", cspec)
                return ("cmp_lit", op, cspec, self._arg(np.asarray(lit)))
            if isinstance(left, E.Col) and isinstance(right, E.Col):
                lspec, lref = self._col(left.name)
                rspec, rref = self._col(right.name)
                if (lref is None) != (rref is None):
                    raise Unsupported(f"Mixed-type column comparison: {e!r}")
                if lref is not None:
                    # ranks are per-column orders; cross-column string
                    # comparison needs the host path
                    raise Unsupported(f"String col-col comparison: {e!r}")
                return ("cmp_col", op, lspec, rspec)
            raise Unsupported(f"Comparison operands: {e!r}")
        if isinstance(e, E.And):
            return ("and", self.lower(e.left), self.lower(e.right))
        if isinstance(e, E.Or):
            return ("or", self.lower(e.left), self.lower(e.right))
        if isinstance(e, E.Not):
            return ("not", self.lower(e.child))
        if isinstance(e, E.IsNull):
            if not isinstance(e.child, E.Col):
                raise Unsupported(f"IS NULL on non-column: {e!r}")
            cspec, _ref = self._col(e.child.name)
            return ("isnull", cspec)
        if isinstance(e, E.In):
            if not isinstance(e.child, E.Col):
                raise Unsupported(f"IN on non-column: {e!r}")
            cspec, ref = self._col(e.child.name)
            # a NULL in the list makes non-matching rows UNKNOWN (host twin)
            has_null = any(v is None for v in e.values)
            vals = [v for v in e.values if v is not None]
            if not vals:
                if has_null:
                    # x IN (NULL): unknown on every row (host: vals=0,
                    # known=0) — exactly the null-literal spec
                    return ("null",)
                # x IN () is never true (matches the host path's all-False)
                return ("const", False)
            if ref is not None:
                ranks = []
                for v in vals:
                    if not isinstance(v, str):
                        continue  # non-string literal never matches
                    lo, hi = ref.rank_bounds(v)
                    if hi > lo:
                        ranks.append(lo)
                arr = np.array(sorted(ranks) or [-1], dtype=np.int64)
            else:
                # shared lowering with the host path (E.lower_in_literals)
                # so device and host IN agree on temporal/typed literals
                lits = E.lower_in_literals(
                    vals, self.batch.column(e.child.name).arrow_type
                )
                if not lits:
                    # NULL marker survives even when every non-null
                    # literal lowered away (host twin: unknown rows)
                    return ("null",) if has_null else ("const", False)
                arr = np.sort(np.array(lits))
                if arr.dtype.kind not in "iuf":
                    raise Unsupported(f"IN literal set: {e!r}")
            return ("in", cspec, self._arg(arr), has_null)
        raise Unsupported(f"Expression not device-compilable: {e!r}")


def _eval_spec(spec, args, n):
    """Recursive jnp evaluation -> (values[bool n], valid[bool n])."""
    kind = spec[0]
    t = lambda: jnp.ones(n, dtype=bool)
    if kind == "null":
        return jnp.zeros(n, bool), jnp.zeros(n, bool)
    if kind == "const":
        return jnp.full(n, spec[1]), t()
    if kind in ("cmp_lit", "cmp_col", "cmp_str"):
        op = spec[1]
        _c, vslot, valslot, _k, _name = spec[2]
        v = args[vslot]
        valid = t() if valslot == -1 else args[valslot]
        if kind == "cmp_lit":
            lit = args[spec[3]]
            vals = _apply_cmp(op, v, lit)
        elif kind == "cmp_str":
            lo, hi = args[spec[3]], args[spec[4]]
            vals = {
                "=": (v >= lo) & (v < hi),
                "!=": ~((v >= lo) & (v < hi)),
                "<": v < lo,
                "<=": v < hi,
                ">": v >= hi,
                ">=": v >= lo,
            }[op]
        else:
            _c2, vslot2, valslot2, _k2, _n2 = spec[3]
            v2 = args[vslot2]
            valid = valid & (t() if valslot2 == -1 else args[valslot2])
            vals = _apply_cmp(op, v, v2)
        return vals, valid
    if kind == "and":
        lv, lk = _eval_spec(spec[1], args, n)
        rv, rk = _eval_spec(spec[2], args, n)
        vals = lv & rv & lk & rk
        known = (lk & rk) | (lk & ~lv) | (rk & ~rv)
        return vals, known
    if kind == "or":
        lv, lk = _eval_spec(spec[1], args, n)
        rv, rk = _eval_spec(spec[2], args, n)
        vals = (lv & lk) | (rv & rk)
        known = (lk & rk) | (lk & lv) | (rk & rv)
        return vals, known
    if kind == "not":
        v, k = _eval_spec(spec[1], args, n)
        return ~v, k
    if kind == "isnull":
        _c, vslot, valslot, _k, _name = spec[1]
        valid = t() if valslot == -1 else args[valslot]
        return ~valid, t()
    if kind == "unrep":
        # constant truth value, unknown on null rows (host-path twin of
        # the unrepresentable-literal comparison)
        _c, vslot, valslot, _k, _name = spec[2]
        valid = t() if valslot == -1 else args[valslot]
        return jnp.full(n, spec[1]), valid
    if kind == "in":
        _c, vslot, valslot, _k, _name = spec[1]
        v = args[vslot]
        valid = t() if valslot == -1 else args[valslot]
        lits = args[spec[2]]
        # binary-search membership (SortedArrayLowerBound-style,
        # dataskipping/expressions/SortedArrayLowerBound.scala)
        pos = jnp.searchsorted(lits, v)
        pos = jnp.clip(pos, 0, lits.shape[0] - 1)
        vals = lits[pos] == v
        if len(spec) > 3 and spec[3]:  # NULL in the list: non-matches unknown
            valid = valid & vals
        return vals, valid
    raise HyperspaceException(f"Bad spec node: {spec!r}")


def _apply_cmp(op, a, b):
    return {
        "=": a == b,
        "!=": a != b,
        "<": a < b,
        "<=": a <= b,
        ">": a > b,
        ">=": a >= b,
    }[op]


@functools.partial(jax.jit, static_argnames=("spec", "n"))
def _run(spec, n, args: Tuple):
    vals, valid = _eval_spec(spec, list(args), n)
    return vals & valid


# ---------------------------------------------------------------------------
# Fused range mask (hs_range_mask; docs/range-serve.md)
# ---------------------------------------------------------------------------
#
# A conjunction of numeric col-vs-lit range/Eq conjuncts — the residual
# mask of the range serve plane after zone-map pruning — evaluates on the
# host as one fused compare-AND pass instead of ~2 numpy passes per
# conjunct plus the Kleene bookkeeping of the expression interpreter.
# Final-mask equivalence is exact: for a conjunction, the filter's final
# mask equals the AND of each conjunct's (values & valid) mask, and each
# supported conjunct's mask is a pair of bound comparisons ANDed with the
# column's validity. Anything outside that shape (strings, IN, OR, NOT,
# IS NULL, !=, unloggable literals) falls back to the interpreter
# unchanged.

# At or above this ROW count the fused mask dispatches to the native
# kernel; below it the numpy twin's vectorized passes win. FALLBACK
# DEFAULT: the effective threshold comes from the per-machine calibration
# probe (native/calibrate.py); an explicit module-attribute override wins.
_NATIVE_RANGE_MASK_MIN_ROWS_DEFAULT = 1 << 15
_NATIVE_RANGE_MASK_MIN_ROWS = _NATIVE_RANGE_MASK_MIN_ROWS_DEFAULT


def _native_range_mask_min_rows() -> int:
    if _NATIVE_RANGE_MASK_MIN_ROWS != _NATIVE_RANGE_MASK_MIN_ROWS_DEFAULT:
        return _NATIVE_RANGE_MASK_MIN_ROWS  # explicit (test/ops) override
    from hyperspace_tpu.native import calibrate

    return (
        calibrate.thresholds().native_range_mask_min_rows
        or _NATIVE_RANGE_MASK_MIN_ROWS
    )


class _BatchColTypes:
    """Lazy ``name -> (dtype_kind, arrow_type)`` view of a batch for
    :func:`lower_range_terms_typed` — only the columns the condition
    actually references are inspected (a wide covering index would
    otherwise pay an all-columns dict per filter serve)."""

    def __init__(self, batch):
        self._batch = batch

    def __contains__(self, name) -> bool:
        return name in self._batch.columns

    def __getitem__(self, name):
        col = self._batch.columns[name]
        return (
            "S" if col.kind == "string" else col.values.dtype.kind,
            col.arrow_type,
        )


def lower_range_terms(expr: E.Expr, batch):
    """[(name, lo, lo_strict, hi, hi_strict, empty)] when EVERY conjunct
    is a numeric col-vs-lit comparison in =,<,<=,>,>= with a literal the
    engine can compare (temporal literals lowered with the same op-aware
    snapping the interpreter uses), else None. ``empty`` marks a conjunct
    whose lowered literal can never match (all-False mask)."""
    return lower_range_terms_typed(expr, _BatchColTypes(batch))


def lower_range_terms_typed(expr: E.Expr, cols):
    """:func:`lower_range_terms` against a ``{name: (dtype_kind,
    arrow_type)}`` mapping instead of a materialized batch — the
    pre-read half the serve-pipeline compiler needs (the decoded numpy
    dtype kind is derivable from the arrow type before any file is
    opened; see ``pipeline_compiler._np_kind``). The batch-based wrapper
    above feeds it the actual decoded kinds, so the two can never
    disagree on a column the batch carries."""
    terms = []
    for cj in E.split_conjuncts(expr):
        norm = E.normalize_comparison(cj)
        if norm is None:
            return None
        op, name, lit = norm
        if op == "!=":
            return None
        if name not in cols:
            return None
        kind, arrow_type = cols[name]
        if kind == "S":
            return None
        if kind not in "if":
            return None  # uint/bool columns keep the interpreter path
        lv = E.lower_literal(lit, arrow_type, op)
        if lv is None:
            terms.append((name, None, False, None, False, True))
            continue
        if isinstance(lv, (np.integer, np.floating)):
            pass  # engine-lowered scalar, compares exactly
        elif isinstance(lv, bool):
            lv = int(lv)
        elif isinstance(lv, int):
            if kind == "i" and not (-(2**63) <= lv < 2**63):
                return None  # out-of-range python int: interpreter decides
        elif not isinstance(lv, float):
            return None  # non-numeric literal on a numeric column
        if op == "=":
            terms.append((name, lv, False, lv, False, False))
        elif op == "<":
            terms.append((name, None, False, lv, True, False))
        elif op == "<=":
            terms.append((name, None, False, lv, False, False))
        elif op == ">":
            terms.append((name, lv, True, None, False, False))
        else:  # >=
            terms.append((name, lv, False, None, False, False))
    if not terms or len(terms) > 16:
        return None
    return terms


def range_mask_numpy(batch, terms) -> np.ndarray:
    """The numpy twin of ``hs_range_mask``: per term the SAME comparison
    expressions the host interpreter runs (so dtype promotion, NaN and
    uint semantics can never diverge), ANDed into one mask."""
    n = batch.num_rows
    out = np.ones(n, dtype=bool)
    with np.errstate(invalid="ignore"):
        for name, lo, lo_strict, hi, hi_strict, empty in terms:
            col = batch.columns[name]
            if empty:
                vals = np.zeros(n, dtype=bool)
            else:
                v = col.values
                vals = np.ones(n, dtype=bool)
                if lo is not None:
                    vals &= (v > lo) if lo_strict else (v >= lo)
                if hi is not None:
                    vals &= (v < hi) if hi_strict else (v <= hi)
            if col.validity is not None:
                vals = vals & col.validity
            out &= vals
    return out


NEVER_MATCH = "never"


def native_range_bounds(terms, f64_flags):
    """Lower range-term bounds into the exact int64/float64 form the
    native kernels compare with — shared by ``hs_range_mask``,
    ``hs_fused_filter_select`` and ``hs_fused_filter_agg`` so the three
    can never disagree with the numpy twin on a bound.

    ``f64_flags``: per-term bool, True when the column is float64 (else
    an int64-view column). Returns ``(lo_i, hi_i, lo_f, hi_f, flags)``
    lists aligned with ``terms``, :data:`NEVER_MATCH` when some bound can
    never hold (all-False mask), or None when a bound is not exactly
    representable natively (the numpy twin must decide). Integer bounds
    given as floats tighten to the enclosing integers (exact on integer
    domains)."""
    lo_i, hi_i, lo_f, hi_f, flags = [], [], [], [], []
    for (name, lo, lo_strict, hi, hi_strict, empty), f64 in zip(
        terms, f64_flags
    ):
        if empty:
            return NEVER_MATCH

        def int_bound(b, is_lo):
            """(bound, strict) in exact int64, or None to bail."""
            nonlocal_strict = lo_strict if is_lo else hi_strict
            if isinstance(b, (np.integer,)):
                b = int(b)
            if isinstance(b, float) or isinstance(b, np.floating):
                fb = float(b)
                if math.isnan(fb):
                    return "never"
                if math.isinf(fb):
                    # -inf lo / +inf hi: unbounded; +inf lo / -inf hi:
                    # nothing can pass
                    if (fb > 0) == is_lo:
                        return "never"
                    return "unbounded"
                if abs(fb) >= 2.0**53:
                    # the interpreter/twin compare int64 values against a
                    # FLOAT bound by promoting the column to float64; an
                    # exact int64 compare diverges for values beyond
                    # 2^53, so the numpy twin must decide these
                    return None
                if fb != int(fb):
                    # v > 2.5 == v >= 3; v < 2.5 == v <= 2 on integers
                    return (
                        (math.ceil(fb), False)
                        if is_lo
                        else (math.floor(fb), False)
                    )
                b = int(fb)
            if not isinstance(b, int):
                return None
            if not (-(2**63) <= b < 2**63):
                return None
            return (b, nonlocal_strict)

        if f64:
            def f_bound(b):
                if isinstance(b, (int, np.integer)) and not isinstance(b, bool):
                    fb = np.float64(b)
                    if int(fb) != int(b):
                        return None  # not exactly representable: bail
                    return float(fb)
                return float(b)

            flo = f_bound(lo) if lo is not None else None
            fhi = f_bound(hi) if hi is not None else None
            if (lo is not None and flo is None) or (
                hi is not None and fhi is None
            ):
                return None
            lo_f.append(flo if flo is not None else 0.0)
            hi_f.append(fhi if fhi is not None else 0.0)
            lo_i.append(0)
            hi_i.append(0)
            flags.append(
                (lo is not None, hi is not None, lo_strict, hi_strict)
            )
        else:
            ilo = int_bound(lo, True) if lo is not None else "unbounded"
            ihi = int_bound(hi, False) if hi is not None else "unbounded"
            if ilo is None or ihi is None:
                return None
            if ilo == "never" or ihi == "never":
                return NEVER_MATCH
            has_lo = ilo != "unbounded"
            has_hi = ihi != "unbounded"
            lo_i.append(ilo[0] if has_lo else 0)
            hi_i.append(ihi[0] if has_hi else 0)
            lo_f.append(0.0)
            hi_f.append(0.0)
            flags.append(
                (
                    has_lo,
                    has_hi,
                    ilo[1] if has_lo else False,
                    ihi[1] if has_hi else False,
                )
            )
    return lo_i, hi_i, lo_f, hi_f, flags


def native_terms_for_batch(batch, terms):
    """The full native argument set for ``terms`` over ``batch``:
    ``(cols, valids, is_f64, lo_i, hi_i, lo_f, hi_f, flags)`` ready for
    ``native.range_mask_u8`` / ``native.fused_filter_select``, or
    :data:`NEVER_MATCH` (all-False), or None (numpy twin decides —
    non-8-byte/non-contiguous columns or unrepresentable bounds)."""
    cols = []
    valids = []
    is_f64 = []
    for name, _lo, _ls, _hi, _hs, _empty in terms:
        col = batch.columns[name]
        v = col.values
        if v.ndim != 1 or v.dtype.itemsize != 8 or not v.flags.c_contiguous:
            return None
        f64 = v.dtype.kind == "f"
        if f64 and v.dtype != np.float64:
            return None
        if not f64 and v.dtype.kind not in "iMm":
            return None
        is_f64.append(f64)
        cols.append(v if f64 else v.view(np.int64))
        valids.append(col.validity)
    bounds = native_range_bounds(terms, is_f64)
    if bounds is None or bounds == NEVER_MATCH:
        return bounds
    lo_i, hi_i, lo_f, hi_f, flags = bounds
    return cols, valids, is_f64, lo_i, hi_i, lo_f, hi_f, flags


def _native_range_mask(batch, terms) -> Optional[np.ndarray]:
    """Native dispatch of the fused mask: contiguous 8-byte numeric
    columns with exactly-representable bounds only — anything else
    returns None and the numpy twin runs."""
    n = batch.num_rows
    prep = native_terms_for_batch(batch, terms)
    if prep is None:
        return None
    if prep == NEVER_MATCH:
        return np.zeros(n, dtype=bool)
    from hyperspace_tpu import native

    return native.range_mask_u8(*prep, n)


def range_mask(batch, terms) -> np.ndarray:
    """Host dispatch of the fused range mask: the native single-pass
    kernel at or above the calibrated row crossover, else the numpy twin
    — identical output either way."""
    if batch.num_rows >= _native_range_mask_min_rows():
        out = _native_range_mask(batch, terms)
        if out is not None:
            return out
    return range_mask_numpy(batch, terms)


def fused_range_mask(expr: E.Expr, batch) -> Optional[np.ndarray]:
    """The executor's entry: the fused mask when the whole predicate
    lowers to numeric range terms, else None (interpreter path)."""
    if batch.num_rows == 0:
        return None
    terms = lower_range_terms(expr, batch)
    if terms is None:
        return None
    return range_mask(batch, terms)


def device_filter_mask(expr: E.Expr, batch) -> np.ndarray:
    """Evaluate a predicate on device; raises :class:`Unsupported` when the
    expression needs the host path (``plan/expressions.filter_mask``).

    Per-row args are padded to ``pad_len`` (ops/__init__ shape policy) so
    the kernel compiles once per (predicate shape, 2x size band); pad rows
    are sliced off the mask. Validity pads are False, so even spec nodes
    that read validity alone (isnull) can't leak pad rows into downstream
    consumers that might ignore the slice.
    """
    from hyperspace_tpu.ops import pad_len

    n = batch.num_rows
    if n == 0:
        return np.zeros(0, dtype=bool)
    p = _Prep(batch)
    spec = p.lower(expr)
    n_pad = pad_len(n)
    args = []
    for i, a in enumerate(p.args):
        a = np.asarray(a)
        if i in p.row_slots and n_pad != n:
            fill = np.zeros((n_pad - n,) + a.shape[1:], dtype=a.dtype)
            a = np.concatenate([a, fill])
        args.append(jnp.asarray(a))
    return np.asarray(_run(spec, n_pad, tuple(args)))[:n]
