"""The z-layout's plain reference against hand-worked rows, the Q6-shaped
range answer on a table small enough to read, the parameters the driver
draws, and the bytes of the z-order build's two device programs."""

import json
import os
import struct
import types

import numpy as np
import pytest

from conftest import BENCH

import reference_zorder as rz
import roofline_zorder
from drivers import zorder_build

TOP = 65535


def _enc_float(x: float) -> int:
    """IEEE total order, from the eight bytes and nothing else."""
    (bits,) = struct.unpack("<Q", struct.pack("<d", x))
    return (~bits) & (2**64 - 1) if bits >> 63 else bits | 1 << 63


def _word(enc: int, lo: int, hi: int) -> int:
    return int(min(float(enc - lo) * (65535.0 / float(hi - lo)), 65535.0)) if hi > lo else 0


def test_order_encoding_by_hand():
    ints = np.array([-(2**63), -1, 0, 1, 2**63 - 1], dtype=np.int64)
    assert [int(e) for e in rz.order_u64(ints)] == [0, 2**63 - 1, 2**63, 2**63 + 1, 2**64 - 1]
    days = np.array([-3, 0, 9131], dtype=np.int32)      # date32 as day counts
    assert [int(e) for e in rz.order_u64(days)] == [2**63 - 3, 2**63, 2**63 + 9131]
    assert [int(e) for e in rz.order_u64(days.astype("datetime64[D]"))] == [2**63 - 3, 2**63, 2**63 + 9131]
    floats = np.array([-np.inf, -1.5, -0.0, 0.0, 5e-324, 0.01, 2.0, np.inf])
    enc = [int(e) for e in rz.order_u64(floats)]
    assert enc == [_enc_float(float(x)) for x in floats] and enc == sorted(enc) and len(set(enc)) == len(enc)
    assert enc[3] == 2**63 and enc[2] == 2**63 - 1      # 0.0, and -0.0 just under it
    with pytest.raises(TypeError):
        rz.order_u64(np.array(["a"]))


def test_words_by_hand_min_max_tie_and_a_float_below_zero():
    # integers whose scaling is exact in float64: (v - 0) x 6553.5
    v = np.array([0, 10, 5, 5, 1], dtype=np.int64)
    assert rz.words(v, 0, 10).tolist() == [0, TOP, 32767, 32767, 6553]
    assert rz.words(v, 7, 7).tolist() == [0] * 5           # max = min
    # a value the stated extremes leave out is clamped, not wrapped
    assert rz.words(np.array([12], dtype=np.int64), 0, 10).tolist() == [TOP]
    f = np.array([-1.5, -0.0, 0.0, 0.5, 2.0])
    lo, hi = _enc_float(-1.5), _enc_float(2.0)
    assert rz.words(f, -1.5, 2.0).tolist() == [_word(_enc_float(float(x)), lo, hi) for x in f]
    assert rz.words(f, -1.5, 2.0)[0] == 0 and rz.words(f, -1.5, 2.0)[-1] in (TOP - 1, TOP)
    # l_discount: scaled in encoding space, so 0.00 is word 0 and 0.01 .. 0.10
    # lie in the top 0.4% of the words, in order
    d = np.arange(11) / 100.0
    w = rz.words(d, 0.0, 0.10)
    assert w[0] == 0 and w[1] > 0.996 * TOP and np.all(np.diff(w.astype(np.int64)) > 0)


def test_address_bits_by_hand():
    # values 0..65535 under min 0, max 65535: the word is the value
    cols = {"a": np.array([0x8000, 0, 0xFFFF, 0, 1, 0], dtype=np.int64),
            "b": np.array([0, 0x8000, 0, 0xFFFF, 0, 1], dtype=np.int64)}
    z = rz.z_address(cols, ["a", "b"], [0, 0], [TOP, TOP])
    assert [int(x) for x in z] == [1 << 31, 1 << 30, 0xAAAAAAAA, 0x55555555, 2, 1]
    # the first indexed column is the more significant of each pair of bits
    assert [int(x) for x in rz.z_address(cols, ["b", "a"], [0, 0], [TOP, TOP])] == [
        1 << 30, 1 << 31, 0x55555555, 0xAAAAAAAA, 1, 2]
    three = dict(cols, c=np.array([0, 0, 0, 0, 0, 0xFFFF], dtype=np.int64))
    z3 = rz.z_address(three, ["a", "b", "c"], [0] * 3, [TOP] * 3)
    assert int(z3[0]) == 1 << 47 and int(z3[1]) == 1 << 46 and int(z3[4]) == 1 << 2
    assert int(z3[5]) == (1 << 1) | int("001" * 16, 2)
    assert int(z3.max()) < 1 << 48
    with pytest.raises(ValueError):
        rz.z_address({c: cols["a"] for c in "abcde"}, list("abcde"), [0] * 5, [TOP] * 5)
    # a tie: equal rows, equal address
    tie = {"a": np.array([7, 7], dtype=np.int64), "b": np.array([0.5, 0.5])}
    z = rz.z_address(tie, ["a", "b"], [0, 0.0], [9, 1.0])
    assert z[0] == z[1]
    assert rz.inversions(np.array([1, 1, 3, 2, 2, 0], dtype=np.uint64)) == 2


@pytest.mark.parametrize("moving", ["l_shipdate", "l_discount", "l_quantity"])
def test_address_is_monotone_in_each_column_alone(moving):
    rng = np.random.default_rng(11)
    n = 2000
    values = {"l_shipdate": np.sort(rng.integers(8036, 10562, n).astype(np.int32)),
              "l_discount": np.sort(rng.integers(0, 11, n) / 100.0),
              "l_quantity": np.sort(rng.integers(1, 51, n))}
    fixed = {"l_shipdate": np.full(n, 9000, dtype=np.int32), "l_discount": np.full(n, 0.05),
             "l_quantity": np.full(n, 25, dtype=np.int64)}
    cols = dict(fixed, **{moving: values[moving]})
    indexed = list(values)
    mins = [values[c].min() for c in indexed]
    maxs = [values[c].max() for c in indexed]
    z = rz.z_address(cols, indexed, mins, maxs)
    assert rz.inversions(z) == 0 and z[0] < z[-1]


def test_range_rows_on_a_ten_row_table():
    day = lambda s: int((np.datetime64(s) - np.datetime64("1970-01-01")).astype(np.int64))  # noqa: E731
    cols = {
        "l_shipdate": np.array([day(s) for s in (
            "1993-12-31", "1994-01-01", "1994-06-15", "1994-12-31", "1995-01-01",
            "1994-03-03", "1994-03-03", "1994-03-03", "1994-03-03", "1994-03-03")], dtype=np.int32),
        "l_discount": np.array([0.06, 0.06, 0.06, 0.06, 0.06, 0.04, 0.05, 0.07, 0.08, 0.06]),
        "l_quantity": np.array([10, 10, 10, 10, 10, 10, 10, 10, 10, 24], dtype=np.int64),
    }
    mask = rz.range_rows(cols, day("1994-01-01"), day("1995-01-01"), 5 / 100.0, 7 / 100.0, 24)
    # the first day is in and the day after the last is out; both ends of
    # the discount are in; the quantity's end is out
    assert mask.tolist() == [False, True, True, True, False, False, True, True, False, False]
    assert rz.range_rows(cols, day("1994-01-01"), day("1995-01-01"), 0.05, 0.07, 25)[-1]
    dated = dict(cols, l_shipdate=cols["l_shipdate"].astype("datetime64[D]"))
    assert rz.range_rows(dated, day("1994-01-01"), day("1995-01-01"), 0.05, 0.07, 24).tolist() == mask.tolist()


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 3400000123])
def test_the_drivers_parameters_are_clause_2_4_6_3s(seed):
    ctx = types.SimpleNamespace(seed=seed, traffic={"range_queries": 8})
    params = zorder_build.range_params(ctx)
    assert params == zorder_build.range_params(ctx) and len(params) == 8
    assert {d0.year for d0, *_ in params} == {1993, 1994, 1995, 1996, 1997}
    for d0, d1, lo, hi, q in params:
        assert (d0.month, d0.day) == (1, 1) and d1 == d0.replace(year=d0.year + 1)
        d = round((lo + hi) * 50)
        assert 2 <= d <= 9 and lo == (d - 1) / 100.0 and hi == (d + 1) / 100.0
        assert q in (24, 25)
    assert params != zorder_build.range_params(types.SimpleNamespace(seed=seed + 1, traffic={}))


def test_the_kernels_bytes_are_one_builds_work():
    with open(os.path.join(BENCH, "configs", "tpch-zorder-1chip.json")) as f:
        config = json.load(f)
    rows = config["rows"]
    # three 32-bit words in, ceil(3 x 16 / 32) = 2 planes out: 20 B a row
    assert roofline_zorder.interleave_bytes(rows, config) == rows * 20 == 320_000_000
    # two planes in, one 4-byte row index out: 12 B a row
    assert roofline_zorder.lexsort_bytes(rows, config) == rows * 12 == 192_000_000
    two = dict(config, index=dict(config["index"], indexed=config["index"]["indexed"][:2]))
    assert roofline_zorder.interleave_bytes(1000, two) == 1000 * 4 * (2 + 1)
    assert roofline_zorder.lexsort_bytes(1000, two) == 1000 * (4 + 4)
    assert roofline_zorder.BITS_PER_COLUMN == rz.BITS


def test_inversions_are_counted_inside_files_and_across_their_boundaries():
    def part(values):
        return {"a": np.array(values, dtype=np.int64)}

    # words are the values (min 0, max 65535): the first file ends above the
    # second's first row, the second has one pair out of order, an empty
    # file lies between
    files = [part([1, 2, 9]), part([]), part([5, 4, 7]), part([7, 8])]
    assert rz.inversions_across(files, ["a"], [0], [TOP]) == (8, 2)
    assert rz.inversions_across([part([1, 2]), part([2, 3])], ["a"], [0], [TOP]) == (4, 0)
    assert rz.inversions_across([], ["a"], [0], [TOP]) == (0, 0)


@pytest.mark.parametrize("k", [1, 2, 3, 4])
def test_the_address_is_the_bit_loops(k):
    """``z_address`` looks each word up in a table of spread bits; the
    definition is the loop over the address's bits, kept here."""
    rng = np.random.default_rng(k)
    names = list("abcd")[:k]
    cols = {c: rng.integers(0, TOP + 1, 5000) for c in names}
    for c in names:
        cols[c][:2] = (0, TOP)
    address = np.zeros(5000, dtype=np.uint64)
    for t in range(k * 16):
        bit = (cols[names[t % k]].astype(np.uint64) >> np.uint64(15 - t // k)) & np.uint64(1)
        address = (address << np.uint64(1)) | bit
    assert np.array_equal(rz.z_address(cols, names, [0] * k, [TOP] * k), address)
