"""The reference's string bits against a loop over bytes, the forms a
string column comes in, and the string payload's control."""

import ast
import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

from conftest import BENCH

import reference

M64 = (1 << 64) - 1
STRINGS = ["", "a", "ab", "ba", "héllo wörld", "x" * 255, "y" * 256, "z" * 257, "carefully final " * 40,
           "special requests", "special  requests"]


def _fmix(z: int) -> int:
    z ^= z >> 30
    z = (z * 0xBF58476D1CE4E5B9) & M64
    z ^= z >> 27
    z = (z * 0x94D049BB133111EB) & M64
    return z ^ (z >> 31)


def _hash_py(s: str) -> int:
    """The documented hash, one byte at a time."""
    data = s.encode()
    total = sum(_fmix(((p << 8 | b) + int(reference._BYTE_SALT)) & M64) for p, b in enumerate(data))
    return _fmix((total + _fmix((len(data) + int(reference._LEN_SALT)) & M64)) & M64)


def test_string_bits_are_the_loop_over_bytes(monkeypatch):
    want = [_hash_py(s) for s in STRINGS]
    assert [int(b) for b in reference.string_bits(pa.array(STRINGS))] == want
    # chunks of a few bytes: rows cut across chunks, a string longer than a chunk alone in one
    monkeypatch.setattr(reference, "_CHUNK_BYTES", 7)
    assert [int(b) for b in reference.string_bits(pa.array(STRINGS))] == want
    assert len(set(want)) == len(want)


def test_every_form_of_the_same_strings_hashes_alike():
    values = STRINGS + [None, "a", None]
    plain = pa.array(values)
    want = reference.string_bits(plain)
    forms = {
        "large": pa.array(values, type=pa.large_string()),
        "dictionary": plain.dictionary_encode(),
        "dictionary_int8": plain.dictionary_encode().cast(pa.dictionary(pa.int8(), pa.string())),
        "chunked": pa.chunked_array([plain.slice(0, 5), plain.slice(5)]),
        "sliced": pa.array(["pad"] + values).slice(1),
        "object": np.array(values, dtype=object),
    }
    for name, form in forms.items():
        assert np.array_equal(reference._bits(form), want), name
    assert np.array_equal(reference._bits(np.array(STRINGS)), want[:len(STRINGS)])   # numpy str_
    # a null differs from '' and from every string here
    assert want[len(STRINGS)] == want[-1] and want[len(STRINGS)] not in set(want[:len(STRINGS)])


def _cols(n=5000, seed=3):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(19, 79, n)
    pool = "".join(rng.choice(list("abcdefghij klmnop"), 200))
    comments = np.array([pool[i % 100:i % 100 + k] for i, k in enumerate(lengths)], dtype=object)
    return {"o_custkey": rng.integers(1, 300, n), "o_orderkey": np.arange(n, dtype=np.int64),
            "o_comment": comments}


def test_the_digest_sees_one_byte_and_ignores_order():
    cols = _cols()
    want = reference.digest(cols)
    perm = np.random.default_rng(0).permutation(len(cols["o_orderkey"]))
    assert reference.digest({k: v[perm] for k, v in cols.items()}) == want
    for at in (0, 3, 18):      # one byte of one string: first, inside, last of the shortest
        bumped = dict(cols, o_comment=cols["o_comment"].copy())
        s = bumped["o_comment"][1234]
        bumped["o_comment"][1234] = s[:at] + chr(ord(s[at]) ^ 1) + s[at + 1:]
        assert reference.digest(bumped) != want, at
    # two rows' strings swapped: each row is another row
    swapped = dict(cols, o_comment=cols["o_comment"].copy())
    swapped["o_comment"][[7, 8]] = swapped["o_comment"][[8, 7]]
    assert reference.digest(swapped) != want
    # as a pyarrow table comes back from a query, dictionary-encoded or not
    table = pa.table({k: pa.array(v) for k, v in cols.items()})
    assert reference.digest(reference.table_cols(table)) == want
    coded = table.set_column(2, "o_comment", table.column("o_comment").dictionary_encode())
    got = reference.table_cols(coded)
    assert got["o_comment"].dtype == object and reference.digest(got) == want


def test_clipped_is_seen_and_passes_numbers_through():
    cols = _cols()
    clipped = reference.clipped(cols)
    assert all(len(s.encode()) == 16 for s in clipped["o_comment"])
    assert all(c.startswith(s) for c, s in zip(cols["o_comment"], clipped["o_comment"]))
    for k in ("o_custkey", "o_orderkey"):
        assert clipped[k] is cols[k]
    assert reference.digest(clipped) != reference.digest(cols)
    # lossy leaves strings alone, and clipped a table with none
    assert reference.digest(reference.lossy(cols)) == reference.digest(cols)
    numbers = {"k": np.arange(9), "v": np.linspace(0, 1, 9)}
    assert reference.digest(reference.clipped(numbers)) == reference.digest(numbers)
    assert reference.clipped({"s": np.array(["a" * 20, None], dtype=object)})["s"].tolist() == ["a" * 16, None]


def test_point_answers_with_a_string_payload_against_brute_force():
    cols = _cols()
    index = reference.KeyIndex(cols, "o_custkey")
    keys = [int(cols["o_custkey"][5]), 300, int(cols["o_custkey"][77])]
    got_cols, counts = reference.ref_point(index, keys, list(cols))
    got = reference.segment_digests(got_cols, counts)
    for i, k in enumerate(keys):
        m = cols["o_custkey"] == k
        assert counts[i] == m.sum()
        want = reference.digest({c: v[m] for c, v in cols.items()})
        assert tuple(int(x) for x in got[i]) == want
    assert counts[1] == 0


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "reference.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    names += [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert names and not [n for n in names if n.split(".")[0] == "hyperspace_tpu"], names
    probe = ("import sys, numpy as np; sys.path.insert(0, sys.argv[1]); import reference; "
             "reference.digest({'s': np.array(['a', None], dtype=object), 'k': np.arange(2)}); "
             "print(sorted(m for m in sys.modules if m.split('.')[0] == 'hyperspace_tpu'))")
    p = subprocess.run([sys.executable, "-c", probe, BENCH], capture_output=True, text=True, timeout=120)
    assert p.returncode == 0 and p.stdout.strip() == "[]", p.stdout + p.stderr
