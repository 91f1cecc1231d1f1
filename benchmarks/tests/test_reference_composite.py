"""``reference_composite.py`` against pairs worked by hand: the murmur
of a pair's sixteen bytes by a scalar MurmurHash3_x86_32 over bytes
(itself held to the algorithm's published test vectors), ``bucket_of``
for one key, the lexicographic order's ties, and the rows of a pair."""

import struct

import numpy as np
import pytest

import reference
import reference_composite as rc

M32 = 0xFFFFFFFF


def murmur3_x86_32(data: bytes, seed: int) -> int:
    """The published algorithm, one byte string at a time."""
    def rotl(x, r):
        return ((x << r) | (x >> (32 - r))) & M32

    h = seed & M32
    whole = len(data) // 4 * 4
    for i in range(0, whole, 4):
        k = struct.unpack_from("<I", data, i)[0]
        k = rotl(k * 0xCC9E2D51 & M32, 15) * 0x1B873593 & M32
        h = (rotl(h ^ k, 13) * 5 + 0xE6546B64) & M32
    k = 0
    for j, byte in enumerate(data[whole:]):
        k |= byte << (8 * j)
    if len(data) > whole:
        h ^= rotl(k * 0xCC9E2D51 & M32, 15) * 0x1B873593 & M32
    h ^= len(data)
    h ^= h >> 16
    h = h * 0x85EBCA6B & M32
    h ^= h >> 13
    h = h * 0xC2B2AE35 & M32
    return h ^ (h >> 16)


@pytest.mark.parametrize("data,seed,want", [
    (b"", 0, 0x00000000), (b"", 1, 0x514E28B7), (b"", 0xFFFFFFFF, 0x81F16F39),
    (b"\x00\x00\x00\x00", 0, 0x2362F9DE), (b"\xff\xff\xff\xff", 0, 0x76293B50),
    (b"\x21\x43\x65\x87", 0, 0xF55B516B), (b"\x21\x43\x65\x87", 0x5082EDEE, 0x2362F9DE),
    (b"\x21\x43\x65", 0, 0x7E4A8634), (b"\x21\x43", 0, 0xA0F7B07A), (b"\x21", 0, 0x72661CF4),
    (b"aaaa", 0x9747B28C, 0x5A97808A), (b"Hello, world!", 0x9747B28C, 0x24884CBA),
    (b"The quick brown fox jumps over the lazy dog", 0x9747B28C, 0x2FA826CD),
])
def test_the_scalar_murmur_gives_the_published_vectors(data, seed, want):
    assert murmur3_x86_32(data, seed) == want


PAIRS = [(1, 1), (1, 2), (2, 1), (533333, 26666), (0, 0), (-1, 7), (7, -1),
         (1 << 40, 3), (3, 1 << 40), (-(1 << 62), (1 << 62) + 5)]


def test_a_pairs_hash_is_the_murmur_of_its_sixteen_bytes():
    first = np.array([a for a, _ in PAIRS], dtype=np.int64)
    second = np.array([b for _, b in PAIRS], dtype=np.int64)
    got = rc.murmur3_32_blocks(rc._blocks((first, second)), 42)
    want = [murmur3_x86_32(struct.pack("<qq", a, b), 42) for a, b in PAIRS]     # l_partkey's 8 bytes first
    assert got.tolist() == want
    assert rc.bucket_of_pairs(first, second, 200).tolist() == [w % 200 for w in want]
    # the order of the keys is part of the hash, and so is the second key
    swapped = [murmur3_x86_32(struct.pack("<qq", b, a), 42) for a, b in PAIRS]
    assert [w for w, s, (a, b) in zip(want, swapped, PAIRS) if a != b and w == s] == []
    assert not np.array_equal(rc.bucket_of_pairs(first, second, 200), rc.bucket_of_keys((first,), 200))


def test_one_key_is_reference_bucket_of():
    keys = np.random.default_rng(5).integers(-(1 << 62), 1 << 62, 4000)
    keys[:50] = np.arange(50)
    assert np.array_equal(rc.bucket_of_keys((keys,), 200), reference.bucket_of(keys, 200))
    assert np.array_equal(rc.murmur3_32_blocks(rc._blocks((keys,)), 42), reference.murmur3_32_int64(keys, 42))
    assert rc.bucket_of_keys((keys[:3],), 200).tolist() == [
        murmur3_x86_32(struct.pack("<q", int(k)), 42) % 200 for k in keys[:3]]


def test_three_keys_extend_the_block_stream():
    a, b, c = (np.array(v, dtype=np.int64) for v in ([5, 6], [7, 8], [-9, 10]))
    want = [murmur3_x86_32(struct.pack("<qqq", *row), 42) % 200 for row in zip(a, b, c)]
    assert rc.bucket_of_keys((a, b, c), 200).tolist() == want


@pytest.mark.parametrize("first,second,want", [
    ([], [], 0), ([3], [9], 0),
    ([1, 1, 2, 2], [1, 1, 0, 5], 0),            # ties and a falling second key under a rising first
    ([1, 1, 2, 2], [2, 1, 0, 5], 1),            # sorted on the first key alone: its ties fall
    ([1, 2, 1], [5, 0, 5], 1),                  # the first key falls
    ([2, 2, 1, 1], [2, 1, 2, 1], 3),
    ([-5, -5, 0], [-2, -1, -9], 0),
])
def test_lex_unsorted_counts_the_falls_of_the_pair(first, second, want):
    assert rc.lex_unsorted(np.array(first, dtype=np.int64), np.array(second, dtype=np.int64)) == want


def test_pair_index_gives_the_rows_of_a_pair():
    cols = {"p": np.array([4, 2, 4, 4, 9, 2, 4]), "s": np.array([1, 7, 3, 1, 1, 7, 1]),
            "v": np.array([.5, 1.5, 2.5, 3.5, 4.5, 5.5, 6.5])}
    index = rc.PairIndex(cols, "p", "s")
    assert sorted(index.rows_of(4, 1).tolist()) == [0, 3, 6]
    assert sorted(index.rows_of(2, 7).tolist()) == [1, 5]
    assert index.rows_of(4, 7).tolist() == [] and index.rows_of(5, 1).tolist() == []     # each key occurs, the pair does not
    assert index.rows_of(9, 1).tolist() == [4]
    assert index.seconds_of(4).tolist() == [1, 3] and index.seconds_of(5).tolist() == []
    assert sorted(index.answer(4, 1, ["v"])["v"].tolist()) == [.5, 3.5, 6.5]
    assert reference.digest(index.answer(4, 7, ["p", "s", "v"])) == (0, 0, 0)


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(rc))
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    imported |= {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert imported == {"__future__", "numpy"}
