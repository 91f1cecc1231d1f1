"""``reference_skipping.py`` against values worked by hand: its numpy
murmur held to MurmurHash3_x86_32's published vectors and to the scalar
algorithm (``test_reference_composite.murmur3_x86_32``, itself held to
them) over a key's eight bytes under the bloom's two seeds, its (m, k),
its bit layout bit by bit, the files a pruning keeps, and the roofline's
bytes."""

import math
import struct

import numpy as np
import pytest

import reference_composite as rc
import reference_skipping as rs
import roofline_skipping
from test_reference_composite import murmur3_x86_32

M32 = 0xFFFFFFFF
KEYS = [0, 1, -1, 7, 533333, 533334, 1 << 40, -(1 << 62), (1 << 63) - 1]


@pytest.mark.parametrize("data,seed,want", [
    (b"\x00\x00\x00\x00", 0, 0x2362F9DE), (b"\xff\xff\xff\xff", 0, 0x76293B50),
    (b"\x21\x43\x65\x87", 0, 0xF55B516B), (b"\x21\x43\x65\x87", 0x5082EDEE, 0x2362F9DE),
    (b"aaaa", 0x9747B28C, 0x5A97808A),
])
def test_the_numpy_murmur_gives_the_published_vectors(data, seed, want):
    blocks = [np.frombuffer(data, dtype="<u4").astype(np.uint32)]
    assert rc.murmur3_32_blocks(blocks, seed).tolist() == [want]


@pytest.mark.parametrize("seed", [rs.SEED_H1, rs.SEED_H2])
def test_a_keys_hash_is_the_murmur_of_its_eight_bytes(seed):
    keys = np.array(KEYS, dtype=np.int64)
    got = rc.murmur3_32_blocks(rc._blocks((keys,)), seed)
    assert got.tolist() == [murmur3_x86_32(struct.pack("<q", k), seed) for k in KEYS]


def test_the_configurations_bloom_has_766848_bits_and_7_hashes():
    assert rs.bloom_params(80000, 0.01) == (766848, 7)
    assert rs.bloom_params(1000, 0.01) == (9600, 7)
    assert rs.bloom_params(1, 0.5) == (64, 16)      # at least a word, at most 16 hashes
    m, _k = rs.bloom_params(12345, 0.03)
    assert m % 64 == 0 and m >= -12345 * math.log(0.03) / math.log(2) ** 2


def test_bit_j_is_h1_plus_j_h2_mod_m_on_32_bits():
    m, k = 766848, 7
    idx = rs.bit_indices(np.array(KEYS, dtype=np.int64), m, k)
    assert idx.shape == (k, len(KEYS))
    for col, key in enumerate(KEYS):
        h1 = murmur3_x86_32(struct.pack("<q", key), rs.SEED_H1)
        h2 = murmur3_x86_32(struct.pack("<q", key), rs.SEED_H2) | 1
        assert idx[:, col].tolist() == [((h1 + j * h2) & M32) % m for j in range(k)]


def test_the_bloom_bytes_are_the_bits_little_endian():
    m, k = 128, 3
    keys = np.array([5, 9, 5], dtype=np.int64)
    blob = rs.bloom_bytes(keys, m, k)
    assert len(blob) == m // 8
    want = set(rs.bit_indices(keys, m, k).ravel().tolist())
    got = {i for i in range(m) if blob[i // 8] >> (i % 8) & 1}
    assert got == want
    assert rs.bloom_bytes(np.array([], dtype=np.int64), m, k) == bytes(m // 8)
    for key in (5, 9):
        assert rs.holds(blob, rs.bit_indices([key], m, k)[:, 0])


def _config(files, expected=64):
    return {"files_per_table": files, "index": {"sketches": [
        {"type": "MinMaxSketch", "column": "a"},
        {"type": "BloomFilterSketch", "column": "b", "fpp": 0.01, "expectedItems": expected}]}}


def test_file_sketches_and_the_files_a_pruning_keeps():
    cols = {"a": np.arange(100, 112, dtype=np.int64), "b": np.array([1, 2, 3, 4] * 3, dtype=np.int64)}
    cols["b"][7] = 99
    config = _config(3)
    assert rs.file_bounds(12, 3) == [(0, 4), (4, 8), (8, 12)]
    sk = rs.file_sketches(cols, config)
    assert sk["min"] == [100, 104, 108] and sk["max"] == [103, 107, 111]
    m, k = rs.bloom_of(config)["m"], rs.bloom_of(config)["k"]
    assert sk["bloom"][1] == rs.bloom_bytes(np.array([1, 2, 3, 99]), m, k)
    assert rs.kept_by_range(sk, 103, 104) == {0, 1}
    assert rs.kept_by_range(sk, 50, 99) == set() and rs.kept_by_range(sk, 111, 500) == {2}
    assert rs.kept_by_lookup(sk, 99, m, k) >= {1}            # where it is, and any false positive
    assert {0, 1, 2} <= rs.kept_by_lookup(sk, 3, m, k)
    assert rs.files_with_rows(cols["b"], 3, lambda v: v == 99) == {1}
    assert rs.files_with_rows(cols["a"], 3, lambda v: (v >= 103) & (v <= 104)) == {0, 1}


def test_the_kernels_bytes_are_the_source_rows_not_the_padded_ones():
    config = _config(200, expected=80000)
    assert roofline_skipping.bit_indices_bytes(16_000_000, config) == 16_000_000 * 36 == 576_000_000


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    for module in (rs, roofline_skipping):
        tree = ast.parse(inspect.getsource(module))
        imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
        imported |= {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
        assert imported <= {"__future__", "math", "numpy", "reference_composite", "reference_skipping"}
