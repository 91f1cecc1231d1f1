"""The plain reference of a data-skipping index: numpy over the generated
columns.

Imports nothing of ``hyperspace_tpu`` and reads nothing the program
wrote; the murmur is ``reference_composite``'s. The configuration
``tpch-skip-1chip`` keeps one sketch row a source file — the min and max
of one column, a bloom filter of another — and this file works out on
its own what each row has to hold and which files a query may skip:

``bloom_params``    (m bits, k hashes) from the expected items and the
                    false-positive rate: m = -n ln(fpp) / ln(2)^2 rounded
                    down, then up to a whole 64-bit word; k = m/n ln(2)
                    rounded, at least 1 and at most 16.
``bit_indices``     a key's k bits: h1 and h2 are MurmurHash3_x86_32 of
                    its eight little-endian bytes (two 4-byte blocks, low
                    word first) under seeds 0x9747B28C and 0x85EBCA6B, h2
                    made odd; bit j = (h1 + j * h2 mod 2^32) mod m.
``bloom_bytes``     a file's bits, packed eight to a byte, bit i of the
                    filter in byte i // 8 at place i % 8 (little-endian).
``file_sketches``   per file of the table as ``datagen`` lays it (file i
                    holds rows [i n / F, (i + 1) n / F)): min, max, bloom.
``kept_by_lookup``  the files a correct pruning keeps for ``column = p``:
``kept_by_range``   those whose bloom holds all of p's bits (false
                    positives included, as the filter has them), or whose
                    [min, max] meets [a, b].
"""

from __future__ import annotations

import math

import numpy as np

from reference_composite import _blocks, murmur3_32_blocks

SEED_H1 = 0x9747B28C
SEED_H2 = 0x85EBCA6B


def bloom_params(expected_items: int, fpp: float) -> tuple:
    n = max(1, int(expected_items))
    m = max(64, int(-n * math.log(fpp) / math.log(2) ** 2))
    m = (m + 63) // 64 * 64
    return m, min(max(1, round(m / n * math.log(2))), 16)


def bloom_of(config: dict) -> dict:
    """The configuration's bloom sketch, with its (m, k)."""
    spec = next(s for s in config["index"]["sketches"] if s["type"] == "BloomFilterSketch")
    m, k = bloom_params(spec["expectedItems"], spec["fpp"])
    return dict(spec, m=m, k=k)


def minmax_of(config: dict) -> dict:
    return next(s for s in config["index"]["sketches"] if s["type"] == "MinMaxSketch")


def bit_indices(keys, m: int, k: int) -> np.ndarray:
    """int64 keys [n] -> [k, n] bit indices, int64."""
    blocks = _blocks((np.asarray(keys, dtype=np.int64),))
    h1 = murmur3_32_blocks(blocks, SEED_H1)
    h2 = murmur3_32_blocks(blocks, SEED_H2) | np.uint32(1)
    with np.errstate(over="ignore"):
        return np.stack([((h1 + np.uint32(j) * h2) % np.uint32(m)).astype(np.int64)
                         for j in range(k)])


def bloom_bytes(keys, m: int, k: int) -> bytes:
    bits = np.zeros(m, dtype=bool)
    if len(keys):
        bits[bit_indices(keys, m, k).ravel()] = True
    return np.packbits(bits, bitorder="little").tobytes()


def holds(bloom: bytes, idx: np.ndarray) -> bool:
    """All of one key's bits (``bit_indices(..)[:, j]``) are set in ``bloom``."""
    byte = np.frombuffer(bloom, dtype=np.uint8)[idx >> 3]
    return bool(np.all((byte >> (idx & 7).astype(np.uint8)) & 1))


def file_bounds(rows: int, n_files: int) -> list:
    """[(first row, row past the last)] of each file, as ``datagen`` writes them."""
    return [(i * rows // n_files, (i + 1) * rows // n_files) for i in range(n_files)]


def file_sketches(cols: dict, config: dict) -> dict:
    """-> {"min": [..], "max": [..], "bloom": [bytes, ..]}, one entry a
    file, in the order of ``file_bounds``."""
    mm, bf = minmax_of(config), bloom_of(config)
    ranged, hashed = cols[mm["column"]], cols[bf["column"]]
    out = {"min": [], "max": [], "bloom": []}
    for lo, hi in file_bounds(len(ranged), int(config["files_per_table"])):
        out["min"].append(int(ranged[lo:hi].min()))
        out["max"].append(int(ranged[lo:hi].max()))
        out["bloom"].append(bloom_bytes(hashed[lo:hi], bf["m"], bf["k"]))
    return out


def kept_by_lookup(sketches: dict, key: int, m: int, k: int) -> set:
    idx = bit_indices([key], m, k)[:, 0]
    return {i for i, b in enumerate(sketches["bloom"]) if holds(b, idx)}


def kept_by_range(sketches: dict, a: int, b: int) -> set:
    lo, hi = np.asarray(sketches["min"]), np.asarray(sketches["max"])
    return set(np.flatnonzero((hi >= a) & (lo <= b)).tolist())


def files_with_rows(column, n_files: int, match) -> set:
    """The files that hold a row of the predicate: ``match(values)`` -> bool mask."""
    return {i for i, (lo, hi) in enumerate(file_bounds(len(column), n_files))
            if np.any(match(column[lo:hi]))}
