"""Bloom filter build/probe kernels.

Reference: the data-skipping Catalyst expression toolkit —
``dataskipping/expressions/BloomFilterAgg.scala`` (per-file bloom
aggregation) and ``BloomFilterMightContain(Any).scala`` (probe). Here both
sides are double-hashing over the murmur3 word kernel (``ops/hash.py``):
bit index j = (h1 + j·h2) mod m, the standard Kirsch-Mitzenmacher scheme.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

import hyperspace_tpu.ops  # noqa: F401  (enables x64)
from hyperspace_tpu.ops import pad_len
from hyperspace_tpu.ops.hash import hash_words, split_words_np


def optimal_params(expected_items: int, fpp: float) -> Tuple[int, int]:
    """(num_bits m, num_hashes k) for a target false-positive rate."""
    expected_items = max(1, expected_items)
    m = max(64, int(-expected_items * math.log(fpp) / (math.log(2) ** 2)))
    m = ((m + 63) // 64) * 64  # word-align
    k = max(1, round(m / expected_items * math.log(2)))
    return m, min(k, 16)


@functools.partial(jax.jit, static_argnames=("m", "k"))
def _bit_indices(words, m: int, k: int):
    """[2, n] uint32 key words -> [k, n] int32 bit indices."""
    h1 = hash_words(words, 0x9747B28C)
    h2 = hash_words(words, 0x85EBCA6B) | jnp.uint32(1)  # odd => full cycle
    idx = []
    for j in range(k):
        idx.append(((h1 + jnp.uint32(j) * h2) % jnp.uint32(m)).astype(jnp.int32))
    return jnp.stack(idx)


def bit_indices_np(key_reps: np.ndarray, m: int, k: int) -> np.ndarray:
    """Host entry of :func:`_bit_indices`: int64 key reps [n] -> [k, n]
    int32 bit indices. The row dimension is padded to ``pad_len``
    (ops/__init__ shape policy) — a data-skipping build hashes one batch
    per source file, and an unpadded ``n`` would compile once per
    distinct file row count."""
    n = len(key_reps)
    words = split_words_np(np.asarray(key_reps, dtype=np.int64)[None, :])
    n_pad = pad_len(n)
    if n_pad != n:
        words = np.concatenate(
            [words, np.zeros((2, n_pad - n), dtype=np.uint32)], axis=1
        )
    return np.asarray(_bit_indices(jnp.asarray(words), m, k))[:, :n]


def build_bloom(key_reps: np.ndarray, m: int, k: int) -> np.ndarray:
    """int64 key reps [n] -> packed bit array as uint64 words [m/64]."""
    if len(key_reps) == 0:
        return np.zeros(m // 64, dtype=np.uint64)
    idx = bit_indices_np(key_reps, m, k).ravel()
    bits = np.zeros(m, dtype=bool)
    bits[idx] = True
    return np.packbits(bits, bitorder="little").view(np.uint64)


def might_contain(bloom_words: np.ndarray, key_reps: np.ndarray, m: int, k: int):
    """[n] reps against one bloom -> bool [n]."""
    if len(key_reps) == 0:
        return np.zeros(0, dtype=bool)
    bits = np.unpackbits(
        bloom_words.view(np.uint8), bitorder="little", count=m
    ).astype(bool)
    return bits[bit_indices_np(key_reps, m, k)].all(axis=0)
