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
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import hyperspace_tpu.ops  # noqa: F401  (enables x64)
from hyperspace_tpu.ops import pad_len
from hyperspace_tpu.ops.hash import _padded_words, hash_words


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


def bit_indices_np(
    key_reps: np.ndarray, m: int, k: int, acc: Optional[Dict] = None
) -> np.ndarray:
    """Host entry of :func:`_bit_indices`: int64 key reps [n] -> [k, n]
    int32 bit indices. The row dimension is padded to ``pad_len``
    (ops/__init__ shape policy) — a data-skipping build hashes one batch
    per source file, and an unpadded ``n`` would compile once per
    distinct file row count; the padded block is the hash's own
    (``ops/hash._padded_words``). ``acc``, where given, sums ``words_s``
    (the split and pad), ``device_s`` (from the upload to the fetch's return:
    dispatch, both transfers and the kernel as the host sees them) and
    the bytes each way, ``h2d_bytes`` and ``d2h_bytes``, from shapes."""
    t0 = time.perf_counter()
    n = len(key_reps)
    reps = np.asarray(key_reps, dtype=np.int64)
    words, _native = _padded_words(reps[None, :], pad_len(n))
    t1 = time.perf_counter()
    idx = np.asarray(_bit_indices(jnp.asarray(words), m, k))
    if acc is not None:
        acc["words_s"] += t1 - t0
        acc["device_s"] += time.perf_counter() - t1
        acc["h2d_bytes"] += words.nbytes
        acc["d2h_bytes"] += idx.nbytes
    return idx[:, :n]


def build_bloom(
    key_reps: np.ndarray, m: int, k: int, acc: Optional[Dict] = None
) -> np.ndarray:
    """int64 key reps [n] -> packed bit array as uint64 words [m/64].
    ``acc``, where given, sums :func:`bit_indices_np`'s and
    ``set_bits_s``, the host's setting and packing of the bits."""
    if len(key_reps) == 0:
        return np.zeros(m // 64, dtype=np.uint64)
    idx = bit_indices_np(key_reps, m, k, acc)
    t0 = time.perf_counter()
    bits = np.zeros(m, dtype=bool)
    bits[idx.ravel()] = True
    packed = np.packbits(bits, bitorder="little").view(np.uint64)
    if acc is not None:
        acc["set_bits_s"] += time.perf_counter() - t0
    return packed


def might_contain(bloom_words: np.ndarray, key_reps: np.ndarray, m: int, k: int):
    """[n] reps against one bloom -> bool [n]."""
    if len(key_reps) == 0:
        return np.zeros(0, dtype=bool)
    bits = np.unpackbits(
        bloom_words.view(np.uint8), bitorder="little", count=m
    ).astype(bool)
    return bits[bit_indices_np(key_reps, m, k)].all(axis=0)
