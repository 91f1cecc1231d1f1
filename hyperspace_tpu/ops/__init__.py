"""Device (XLA) kernels for the index data plane.

Everything in this package is jit-compilable JAX: bucket hashing
(:mod:`hyperspace_tpu.ops.hash`), packed-key sorting
(:mod:`hyperspace_tpu.ops.sort`), z-address bit interleaving
(:mod:`hyperspace_tpu.ops.zorder`) and bloom-filter build/probe
(:mod:`hyperspace_tpu.ops.bloom`). These replace the row-pipeline work that
the reference leaves to Spark executors (hash partitioning, sort-within-
bucket, sketch aggregation).

Dtype policy: hot kernels (hash, sort keys, z-address) run on 32-bit words
— TPU VPUs are 32-bit and int64 is emulated — so int64 key reps are split
into (lo, hi) uint32 planes at the host boundary. x64 is still enabled
globally because payload columns (int64 values, file ids) must round-trip
through device exchanges losslessly.

Shape policy: every host kernel entry point pads its row dimension up to
the next power of two before dispatch (:func:`pad_len`): bucket hashing
(``hash.bucket_ids_np``), all sort paths (``sort.lexsort_perm``, used by
``sort_permutation``/``ordering_permutation``/``zorder``), predicate
evaluation (``filter.device_filter_mask``), the per-bucket join width
(``execution/join_exec.side_arrays``) and the shuffle row dimension
(``parallel/shuffle.bucket_shuffle``). Under jit each distinct input shape
is a fresh XLA compile — on TPU a large sort alone costs tens of seconds
of compile — so row counts must never leak into compiled shapes. Padding
buys an O(log n)-sized shape universe: any two datasets within a 2x size
band share every kernel binary. Combined with the persistent compilation
cache (below), steady-state builds and queries never recompile.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache. TPU sort kernels take 40-80s to
# compile while executing in milliseconds; caching them on disk makes every
# process after the first pay only dispatch cost. The cache lives where
# JAX_COMPILATION_CACHE_DIR says (jax reads that variable into
# ``jax_compilation_cache_dir`` itself — nothing is set here, so the
# directory is exactly the one given). Unset, it is a fixed directory in
# the checkout: the path is part of what makes a cache reusable, so it is
# never derived from a temp name, a pid or the home directory. No
# per-machine subdirectory: jax's own cache key hashes the backend
# topology, which for XLA:CPU carries the host's CPU feature list, so an
# entry compiled for other features is never looked up.
JAX_CACHE_DIR_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR_DEFAULT)
if jax.config.jax_platforms != "cpu":
    # Toward an accelerator every compile is kept, however short: a second
    # process over the same directory then compiles nothing. A process
    # held to the CPU (tests, fleet workers) keeps jax's own threshold —
    # XLA:CPU compiles of these programs are sub-second, and jax 0.9.0
    # logs two multi-KB "machine feature" lines per CPU entry it loads.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def pad_len(n: int, minimum: int = 8) -> int:
    """Next power of two >= max(n, minimum) — the padded row count every
    kernel dispatches at (see module docstring)."""
    n = max(n, minimum)
    return 1 << (n - 1).bit_length()
