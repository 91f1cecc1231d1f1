"""Drive one run of a mesh cell with the exchange broken underneath:
``python mesh_fault_runner.py <fault> <run.py arguments>``.

Faults (each breaks the program, never the benchmark), planted where the
build calls the exchange (``parallel/shuffle.bucket_shuffle``):
  peer_rows_dropped   rows whose owner is another chip than the one that
                      held them vanish at the exchange
  peers_swapped       the blocks that chips 1 and 2 received change
                      places; the host's bucket ids stay as they were
  none                nothing broken

With ``--cpu-rehearsal --rows N`` among the arguments it runs here at a
tiny size, and has ``shuffle.resolve_strategy`` answer ``compact``
(``auto`` on a CPU mesh is ``host``, which crosses no chip); without
them, on the chips at the cell's own size, under ``auto``.
"""

import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

SWAPPED = (1, 2)


def _exchange_then(after):
    """Put ``after(chips, buckets, cols, offsets, source) -> (buckets,
    cols, offsets)`` behind the program's exchange. ``source`` is the
    chip that held each exchanged row before: it rides the exchange as
    one more payload and is taken off again."""
    from hyperspace_tpu.parallel import shuffle

    real = shuffle.bucket_shuffle

    def broken(mesh, key_reps, payloads, num_buckets, **kw):
        chips, n = int(mesh.devices.size), key_reps.shape[1]
        source = (np.arange(n, dtype=np.int64) // max(-(-n // chips), 1)).astype(np.int32)
        want_offsets = kw.pop("with_shard_offsets", False)
        buckets, cols, offsets = real(mesh, key_reps, list(payloads) + [source],
                                      num_buckets, with_shard_offsets=True, **kw)
        buckets, cols, offsets = after(chips, buckets, cols[:-1], offsets, cols[-1])
        return (buckets, cols, offsets) if want_offsets else (buckets, cols)

    shuffle.bucket_shuffle = broken


def _drop_peer_rows(chips, buckets, cols, offsets, source):
    keep = source == buckets % chips
    per_owner = np.bincount(buckets[keep] % chips, minlength=chips)
    offsets = np.concatenate([[0], np.cumsum(per_owner)]).astype(np.int64)
    return buckets[keep], [c[keep] for c in cols], offsets


def _swap_peers(chips, buckets, cols, offsets, source):
    a, b = SWAPPED
    block = [np.arange(offsets[s], offsets[s + 1]) for s in range(chips)]
    block[a], block[b] = block[b], block[a]
    moved = np.concatenate(block)
    return buckets, [c[moved] for c in cols], offsets


def plant(fault: str, force_compact: bool) -> None:
    if force_compact:
        from hyperspace_tpu.parallel import shuffle

        shuffle.resolve_strategy = lambda strategy, mesh: shuffle.STRATEGY_COMPACT
    if fault == "peer_rows_dropped":
        _exchange_then(_drop_peer_rows)
    elif fault == "peers_swapped":
        _exchange_then(_swap_peers)
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault, argv = sys.argv[1], sys.argv[2:]
    import run

    # run.main sets the platform before jax is imported; the fault is
    # planted by the harness's first step after that
    import harness

    real_device_info = harness.device_info

    def device_info_then_plant():
        info = real_device_info()
        plant(fault, force_compact="--cpu-rehearsal" in argv)
        return info

    harness.device_info = device_info_then_plant
    sys.exit(run.main(argv))
