"""The exchange's way back from the chips, timed alone (ISSUE 33, step 4).

Five ``int64[D*D, cap]`` planes — the ``compact`` exchange's outputs in
the ``tpch-mesh-build`` cell at ``cap`` 1,048,576 on four chips — are
sent through ``shuffle._compact_program`` and fetched several ways, each
from fresh outputs, the ways taking turns:

``a``  one output after another, ``np.asarray(o)`` (the fetch before PR 33)
``b``  every output's ``copy_to_host_async()`` first, then ``np.asarray(o)``
``c``  every copy started first, then each shard read in place
       (``np.asarray(shard.data)``: ``shuffle._fetch_shards``)
``c0`` the shards read in place, no copy started beforehand
``ct`` as ``c``, the shards read by a pool of threads
``u``  as ``c``, the planes sent as their ``uint32`` views ``[D*D, 2*cap]``
       and the shards viewed back as int64

Prints one JSON line: seconds of every repeat, the medians, the bytes,
and the device. Numbers from a CPU run are no device metric.

Usage: python scripts/measure_exchange_fetch.py [--cap 1048576] [--planes 5] [--repeats 5]
"""

import argparse
import json
import os
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cap", type=int, default=1 << 20)
    ap.add_argument("--planes", type=int, default=5)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()

    import jax
    import numpy as np

    from hyperspace_tpu.parallel import shuffle as sh
    from hyperspace_tpu.parallel.mesh import put_sharded

    devices = jax.devices()
    D = len(devices)
    mesh = jax.sharding.Mesh(np.array(devices), (sh.SHARD_AXIS,))
    rng = np.random.default_rng(33)
    sends = [
        rng.integers(-(2**62), 2**62, (D * D, args.cap)).astype(np.int64)
        for _ in range(args.planes)
    ]
    # what slot (o, s) of the output must hold: slot (s, o) of the input
    want = [
        s.reshape(D, D, args.cap).transpose(1, 0, 2).reshape(D * D, args.cap)
        for s in sends
    ]

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return time.perf_counter() - t0, out

    h2d_s, ops64 = timed(lambda: jax.block_until_ready(
        tuple(put_sharded(mesh, s) for s in sends)))
    h2d_u32_s, ops32 = timed(lambda: jax.block_until_ready(
        tuple(put_sharded(mesh, s.view(np.uint32)) for s in sends)))

    def outputs(ops):
        return jax.block_until_ready(sh._compact_program(mesh, ops))

    def shards_of(o):
        return sorted(o.addressable_shards, key=lambda s: s.index[0].start or 0)

    def start_all(out):
        for o in out:
            o.copy_to_host_async()

    def fetch_a(out):
        return [np.asarray(o).reshape(-1) for o in out]

    def fetch_b(out):
        start_all(out)
        return fetch_a(out)

    def fetch_c0(out):
        return [[np.asarray(s.data) for s in shards_of(o)] for o in out]

    def fetch_c(out):
        return sh._fetch_shards(out)[0]

    def fetch_ct(out):
        start_all(out)
        with ThreadPoolExecutor(max_workers=len(out)) as pool:
            return list(pool.map(
                lambda o: [np.asarray(s.data) for s in shards_of(o)], out))

    def fetch_u(out):
        return [[x.view(np.int64) for x in shards] for shards in fetch_c(out)]

    ways = {
        "a": (fetch_a, ops64), "b": (fetch_b, ops64), "c": (fetch_c, ops64),
        "c0": (fetch_c0, ops64), "ct": (fetch_ct, ops64), "u": (fetch_u, ops32),
    }
    outputs(ops64), outputs(ops32)  # compile both shapes
    seconds = {k: [] for k in ways}
    kernel_s = []
    checked = set()
    for rep in range(args.repeats):
        names = list(ways)
        names = names[rep % len(names):] + names[:rep % len(names)]
        for name in names:
            fetch, ops = ways[name]
            k_s, out = timed(lambda: outputs(ops))
            kernel_s.append(k_s)
            s, got = timed(lambda: fetch(out))
            seconds[name].append(s)
            if name not in checked:  # every way returns the same elements
                checked.add(name)
                for g, w in zip(got, want):
                    flat = g if isinstance(g, np.ndarray) else np.concatenate(
                        [x.reshape(-1) for x in g])
                    assert flat.dtype == np.int64, (name, flat.dtype)
                    assert np.array_equal(flat.reshape(w.shape), w), name
            del out, got
    dev = devices[0]
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": D},
        "bytes": int(sum(s.nbytes for s in sends)),
        "cap": args.cap, "planes": args.planes,
        "h2d_s": h2d_s, "h2d_uint32_s": h2d_u32_s,
        "kernel_s_median": statistics.median(kernel_s),
        "seconds": seconds,
        "median_s": {k: statistics.median(v) for k, v in seconds.items()},
    }))


if __name__ == "__main__":
    main()
