"""Multi-host dryrun: 2 REAL processes x 4 CPU devices each.

Exercises the multi-host plane end-to-end (docs/MULTIHOST.md):

  * ``initialize_distributed`` joins both processes into one jax job
    (gloo CPU collectives — the simulation stand-in for DCN);
  * the flat data-plane mesh (``default_mesh``) spans all 8 devices and
    runs the shuffle's collective shape (shard_map all_to_all + psum)
    ACROSS the process boundary;
  * the hierarchical (dcn, ici) mesh runs the two-stage reduction
    (ici-first, then dcn) and both stages agree with the flat psum;
  * the process-local bucket shuffle (per-host feed -> twostage DCN
    exchange -> per-host owned rows) matches the canonical order;
  * a CREATE runs end to end across both processes: each host scans its
    file stripe, the exchange routes rows to their owner host, and the
    metadata plane stays single-writer (``is_coordinator`` gates the
    begin/commit log writes + latestStable publish) — ONE log entry
    pair, identical global content on both processes, zero stranded
    state.

When ``HS_COLLECTIVE_WITNESS=<prefix>`` is set, every worker wraps the
``COLLECTIVE_SITES`` registry (``testing/collective_witness.py``)
before the bootstrap and dumps its ordered collective sequence to
``<prefix>.p<i>.json``; ``hslint --witness <prefix>`` then merges the
artifacts and gates on zero cross-process divergence (the HS804 loop;
``scripts/bench_smoke.sh`` runs exactly that). The witness-coverage
matrix below is the contract the HS703 lint checks the registry
against: every registered site is either exercised here multi-process,
proven coordinator-only, or asserted to be a single-controller program
a multi-process job must never route through.

Run directly (spawns its own workers):   python scripts/dryrun_multihost.py
Run as one worker (used by the parent):  python scripts/dryrun_multihost.py --worker <pid> <port>
"""
import hashlib
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# COLLECTIVE_SITES coverage matrix (checked statically by hslint HS703
# and at runtime against the recorded witness artifact):
#: sites every process must witness during this dryrun
WITNESS_MULTIHOST_SITES = (
    "hyperspace_tpu.parallel.mesh.initialize_distributed",
    "hyperspace_tpu.parallel.shuffle._twostage_program",
    "hyperspace_tpu.parallel.shuffle._twostage_exchange_mp",
    "hyperspace_tpu.indexes.covering_build._global_written",
    "hyperspace_tpu.actions.base._action_rendezvous",
)
#: coordinator-gated sites: witnessed on process 0, NEVER elsewhere
WITNESS_COORDINATOR_SITES = (
    "hyperspace_tpu.actions.base._publish_log",
    "hyperspace_tpu.actions.base._publish_latest_stable",
)
#: single-controller device programs a multi-process job must never
#: route through (resolve_strategy coerces to twostage)
WITNESS_SINGLE_HOST_SITES = (
    "hyperspace_tpu.parallel.shuffle._compact_program",
)

N_GLOBAL_CREATE = 4000
CREATE_FILES = 4


def worker(pid: int, port: int) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO)
    import jax

    jax.config.update("jax_platforms", "cpu")

    witness_prefix = os.environ.get("HS_COLLECTIVE_WITNESS")
    if witness_prefix:
        # wrap the registered sites BEFORE the bootstrap so even
        # initialize_distributed lands in the recorded sequence
        from hyperspace_tpu.testing import collective_witness

        collective_witness.install()

    # module-attribute access (not from-imports) so the witness wrappers
    # are seen by every call below
    from hyperspace_tpu.parallel import mesh as hs_mesh

    hs_mesh.initialize_distributed(
        coordinator_address=f"localhost:{port}",
        num_processes=2,
        process_id=pid,
        cpu_local_devices=4,
    )
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    try:  # jax >= 0.6 exposes shard_map at top level
        shard_map = jax.shard_map
    except AttributeError:
        from jax.experimental.shard_map import shard_map

    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()

    DCN_AXIS, ICI_AXIS, SHARD_AXIS = (
        hs_mesh.DCN_AXIS,
        hs_mesh.ICI_AXIS,
        hs_mesh.SHARD_AXIS,
    )

    # --- flat mesh: the data-plane collective shape used by the shuffle
    mesh = hs_mesh.default_mesh()
    D = mesh.devices.size

    def exchange(a):
        # one all_to_all over the flat shard axis (the bucket shuffle's
        # collective) + a psum checksum
        b = jax.lax.all_to_all(
            a.reshape(D, -1), SHARD_AXIS, 0, 0, tiled=False
        )
        return jax.lax.psum(b.sum(), SHARD_AXIS)

    x = jax.device_put(
        jnp.arange(float(D * D)).reshape(D, D),
        NamedSharding(mesh, P(SHARD_AXIS)),
    )
    flat_total = jax.jit(
        shard_map(
            exchange, mesh=mesh, in_specs=P(SHARD_AXIS), out_specs=P()
        )
    )(x)
    flat_total = float(np.asarray(jax.device_get(flat_total)).ravel()[0])
    expect = float(np.arange(D * D).sum())
    assert flat_total == expect, (flat_total, expect)

    # --- hierarchical mesh: two-stage reduction (ici first, then dcn)
    hmesh = hs_mesh.hierarchical_mesh()

    def two_stage(a):
        local = jax.lax.psum(a.sum(), ICI_AXIS)  # within-host (ICI)
        return jax.lax.psum(local, DCN_AXIS)  # once across hosts (DCN)

    y = jax.device_put(
        jnp.arange(float(D * 4)).reshape(D, 4),
        NamedSharding(hmesh, P((DCN_AXIS, ICI_AXIS))),
    )
    hier_total = jax.jit(
        shard_map(
            two_stage,
            mesh=hmesh,
            in_specs=P((DCN_AXIS, ICI_AXIS)),
            out_specs=P(),
        )
    )(y)
    hier_total = float(np.asarray(jax.device_get(hier_total)).ravel()[0])
    assert hier_total == float(np.arange(D * 4).sum()), hier_total

    # --- process-local bucket shuffle: the exchange-strategy plane's
    # real multi-host leg (per-host feed -> twostage DCN exchange with
    # per-peer round caps -> per-host owned rows). Both workers hold the
    # same deterministic GLOBAL dataset, feed only their process-major
    # slice, and check their received rows against the host-computed
    # canonical order restricted to the buckets their devices own.
    from hyperspace_tpu.ops.hash import bucket_ids_np
    from hyperspace_tpu.parallel import shuffle as hs_shuffle

    rng = np.random.default_rng(7)
    n_global, nb = 4000, 16
    keys_g = rng.integers(0, 500, (1, n_global)).astype(np.int64)
    pay_g = rng.integers(0, 10**9, n_global).astype(np.int64)
    half = n_global // 2
    lo, hi = pid * half, (pid + 1) * half
    got_b, got_cols, got_offs = hs_shuffle.bucket_shuffle(
        mesh,
        keys_g[:, lo:hi],
        [keys_g[0, lo:hi], pay_g[lo:hi]],
        nb,
        with_shard_offsets=True,
    )
    stats = hs_shuffle.last_shuffle_stats
    assert stats["strategy"] == "twostage", stats
    assert stats.get("process_local") == 1.0, stats
    ids = bucket_ids_np(keys_g, nb)
    L = jax.local_device_count()
    order = np.lexsort((np.arange(n_global), ids, ids % D))
    mine = (ids[order] % D) // L == pid
    exp_rows = order[mine]
    np.testing.assert_array_equal(got_b, ids[exp_rows])
    np.testing.assert_array_equal(got_cols[0], keys_g[0, exp_rows])
    np.testing.assert_array_equal(got_cols[1], pay_g[exp_rows])
    per_shard = np.zeros(D, dtype=np.int64)
    counts = np.bincount(ids % D, minlength=D)
    per_shard[pid * L : (pid + 1) * L] = counts[pid * L : (pid + 1) * L]
    np.testing.assert_array_equal(
        got_offs, np.concatenate([[0], np.cumsum(per_shard)])
    )

    # --- 2-process CREATE end to end: per-host scan stripes, twostage
    # exchange, coordinator-gated metadata plane (ROADMAP item 4's
    # multi-writer gap). The parent wrote the shared dataset.
    content_hash = create_rows = ""
    root = os.environ.get("HS_DRYRUN_ROOT")
    if root:
        content_hash, create_rows = _create_end_to_end(root)

    if witness_prefix:
        from hyperspace_tpu.testing import collective_witness

        doc = collective_witness.dump(witness_prefix)
        witnessed = {r["site"] for r in doc["sequence"]}
        missing = [s for s in WITNESS_MULTIHOST_SITES if s not in witnessed]
        assert not missing, f"unwitnessed multi-host sites: {missing}"
        for site in WITNESS_COORDINATOR_SITES:
            if root:  # the CREATE drives the metadata plane
                assert (site in witnessed) == (pid == 0), (
                    site,
                    pid,
                    site in witnessed,
                )
        routed = [s for s in WITNESS_SINGLE_HOST_SITES if s in witnessed]
        assert not routed, (
            f"multi-process job routed through single-controller "
            f"programs: {routed}"
        )

    print(
        f"DRYRUN-OK proc={pid} procs={jax.process_count()} "
        f"devices={jax.device_count()} flat_psum={flat_total} "
        f"two_stage={hier_total} "
        f"exchange_rows={len(got_b)}/{n_global} "
        f"round_caps=[{stats['round_cap_min']:.0f},"
        f"{stats['round_cap_max']:.0f}] "
        f"create_content={content_hash} create_rows={create_rows}",
        flush=True,
    )


def _create_end_to_end(root: str) -> tuple:
    """Run the CREATE on both processes, assert the single-writer log
    and the global content, return (content hash, row count) for the
    parent's cross-process identity check."""
    import pyarrow.parquet as pq
    from jax.experimental import multihost_utils as mhu

    from hyperspace_tpu import (
        CoveringIndexConfig,
        Hyperspace,
        HyperspaceSession,
    )
    from hyperspace_tpu import constants as C
    from hyperspace_tpu.constants import States

    session = HyperspaceSession()
    session.conf.set(C.INDEX_SYSTEM_PATH, os.path.join(root, "indexes"))
    session.conf.set(C.INDEX_NUM_BUCKETS, 16)
    hs = Hyperspace(session)
    df = session.read.parquet(os.path.join(root, "data"))
    hs.create_index(df, CoveringIndexConfig("mh_create", ["k"], ["v"]))
    # the worker returns from op() before the coordinator publishes the
    # final entry — rendezvous before asserting the metadata plane
    mhu.sync_global_devices("dryrun_create_done")

    index_root = os.path.join(root, "indexes", "mh_create")
    log_dir = os.path.join(index_root, C.HYPERSPACE_LOG_DIR)
    ids = sorted(int(n) for n in os.listdir(log_dir) if n.isdigit())
    assert ids == [1, 2], f"expected ONE begin/commit pair, got ids {ids}"
    from hyperspace_tpu.metadata.log_manager import IndexLogManager

    log_mgr = IndexLogManager(index_root)
    assert log_mgr.get_log(1).state == States.CREATING
    final = log_mgr.get_log(2)
    assert final.state == States.ACTIVE, final.state
    assert log_mgr.get_latest_stable_pointer_id() == 2
    # zero stranded state: no spill dirs, every data file accounted for
    # in the committed content and vice versa
    strays = [n for n in os.listdir(index_root) if n.startswith("_spill_")]
    assert not strays, strays
    content_files = sorted(final.content.files)
    data_dirs = [
        os.path.join(index_root, n)
        for n in os.listdir(index_root)
        if n.startswith("v__=")
    ]
    assert len(data_dirs) == 1, data_dirs
    on_disk = sorted(
        os.path.join(data_dirs[0], n)
        for n in os.listdir(data_dirs[0])
        # hidden-path filter: sidecars (_aggsample.parquet) are not data
        if n.endswith(".parquet") and not n.startswith(("_", "."))
    )
    assert [os.path.basename(f) for f in content_files] == [
        os.path.basename(f) for f in on_disk
    ], (content_files, on_disk)
    rows = 0
    digest = hashlib.md5()
    for f in on_disk:
        meta = pq.read_metadata(f)
        rows += meta.num_rows
        digest.update(f"{os.path.basename(f)}:{meta.num_rows}\n".encode())
    assert rows == N_GLOBAL_CREATE, rows

    # a failing action must abort SYMMETRICALLY (the abort-aware
    # rendezvous), never hang: the duplicate CREATE fails validate on
    # every process with the same typed error, and leaves no new state
    from hyperspace_tpu.exceptions import HyperspaceException

    try:
        hs.create_index(df, CoveringIndexConfig("mh_create", ["k"], ["v"]))
        raise AssertionError("duplicate CREATE unexpectedly succeeded")
    except HyperspaceException:
        pass
    mhu.sync_global_devices("dryrun_dup_create_done")
    ids_after = sorted(int(n) for n in os.listdir(log_dir) if n.isdigit())
    assert ids_after == [1, 2], ids_after
    return digest.hexdigest()[:12], str(rows)


def _write_create_dataset(root: str) -> None:
    """The shared CREATE input: numeric key/payload (the supported
    multi-process build shape, docs/MULTIHOST.md), several files so each
    process scans a real stripe (``files[p::P]``)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    data_dir = os.path.join(root, "data")
    os.makedirs(data_dir)
    rng = np.random.default_rng(11)
    per = N_GLOBAL_CREATE // CREATE_FILES
    for i in range(CREATE_FILES):
        pq.write_table(
            pa.table(
                {
                    "k": pa.array(
                        rng.integers(0, 300, per), type=pa.int64()
                    ),
                    "v": pa.array(
                        rng.integers(0, 10**9, per), type=pa.int64()
                    ),
                }
            ),
            os.path.join(data_dir, f"part-{i}.parquet"),
        )


def main() -> int:
    import re
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    root = tempfile.mkdtemp(prefix="hs_dryrun_")
    try:
        _write_create_dataset(root)
        env = dict(os.environ, HS_DRYRUN_ROOT=root)
        procs = [
            subprocess.Popen(
                [
                    sys.executable,
                    os.path.abspath(__file__),
                    "--worker",
                    str(i),
                    str(port),
                ],
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                env=env,
            )
            for i in range(2)
        ]
        ok = 0
        contents = []
        for p in procs:
            out, _ = p.communicate(timeout=300)
            if p.returncode == 0 and "DRYRUN-OK" in out:
                ok += 1
            contents += re.findall(r"create_content=(\w+)", out)
            sys.stdout.write(out)
        # "identical global content": both processes listed the same
        # committed file set with the same per-file row counts
        if len(set(contents)) != 1:
            print(f"multihost dryrun: content hashes diverge: {contents}")
            return 1
        print(f"multihost dryrun: {ok}/2 workers ok")
        return 0 if ok == 2 else 1
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--worker":
        worker(int(sys.argv[2]), int(sys.argv[3]))
    else:
        raise SystemExit(main())
