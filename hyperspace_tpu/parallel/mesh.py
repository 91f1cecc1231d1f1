"""Session device mesh + multi-host bootstrap.

The reference's execution substrate is a Spark cluster (driver +
executors); ours is a 1-D ``jax.sharding.Mesh`` over all addressable
devices — the "executors" are mesh shards, the host Python process is the
driver. Multi-host scaling is the same code: after
:func:`initialize_distributed`, ``jax.devices()`` spans every host
(process-major order, so consecutive mesh positions are ICI neighbors
within a host's chips) and the same ``shard_map`` collectives ride ICI
within a slice and DCN across hosts. The DCN-aware layout and the
collective plan for a v5e-64 are documented in ``docs/MULTIHOST.md``;
``scripts/dryrun_multihost.py`` exercises this bootstrap as 2 real
processes x 4 CPU devices.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

SHARD_AXIS = "shard"
# hierarchical mesh axes: DCN (cross-host) outer, ICI (intra-host) inner
DCN_AXIS = "dcn"
ICI_AXIS = "ici"

_DISTRIBUTED_INITIALIZED = False

# Held while a multi-device program is ENQUEUED (not while it runs). A
# program spanning several devices is launched device by device; two host
# threads launching two such programs at once (serve workers running the
# sharded join, a build's exchange, the calibration probe's exchanges) can
# enqueue them in different orders on different devices, and collectives
# that wait for each other in crossed order never finish. One lock makes
# every device see one launch order. Single-device programs need none.
mesh_dispatch_lock = threading.Lock()


def put_sharded(mesh: jax.sharding.Mesh, array, spec=None):
    """Host array -> device array split over the leading axis of ``mesh``
    (or by ``spec``), each shard transferred from the host to its own
    device. ``jnp.asarray`` would instead put the whole array on device 0
    and leave the program to reshard it — a first-chip pile-up of every
    operand at build sizes."""
    if spec is None:
        spec = PartitionSpec(mesh.axis_names[0])
    return jax.device_put(array, NamedSharding(mesh, spec))


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    cpu_local_devices: Optional[int] = None,
) -> None:
    """Join a multi-host job (idempotent). Call BEFORE creating a
    HyperspaceSession on every process.

    On TPU pods the three job parameters come from the runtime
    environment and may be omitted (``jax.distributed.initialize()``
    auto-detects). On CPU — the simulation used by tests and the
    multi-host dryrun — the coordination service needs them explicitly,
    plus the gloo cross-process collectives backend and a forced local
    device count (``cpu_local_devices``).

    Registered in ``COLLECTIVE_SITES`` (``parallel/collectives.py``):
    the bootstrap is itself part of the collective program the HS8xx
    sanitizer and the runtime collective witness check.
    """
    global _DISTRIBUTED_INITIALIZED
    if _DISTRIBUTED_INITIALIZED:
        return
    explicit = (coordinator_address, num_processes, process_id)
    if any(v is not None for v in explicit) and any(
        v is None for v in explicit
    ):
        raise ValueError(
            "initialize_distributed needs coordinator_address, "
            "num_processes AND process_id together (explicit job), or "
            f"none of them (auto-detected TPU pod); got {explicit}"
        )
    if cpu_local_devices is not None:
        jax.config.update("jax_num_cpu_devices", int(cpu_local_devices))
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    jax.distributed.initialize(**kwargs)
    _DISTRIBUTED_INITIALIZED = True


def bucket_owner_groups(
    bucket_ids: Sequence[int], num_shards: int, min_tasks: int = 1
):
    """Index groups of ``bucket_ids`` by owner shard — THE bucket
    ownership layout (``bucket % num_shards``, the same routing the
    build shuffle uses), shared by the sharded build/serve tails so the
    mapping lives in one place. Returns a list of position lists, one
    per occupied shard, ascending shard id.

    ``min_tasks`` splits large groups WITHIN a shard (chunks never cross
    an ownership boundary) until at least that many task units exist —
    a 2-shard mesh must not cap a thread fan-out below the caller's
    worker budget when there are buckets to spare. Callers always
    collect results per bucket position, so any grouping yields
    identical output; only scheduling changes."""
    groups: dict = {}
    for i, b in enumerate(bucket_ids):
        groups.setdefault(int(b) % num_shards, []).append(i)
    ordered = [groups[s] for s in sorted(groups)]
    if min_tasks <= len(ordered):
        return ordered
    chunks_per = -(-min_tasks // len(ordered))  # ceil
    out = []
    for g in ordered:
        size = -(-len(g) // chunks_per)
        out.extend(g[i : i + size] for i in range(0, len(g), size))
    return out


def default_mesh(devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """The flat data-plane mesh: ONE shard axis over every addressable
    device. ``jax.devices()`` is process-major, so the axis is
    ICI-contiguous per host and XLA routes the shuffle's ``all_to_all``
    over ICI within a host and DCN across hosts."""
    devs = list(devices) if devices is not None else jax.devices()
    return jax.sharding.Mesh(np.array(devs), (SHARD_AXIS,))


def hierarchical_mesh() -> jax.sharding.Mesh:
    """The (dcn, ici) 2-D mesh over all hosts: outer axis = process,
    inner axis = that process's local devices. The layout for
    DCN-minimizing two-stage collectives (docs/MULTIHOST.md): reduce or
    exchange over ``ici`` first (fast, within-host), then once over
    ``dcn``."""
    procs = jax.process_count()
    local = jax.local_device_count()
    devs = np.array(jax.devices()).reshape(procs, local)
    return jax.sharding.Mesh(devs, (DCN_AXIS, ICI_AXIS))


class MeshRuntime:
    """Lazily-built mesh owned by a session (one per HyperspaceSession)."""

    def __init__(self, devices: Optional[Sequence] = None):
        self._devices = devices
        self._mesh: Optional[jax.sharding.Mesh] = None

    @property
    def mesh(self) -> jax.sharding.Mesh:
        if self._mesh is None:
            self._mesh = default_mesh(self._devices)
        return self._mesh

    @property
    def num_shards(self) -> int:
        return self.mesh.devices.size

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def is_coordinator(self) -> bool:
        """Process 0 owns the metadata plane (action protocol, log OCC
        writes) on a multi-host job — the driver role of the reference's
        Spark driver (SURVEY §2.11 driver/executor row)."""
        return jax.process_index() == 0
