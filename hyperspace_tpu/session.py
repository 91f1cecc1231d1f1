"""HyperspaceSession — conf + mesh + reader + optimizer hook.

The analogue of a SparkSession *for this framework's scope*: it owns the
config (reference: Spark SQL conf, ``util/HyperspaceConf.scala``), the
device-mesh runtime (reference: the Spark cluster), source reading
(reference: ``DataFrameReader``), and the optimizer extension point where
``enable_hyperspace()`` injects the index-rewrite rule — mirroring the
implicit ``spark.enableHyperspace()`` (``package.scala:26-95``) and the
session extension (``HyperspaceSparkSessionExtension.scala:44-69``).
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional, Sequence

import pyarrow as pa
import pyarrow.parquet as pq

from hyperspace_tpu.config import Config
from hyperspace_tpu.dataframe import DataFrame
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.parallel.mesh import MeshRuntime
from hyperspace_tpu.plan.nodes import Relation, Scan
from hyperspace_tpu.telemetry import EventLogging


class DataFrameReader:
    """``session.read.parquet(path)`` etc. — builds a Scan over a file
    snapshot (listing happens here, like Spark's ``InMemoryFileIndex``)."""

    def __init__(self, session: "HyperspaceSession"):
        self._session = session

    def _scan(self, fmt: str, paths: Sequence[str]) -> DataFrame:
        from hyperspace_tpu.io.parquet import expand_path, read_table

        files: List[str] = []
        for p in paths:
            files.extend(expand_path(p, fmt))
        if not files:
            raise HyperspaceException(f"No {fmt} files under {list(paths)}")
        if fmt == "parquet":
            schema = pq.read_schema(files[0])
            fields = tuple((f.name, f.type) for f in schema)
        else:
            head = read_table(files[:1], None, fmt)
            fields = tuple((n, head.schema.field(n).type) for n in head.column_names)
        # struct columns surface as flat __hs_nested.<path> leaf columns
        # (the engine's data plane is SoA; see io/columnar.py)
        from hyperspace_tpu.io.columnar import flatten_schema_fields

        fields = flatten_schema_fields(fields)
        # glob patterns stay patterns in root_paths (re-expanded on every
        # refresh/signature listing) — but absolutized like plain paths,
        # or re-expansion would depend on the process cwd
        rel = Relation(
            root_paths=tuple(os.path.abspath(p) for p in paths),
            files=tuple(os.path.abspath(f) for f in files),
            fmt=fmt,
            schema_fields=fields,
        )
        return DataFrame(self._session, Scan(rel))

    def parquet(self, *paths: str) -> DataFrame:
        return self._scan("parquet", paths)

    def csv(self, *paths: str) -> DataFrame:
        return self._scan("csv", paths)

    def json(self, *paths: str) -> DataFrame:
        return self._scan("json", paths)

    def orc(self, *paths: str) -> DataFrame:
        return self._scan("orc", paths)

    def avro(self, *paths: str) -> DataFrame:
        return self._scan("avro", paths)

    def text(self, *paths: str) -> DataFrame:
        return self._scan("text", paths)

    def delta(self, path: str, version_as_of: Optional[int] = None) -> DataFrame:
        """Read a Delta Lake table (optionally pinned to a version — the
        reference records ``versionAsOf`` for time travel,
        DeltaLakeRelation.scala:96-99)."""
        from hyperspace_tpu.sources import delta_log

        snap = delta_log.read_snapshot(path, version_as_of)
        options = [("deltaVersion", str(snap.version))]
        if version_as_of is not None:
            options.append(("versionAsOf", str(version_as_of)))
        from hyperspace_tpu.io.columnar import flatten_schema_fields

        rel = Relation(
            root_paths=(os.path.abspath(path),),
            files=tuple(snap.file_paths),
            fmt="delta",
            schema_fields=flatten_schema_fields(snap.schema_fields),
            options=tuple(options),
        )
        return DataFrame(self._session, Scan(rel))

    def iceberg(self, path: str, snapshot_id: Optional[int] = None) -> DataFrame:
        """Read an Iceberg table (optionally pinned to a snapshot — the
        reference pins scans to snapshot ids, IcebergRelation.scala:222-223)."""
        from hyperspace_tpu.sources import iceberg_meta

        snap = iceberg_meta.read_snapshot(path, snapshot_id)
        options = [("snapshotId", str(snap.snapshot_id))]
        if snapshot_id is not None:
            options.append(("snapshotAsOf", str(snapshot_id)))
        from hyperspace_tpu.io.columnar import flatten_schema_fields

        rel = Relation(
            root_paths=(os.path.abspath(path),),
            files=tuple(snap.file_paths),
            fmt="iceberg",
            schema_fields=flatten_schema_fields(snap.schema_fields),
            options=tuple(options),
        )
        return DataFrame(self._session, Scan(rel))


class HyperspaceSession:
    def __init__(self, devices: Optional[Sequence] = None):
        self.conf = Config()
        self.runtime = MeshRuntime(devices)
        self.event_logging = EventLogging(self.conf)
        self._hyperspace_enabled = False
        self._source_manager = None
        self._index_manager = None
        self._serve_cache = None
        self._serve_cache_lock = threading.Lock()
        self._serve_frontend = None
        self._serve_frontend_lock = threading.Lock()
        self._catalog: dict = {}
        # Pre-warm the native host kernels off-thread: the one-time g++
        # compile (~2s, cached per machine) then lands during session
        # setup instead of inside the first large sort or join; hot paths
        # use load(wait=False) and fall back to numpy until it finishes.
        # Host work only: this thread never touches JAX. The dispatch-
        # calibration probe (native/calibrate.py) runs device programs —
        # collectives, on a mesh — so it runs on the thread of the first
        # dispatch that asks for thresholds(), in sequence with that
        # thread's own device work, not here beside it (and no daemon
        # thread can be inside an XLA compile when the interpreter exits).
        from hyperspace_tpu import native

        threading.Thread(
            target=native.load, name="hs-native-warm", daemon=True
        ).start()

    # -- context (HyperspaceContext, Hyperspace.scala:195-223) --------------
    @property
    def source_manager(self):
        if self._source_manager is None:
            from hyperspace_tpu.sources.manager import SourceProviderManager

            self._source_manager = SourceProviderManager(self)
        return self._source_manager

    @property
    def index_manager(self):
        if self._index_manager is None:
            from hyperspace_tpu.manager import CachingIndexCollectionManager

            self._index_manager = CachingIndexCollectionManager(self)
        return self._index_manager

    @property
    def serve_cache(self):
        """The serve-server data cache (``execution/serve_cache.py``) when
        ``hyperspace.serve.cache.enabled`` is on, else None. Stale entries
        are impossible (keys fingerprint the immutable index file set);
        ``clear_serve_cache()`` just frees the memory."""
        if not self.conf.serve_cache_enabled:
            return None
        max_bytes = self.conf.serve_cache_max_bytes
        spill_max_bytes = self.conf.serve_spill_max_bytes
        with self._serve_cache_lock:
            if (
                self._serve_cache is None
                or self._serve_cache.max_bytes != max_bytes
                or self._serve_cache.spill_max_bytes != spill_max_bytes
            ):
                from hyperspace_tpu.execution.serve_cache import (
                    ServeCache,
                    spill_root,
                )

                self._serve_cache = ServeCache(
                    max_bytes,
                    spill_dir=(
                        spill_root(self.conf) if spill_max_bytes > 0 else None
                    ),
                    spill_max_bytes=spill_max_bytes,
                )
            return self._serve_cache

    def clear_serve_cache(self) -> None:
        if self._serve_cache is not None:
            self._serve_cache.clear()

    @property
    def serve_frontend(self):
        """The session's long-lived concurrent serve frontend
        (``serve/frontend.py``): admission control, snapshot-consistent
        pinning, retry/degrade. With ``hyperspace.fleet.enabled`` it is
        a :class:`~hyperspace_tpu.serve.fleet.FleetFrontend` — the same
        surface plus durable cross-process pins, fanout-bus
        subscription and cross-process single-flight
        (docs/fleet-serve.md). Created lazily; pool size, SLO classes
        and the fleet flag are read at first touch (construct a
        frontend directly for a differently-configured or short-lived
        one). A closed — or mode-mismatched, after a fleet-flag flip —
        frontend is discarded and replaced on the next touch;
        ``close()`` must not brick serving on the session forever."""
        with self._serve_frontend_lock:
            from hyperspace_tpu.serve import ServeFrontend

            fe = self._serve_frontend
            if self.conf.fleet_enabled:
                from hyperspace_tpu.serve.fleet import FleetFrontend

                if fe is None or fe.closed or not isinstance(fe, FleetFrontend):
                    if fe is not None and not fe.closed:
                        fe.close(wait=False)
                    self._serve_frontend = FleetFrontend(self)
            elif fe is None or fe.closed or type(fe) is not ServeFrontend:
                if fe is not None and not fe.closed:
                    fe.close(wait=False)
                self._serve_frontend = ServeFrontend(self)
            return self._serve_frontend

    # -- reading ------------------------------------------------------------
    @property
    def read(self) -> DataFrameReader:
        return DataFrameReader(self)

    # -- SQL surface (HyperspaceSparkSessionExtension.scala:44-69 analogue:
    # SQL flows through the same optimizer, so index rewrites apply) ------
    def register_view(self, name: str, df: DataFrame) -> None:
        self._catalog[name.lower()] = df

    def sql(self, query: str) -> DataFrame:
        from hyperspace_tpu.sql import parse_sql

        return parse_sql(self, query, self._catalog)

    # -- hyperspace enable/disable (package.scala:40-80) --------------------
    def enable_hyperspace(self) -> "HyperspaceSession":
        self._hyperspace_enabled = True
        return self

    def disable_hyperspace(self) -> "HyperspaceSession":
        self._hyperspace_enabled = False
        return self

    def is_hyperspace_enabled(self) -> bool:
        return self._hyperspace_enabled

    # -- planning & execution ----------------------------------------------
    def optimize(self, plan):
        """Apply the Hyperspace rewrite when enabled (the injected-rule
        equivalent of ``ApplyHyperspace``, rules/ApplyHyperspace.scala:45-66)."""
        if self._hyperspace_enabled and self.conf.apply_enabled:
            from hyperspace_tpu.rules.apply import apply_hyperspace

            return apply_hyperspace(self, plan)
        return plan

    def execute(self, plan) -> pa.Table:
        from hyperspace_tpu.execution import execute

        trace_dir = self.conf.profile_trace_dir
        if trace_dir:
            # XLA profiler integration (SURVEY §5): device kernels, host
            # callbacks and transfers land in a TensorBoard/Perfetto trace
            import jax

            with jax.profiler.trace(trace_dir):
                return execute(self.optimize(plan), self)
        return execute(self.optimize(plan), self)
