"""Config keys, index states and reserved property names.

Reference: ``index/IndexConstants.scala:21-170`` and
``actions/Constants.scala:20-34``. Keys drop the ``spark.`` prefix — this
framework owns its own config system (see :mod:`hyperspace_tpu.config`).

Contract (machine-checked by hslint HS7xx, ``analysis/contracts.py``):
every ``hyperspace.*`` key the package reads has its ``<NAME>_DEFAULT``
sibling here and a row in ``docs/CONFIG.md``; keys nothing reads are
flagged as dead.
"""

import os

# ---------------------------------------------------------------------------
# Index lifecycle states (actions/Constants.scala:20-34)
# ---------------------------------------------------------------------------


class States:
    DOESNOTEXIST = "DOESNOTEXIST"
    CREATING = "CREATING"
    ACTIVE = "ACTIVE"
    REFRESHING = "REFRESHING"
    OPTIMIZING = "OPTIMIZING"
    DELETING = "DELETING"
    DELETED = "DELETED"
    RESTORING = "RESTORING"
    VACUUMING = "VACUUMING"
    VACUUMINGOUTDATED = "VACUUMINGOUTDATED"

    STABLE_STATES = frozenset({ACTIVE, DELETED, DOESNOTEXIST})

    # transient state -> stable state it rolls back to on cancel()
    ROLLBACK = {
        CREATING: DOESNOTEXIST,
        REFRESHING: ACTIVE,
        OPTIMIZING: ACTIVE,
        VACUUMINGOUTDATED: ACTIVE,
        DELETING: ACTIVE,
        RESTORING: DELETED,
        VACUUMING: DELETED,
    }


# ---------------------------------------------------------------------------
# Config keys (index/IndexConstants.scala) — flat string keys
# ---------------------------------------------------------------------------

HYPERSPACE_APPLY_ENABLED = "hyperspace.apply.enabled"
HYPERSPACE_APPLY_ENABLED_DEFAULT = True

INDEX_SYSTEM_PATH = "hyperspace.system.path"
# PathResolver.scala's <warehouse>/indexes, anchored at the user's home
# (no Spark warehouse here); metadata/path_resolver.py reads through this
INDEX_SYSTEM_PATH_DEFAULT = os.path.join(
    os.path.expanduser("~"), "hyperspace", "indexes"
)

INDEX_NUM_BUCKETS = "hyperspace.index.num_buckets"
INDEX_NUM_BUCKETS_DEFAULT = 200  # IndexConstants.scala:33-36 (= shuffle partitions)

INDEX_LINEAGE_ENABLED = "hyperspace.index.lineage.enabled"
INDEX_LINEAGE_ENABLED_DEFAULT = False  # IndexConstants.scala:105-106

INDEX_HYBRID_SCAN_ENABLED = "hyperspace.index.hybridscan.enabled"
INDEX_HYBRID_SCAN_ENABLED_DEFAULT = False
INDEX_HYBRID_SCAN_MAX_APPENDED_RATIO = "hyperspace.index.hybridscan.maxAppendedRatio"
INDEX_HYBRID_SCAN_MAX_APPENDED_RATIO_DEFAULT = 0.3  # IndexConstants.scala:44-52
INDEX_HYBRID_SCAN_MAX_DELETED_RATIO = "hyperspace.index.hybridscan.maxDeletedRatio"
INDEX_HYBRID_SCAN_MAX_DELETED_RATIO_DEFAULT = 0.2

INDEX_FILTER_RULE_USE_BUCKET_SPEC = "hyperspace.index.filterRule.useBucketSpec"
INDEX_FILTER_RULE_USE_BUCKET_SPEC_DEFAULT = False  # IndexConstants.scala:56-57

OPTIMIZE_FILE_SIZE_THRESHOLD = "hyperspace.index.optimize.fileSizeThreshold"
OPTIMIZE_FILE_SIZE_THRESHOLD_DEFAULT = 256 * 1024 * 1024  # 256MB, :116-117
OPTIMIZE_MODE_QUICK = "quick"
OPTIMIZE_MODE_FULL = "full"
OPTIMIZE_MODES = (OPTIMIZE_MODE_QUICK, OPTIMIZE_MODE_FULL)

REFRESH_MODE_FULL = "full"
REFRESH_MODE_INCREMENTAL = "incremental"
REFRESH_MODE_QUICK = "quick"
REFRESH_MODES = (REFRESH_MODE_FULL, REFRESH_MODE_INCREMENTAL, REFRESH_MODE_QUICK)

INDEX_CACHE_EXPIRY_SECONDS = "hyperspace.index.cache.expiryDurationInSeconds"
INDEX_CACHE_EXPIRY_SECONDS_DEFAULT = 300  # CachingIndexCollectionManager.scala

INDEX_SOURCES_PROVIDERS = "hyperspace.index.sources.fileBasedBuilders"
INDEX_SOURCES_PROVIDERS_DEFAULT = (
    "hyperspace_tpu.sources.default.DefaultFileBasedSourceBuilder,"
    "hyperspace_tpu.sources.delta.DeltaLakeSourceBuilder,"
    "hyperspace_tpu.sources.iceberg.IcebergSourceBuilder"
)

DEFAULT_SUPPORTED_FORMATS = "hyperspace.index.sources.defaultSupportedFormats"
# reference default: DefaultFileBasedSource.scala:76-85
DEFAULT_SUPPORTED_FORMATS_DEFAULT = "avro,csv,json,orc,parquet,text"

# Observability: when set, every session.execute runs under an XLA
# profiler trace written to this directory (TensorBoard/Perfetto format).
# SURVEY §5 calls for profiler integration on top of the typed event bus.
PROFILE_TRACE_DIR = "hyperspace.profile.traceDir"
PROFILE_TRACE_DIR_DEFAULT = ""

# Explain rendering (DisplayMode.scala: plaintext / console / html)
EXPLAIN_DISPLAY_MODE = "hyperspace.explain.displayMode"
EXPLAIN_DISPLAY_MODE_DEFAULT = "plaintext"

# Streaming build: cap the bytes materialized per wave of the covering
# index build (0 = unbounded, one in-memory pass). The reference gets
# disk-backed spill for free from Spark's shuffle
# (covering/CoveringIndex.scala:58-61 repartition); our wave loop lives in
# indexes/covering_build.py.
INDEX_BUILD_MEMORY_BUDGET = "hyperspace.index.build.memoryBudgetBytes"
INDEX_BUILD_MEMORY_BUDGET_DEFAULT = 0

# Exchange-strategy plane (parallel/shuffle.py, docs/MULTIHOST.md): the
# build's bucket shuffle has three strategies behind one interface and
# "auto" picks by platform alone (multi-process job -> "twostage"
# DCN/ICI decomposition; CPU mesh -> "host" pure-RAM reorder, the
# simulation must never pay ICI-emulation costs; single-host accelerator
# -> "compact"). A diagnostic override and test seam, not a tuning knob:
# every strategy is differential-tested bit-identical to "host", the
# numpy reference.
BUILD_EXCHANGE_STRATEGY = "hyperspace.build.exchange.strategy"
BUILD_EXCHANGE_STRATEGY_DEFAULT = "auto"

# Simulated host count for the twostage strategy on a SINGLE-process
# mesh (tests / A-B runs carve the flat mesh into this many groups of
# contiguous devices); 0 = derive from jax.process_count(). A real
# multi-process job always uses the process count.
BUILD_EXCHANGE_TWOSTAGE_HOSTS = "hyperspace.build.exchange.twostageHosts"
BUILD_EXCHANGE_TWOSTAGE_HOSTS_DEFAULT = 0

# Warn when the bucket shuffle's per-(shard, peer) send-count skew
# (max/mean) exceeds this: the exchange pads every slot to the max
# count, so one hot bucket silently inflates exchange memory by ~skew×.
# Tiny builds skip the warning (below the row floor the padded buffers
# are KBs — the ratio is always noisy there); telemetry records the
# ratio regardless.
BUILD_SHUFFLE_SKEW_WARN_RATIO = 4.0
BUILD_SHUFFLE_SKEW_WARN_MIN_ROWS = 1 << 12

# Z-order (IndexConstants.scala:59-74)
ZORDER_TARGET_SOURCE_BYTES_PER_PARTITION = (
    "hyperspace.index.zorder.targetSourceBytesPerPartition"
)
ZORDER_TARGET_SOURCE_BYTES_PER_PARTITION_DEFAULT = 1024 * 1024 * 1024
ZORDER_QUANTILE_ENABLED = "hyperspace.index.zorder.quantile.enabled"
ZORDER_QUANTILE_ENABLED_DEFAULT = False
ZORDER_QUANTILE_RELATIVE_ERROR = "hyperspace.index.zorder.quantile.relativeError"
ZORDER_QUANTILE_RELATIVE_ERROR_DEFAULT = 0.01

# Data-skipping (IndexConstants.scala:149-169)
DATASKIPPING_TARGET_INDEX_DATA_FILE_SIZE = (
    "hyperspace.index.dataskipping.targetIndexDataFileSize"
)
DATASKIPPING_TARGET_INDEX_DATA_FILE_SIZE_DEFAULT = 256 * 1024 * 1024
DATASKIPPING_AUTO_PARTITION_SKETCH = (
    "hyperspace.index.dataskipping.autoPartitionSketch"
)
DATASKIPPING_AUTO_PARTITION_SKETCH_DEFAULT = True

EVENT_LOGGER_CLASS = "hyperspace.eventLoggerClass"
EVENT_LOGGER_CLASS_DEFAULT = ""  # empty = the no-op EventLogger

# Number of device shards used for the build plane; 0 = all devices in
# the session mesh. A positive value caps the build mesh to the first N
# devices (A/B scaling runs; pinning a build off busy serve chips).
BUILD_NUM_SHARDS = "hyperspace.build.numShards"
BUILD_NUM_SHARDS_DEFAULT = 0

# ---------------------------------------------------------------------------
# Reserved column / property names
# ---------------------------------------------------------------------------

# Lineage column (IndexConstants: DATA_FILE_NAME_ID = "_data_file_id")
DATA_FILE_NAME_ID = "_data_file_id"

# Index log directory + data-version prefix (IndexDataManager.scala:24-37)
HYPERSPACE_LOG_DIR = "_hyperspace_log"
INDEX_VERSION_DIR_PREFIX = "v__"
LATEST_STABLE_LOG_NAME = "latestStable"

# IndexLogEntry property keys
LINEAGE_PROPERTY = "lineage"
HAS_PARQUET_AS_SOURCE_FORMAT_PROPERTY = "hasParquetAsSourceFormat"
DELTA_VERSION_HISTORY_PROPERTY = "deltaVersions"

# Nested-column prefix (util/ResolverUtils.scala `__hs_nested.`)
NESTED_FIELD_PREFIX = "__hs_nested."

# Nested (struct) field indexing is opt-in, as in the reference
# (conf.supportNestedFields gate, actions/CreateAction.scala:69-71;
# flattened-name machinery in util/ResolverUtils.scala:130-234).
INDEX_SUPPORT_NESTED_FIELDS = "hyperspace.index.supportNestedFields"
INDEX_SUPPORT_NESTED_FIELDS_DEFAULT = False

# Filenames written by the index data plane.
INDEX_FILE_PREFIX = "part"

# -- execution tuning --------------------------------------------------------
# Predicate evaluation dispatches to the XLA kernel only at/above this
# row count. Serve-path batches come out of host parquet reads, so the
# mask pays host->device transfer + readback before any compute. The
# value was set from a round-5 measurement on a different host attachment
# (~100ms for a 500k-row bucket vs ~2ms of host numpy) and has NOT been
# re-measured on this machine. Data already resident in HBM (mesh-sharded
# serve) is a different regime; lower this to force the device kernel.
EXECUTION_DEVICE_FILTER_MIN_ROWS = "hyperspace.execution.deviceFilterMinRows"
EXECUTION_DEVICE_FILTER_MIN_ROWS_DEFAULT = 8_000_000

# Single-device join matching runs on host by default (round-5
# measurement on a different host attachment, not re-measured on this
# machine: ~10x faster than the device sort+transfer round trip on one
# chip; a >1-device mesh always uses the sharded device program). Set a
# positive row count to force the device program on a single device once
# total rows reach it.
EXECUTION_DEVICE_JOIN_MIN_ROWS = "hyperspace.execution.deviceJoinMinRows"
EXECUTION_DEVICE_JOIN_MIN_ROWS_DEFAULT = 0  # 0 = never on single device

# -- aggregate index plane (indexes/aggindex.py, docs/agg-serve.md) ----------
# Master switch for the aggregate/approximate index plane: build-time
# capture of per-row-group partial-aggregate state into an
# ``_aggstate.json`` sidecar (+ the ``_aggsample.parquet`` stratified
# row sample), the serve-side metadata lowering that answers fully-
# covered Filter(→Project)→Aggregate plans from those partials without
# opening a single parquet file, and the AggregateIndexRule rewrite of
# bare Aggregate∘Scan plans onto a covering index. Off = the pre-plane
# behavior everywhere (no capture, no metadata lowering, no rewrite).
INDEX_AGG_ENABLED = "hyperspace.index.agg.enabled"
INDEX_AGG_ENABLED_DEFAULT = True

# Grouped-partial capture cap: per row group, single-column grouped
# partials are captured only for (fusable) columns whose distinct-value
# count in that row group stays at/below this. Row groups over the cap
# simply have no grouped entry for that key and fall back to the fused
# scan at serve time — a cap, never a correctness knob.
INDEX_AGG_MAX_GROUPS = "hyperspace.index.agg.maxGroupsPerRowGroup"
INDEX_AGG_MAX_GROUPS_DEFAULT = 256

# Stratified-sample size: rows sampled per row group (without
# replacement, seeded by (file, row group) so capture and lazy backfill
# produce the same sample) into the ``_aggsample.parquet`` sidecar that
# serves the approximate plane. 0 disables sampling at capture.
INDEX_AGG_SAMPLE_ROWS = "hyperspace.index.agg.sampleRowsPerGroup"
INDEX_AGG_SAMPLE_ROWS_DEFAULT = 128

# Approximate serving (execution/approx_exec.py): explicit opt-in for
# sample-based COUNT/SUM estimates with 95% confidence intervals via
# ``DataFrame.collect_approx()``. NEVER substituted for exact answers —
# the exact serve path ignores samples entirely; with the flag off,
# ``collect_approx`` raises instead of estimating.
SERVE_APPROX_ENABLED = "hyperspace.serve.approx.enabled"
SERVE_APPROX_ENABLED_DEFAULT = False

# Per-query error budget: the widest acceptable 95%-CI half-width
# relative to the estimate's magnitude. Estimates whose interval blows
# the budget raise ApproximationError (run exact instead) rather than
# returning a number the caller would over-trust. Overridable per query
# via ``collect_approx(max_rel_error=...)``.
SERVE_APPROX_MAX_REL_ERROR = "hyperspace.serve.approx.maxRelativeError"
SERVE_APPROX_MAX_REL_ERROR_DEFAULT = 0.05

# -- serve-server mode (execution/serve_cache.py) ----------------------------
# Opt-in cache of decoded index data (batches, prepared join sides) in
# host RAM, keyed by the immutable index file set — the data-plane
# extension of the reference's metadata TTL cache
# (CachingIndexCollectionManager.scala:38-108). First touch decodes and
# retains; later queries skip parquet entirely. LRU-evicted by bytes.
SERVE_CACHE_ENABLED = "hyperspace.serve.cache.enabled"
SERVE_CACHE_ENABLED_DEFAULT = False
SERVE_CACHE_MAX_BYTES = "hyperspace.serve.cache.maxBytes"
SERVE_CACHE_MAX_BYTES_DEFAULT = 4 << 30  # 4 GiB

# -- out-of-core serve (docs/out-of-core.md) ---------------------------------
# Streaming per-bucket join serve: prepared join sides are produced,
# matched, expanded and released wave-by-wave instead of materializing
# both whole prepared sides, so peak residency is one wave's buckets
# (<= stream.maxBytes estimated) rather than the relation. Bit-identical
# to the materializing path (differential-tested); the flag exists for
# A/B timing and as an escape hatch.
SERVE_STREAM_ENABLED = "hyperspace.serve.stream.enabled"
SERVE_STREAM_ENABLED_DEFAULT = False

# Wave budget for the streaming join path: the estimated decoded bytes
# of prepared buckets held in flight at once. Estimates come from
# parquet footer row counts x projected columns; waves are planned so
# their estimate stays under this cap (a single oversized bucket still
# runs alone — the bucket is the atom of residency).
SERVE_STREAM_MAX_BYTES = "hyperspace.serve.stream.maxBytes"
SERVE_STREAM_MAX_BYTES_DEFAULT = 256 << 20  # 256 MiB

# Spill tier for the ServeCache (execution/serve_cache.py): evicted
# prepared sides / decoded scans are demoted to fsync'd files under
# <system.path>/_hyperspace_spill/ (atomic publish per utils/files.py)
# and restored zero-copy (mmap + pickle5 out-of-band buffers) on the
# next miss, instead of being re-derived from parquet. 0 = off (evict
# to oblivion, the pre-spill behavior). The byte cap bounds the on-disk
# tier; oldest spill files are deleted when it overflows.
SERVE_SPILL_MAX_BYTES = "hyperspace.serve.spill.maxBytes"
SERVE_SPILL_MAX_BYTES_DEFAULT = 0

# Lease age for orphaned spill files: recovery's spill reaper
# (metadata/recovery.py reap_spill_orphans) deletes spill files and
# torn .tmp_spool_ temps whose mtime is older than this and that no
# live ServeCache in this process claims. Crashed serve processes leak
# spill files; the reaper is what makes the tier derived state, not
# durable state.
SERVE_SPILL_ORPHAN_TTL_MS = "hyperspace.serve.spill.orphanTtlMs"
SERVE_SPILL_ORPHAN_TTL_MS_DEFAULT = 10 * 60 * 1000  # 10 minutes

# Memory-mapped Arrow/parquet reads (io/parquet.py): pass
# memory_map=True into pyarrow readers so file bytes enter as kernel
# page-cache mappings. Parquet decode still copies (decompression), so
# this mainly helps uncompressed/IPC payloads; the honest-accounting
# half lives in serve_cache.estimate_nbytes, which charges mmap-backed
# buffers as file-backed (near-zero resident).
IO_MMAP_ENABLED = "hyperspace.io.mmap.enabled"
IO_MMAP_ENABLED_DEFAULT = False

# Range serve plane (executor._range_pruned_scan + indexes/zonemaps.py,
# see docs/range-serve.md): zone-map pruning of index files and row
# groups under range/Eq/In conjuncts, z-address range decomposition for
# z-order relations, and the fused hs_range_mask residual kernel.
# Superset-safe by construction (pruned-scan ≡ full-scan+mask,
# differential-tested); the flag restores the unpruned path bit-
# identically for A/B timing and as an escape hatch.
SERVE_RANGEPRUNE_ENABLED = "hyperspace.serve.rangeprune.enabled"
SERVE_RANGEPRUNE_ENABLED_DEFAULT = True

# Pipelined serve path (execution/executor.py + join_exec.py, see
# docs/serve-pipeline.md): on a co-bucketed join over clean index-scan
# shapes, the two sides prepare concurrently, per-bucket parquet reads
# overlap per-bucket prepare (reps/combine/sortedness), and the
# hybrid-scan appended-files delta is prepared off the critical path.
# Results are bit-identical to the sequential path (differential-tested);
# the flag exists for A/B timing and as an escape hatch.
SERVE_PIPELINE_ENABLED = "hyperspace.serve.pipeline.enabled"
SERVE_PIPELINE_ENABLED_DEFAULT = True

# Fused serve-pipeline compiler (execution/pipeline_compiler.py, see
# docs/serve-compiler.md): a Filter→Project→Aggregate (or plain
# Filter→Project) subtree over a pruned index scan is lowered into one
# fused native pass per surviving row-group chunk — predicate, projection
# and partial COUNT/SUM/MIN/MAX (grouped or not) in a single sweep, no
# materialized mask/gather/filtered-batch intermediates, partials merged
# at the edge. Bit-identical to the interpreted chain (differential-
# tested); the flag restores the old op-at-a-time path for A/B timing
# and as an escape hatch.
SERVE_FUSEDPIPELINE_ENABLED = "hyperspace.serve.fusedpipeline.enabled"
SERVE_FUSEDPIPELINE_ENABLED_DEFAULT = True

# FALLBACK default for the fused-pipeline dispatch crossover: at/above
# this many scanned rows the fused native pass runs; below it the
# interpreted chain (numpy twins) wins on kernel-call overhead. The
# effective value comes from the per-machine calibration probe
# (native/calibrate.py, native_fused_pipeline_min_rows); this constant
# is the probe-failure fallback, like every other dispatch threshold.
NATIVE_FUSED_PIPELINE_MIN_ROWS_DEFAULT = 1 << 15

# -- concurrent serve frontend (hyperspace_tpu/serve/) -----------------------
# Worker threads answering queries concurrently. 0 = auto: min(32,
# 4 x cores) — serve work is read-dominated (parquet/Arrow release the
# GIL), so oversubscribing cores keeps the scan pool fed while masks/
# merges run.
SERVE_MAX_CONCURRENCY = "hyperspace.serve.maxConcurrency"
SERVE_MAX_CONCURRENCY_DEFAULT = 0

# Admission control: queries queued (admitted but not yet running)
# beyond this bound are shed with a typed ServeOverloadedError instead
# of growing an unbounded backlog whose tail latency is unbounded too.
# 0 = unbounded (benchmark/batch use).
SERVE_MAX_QUEUE_DEPTH = "hyperspace.serve.maxQueueDepth"
SERVE_MAX_QUEUE_DEPTH_DEFAULT = 128

# Retry-with-backoff for TRANSIENT failures at the serve operation
# boundary (Exoshuffle doctrine: fault handling lives in the
# application-level dataflow, not under it): maxAttempts total tries,
# exponential backoff starting at backoffMs. Each retry re-pins the
# index snapshot, so a vacuum that removed the pinned version's files
# mid-query recovers onto the current version.
SERVE_RETRY_MAX_ATTEMPTS = "hyperspace.serve.retry.maxAttempts"
SERVE_RETRY_MAX_ATTEMPTS_DEFAULT = 3
SERVE_RETRY_BACKOFF_MS = "hyperspace.serve.retry.backoffMs"
SERVE_RETRY_BACKOFF_MS_DEFAULT = 10

# Fault injection (hyperspace_tpu/testing/faults.py): config keys
# ``hyperspace.faults.<point>`` name an injection point with a spec like
# "transient", "transient:3", "persistent", or "persistent;match=v__="
# (match = only paths containing the substring fault). Points:
# parquet_read, kernel_dispatch, log_read, cache_insert. The keys are
# READ only by an explicit ``faults.configure(session.conf)`` call (an
# operator/test act — production never arms itself); the serve plane's
# retry/degrade behavior under armed faults is the tested contract
# (docs/serve-server.md fault matrix).
FAULTS_KEY_PREFIX = "hyperspace.faults."

# Crash injection (same module): ``hyperspace.faults.crash.<point>``
# with a spec "raise[;at=N][;match=substr]" (in-process SimulatedCrash)
# or "exit[...]" (os._exit mid-protocol — true torn state). Points:
# after_begin_log, mid_data_write, after_data_write, after_end_log,
# mid_vacuum_delete. The crash × action recovery matrix is the tested
# contract (docs/recovery.md, tests/test_crash_recovery.py).
CRASH_KEY_PREFIX = "hyperspace.faults.crash."

# -- crash-safe lifecycle recovery (metadata/recovery.py) --------------------
# Master switch for the recovery plane: writer leases stamped into
# transient log entries, stranded-entry rollback at action start /
# session attach, stale latestStable healing, and the OCC retry loop in
# Action.run. Off = the pre-recovery behavior (a crashed writer strands
# the index until a manual cancel()).
RECOVERY_ENABLED = "hyperspace.recovery.enabled"
RECOVERY_ENABLED_DEFAULT = True

# Writer lease duration. A live action's heartbeat re-stamps its
# transient entry every leaseMs/3; an entry whose lease expired belongs
# to a DEAD writer (crash) and may be rolled back — this is what makes a
# slow writer distinguishable from a dead one. Entries written before
# the lease era (no lease properties) fall back to entry.timestamp +
# leaseMs.
RECOVERY_LEASE_MS = "hyperspace.recovery.leaseMs"
RECOVERY_LEASE_MS_DEFAULT = 60_000

# Orphan GC quarantine TTL: index data files referenced by no stable log
# entry are first MOVED into <index>/_hyperspace_quarantine/<stamp>/ and
# only deleted once the stamp is older than this grace period — so a
# serve that pinned its snapshot before the files went unreferenced
# finishes from the quarantine-free window (in-process pins are excluded
# from quarantine outright; the TTL covers other processes).
RECOVERY_ORPHAN_GRACE_MS = "hyperspace.recovery.orphanGraceMs"
RECOVERY_ORPHAN_GRACE_MS_DEFAULT = 10 * 60_000

# Lifecycle retry: an action losing the write_log OCC race re-snapshots
# the log tip and retries with exponential backoff (the PR 8 serve-retry
# shape at the write boundary) instead of surfacing
# ConcurrentWriteException to the user on the first collision.
RECOVERY_RETRY_MAX_ATTEMPTS = "hyperspace.recovery.retry.maxAttempts"
RECOVERY_RETRY_MAX_ATTEMPTS_DEFAULT = 3
RECOVERY_RETRY_BACKOFF_MS = "hyperspace.recovery.retry.backoffMs"
RECOVERY_RETRY_BACKOFF_MS_DEFAULT = 10

# Quarantine directory name (underscore-prefixed: invisible to data
# scans, like HYPERSPACE_LOG_DIR).
HYPERSPACE_QUARANTINE_DIR = "_hyperspace_quarantine"

# -- observability plane (hyperspace_tpu/obs/, docs/observability.md) --------
# The SERVE-plane switch for structured tracing + the durable query
# log: every query through the serve frontend gets ONE root span with
# child stage spans mirroring the breakdown keys, and each served query
# appends one JSONL record to the _hyperspace_obs/ sidecar next to the
# lake. Off (the default) = the zero-cost serve path: no span is live
# there, every obs call site costs one ContextVar.get, and serve
# behavior is bit-identical to the pre-obs tree. Lifecycle-action traces
# (action.<Class> roots) are recorded whatever this says.
OBS_ENABLED = "hyperspace.obs.enabled"
OBS_ENABLED_DEFAULT = False

# Durable query log (obs/querylog.py): one JSONL record per served
# query (fingerprint, predicate shape, stage timings, retry/degrade
# events, trace id), written to per-process files under
# <system.path>/_hyperspace_obs/ — the machine-readable workload
# profile the advisor loop (ROADMAP item 5) mines. Requires obs.enabled.
OBS_QUERYLOG_ENABLED = "hyperspace.obs.querylog.enabled"
OBS_QUERYLOG_ENABLED_DEFAULT = True

# Rotation bounds: the active per-process file rotates (fsync-before-
# rename, crash-safe — see the mid_querylog_rotate crash point) once it
# exceeds maxBytes, and at most maxFiles rotated segments are retained
# per process (oldest pruned first). Readers union every segment of
# every process, so rotation never loses in-flight records.
OBS_QUERYLOG_MAX_BYTES = "hyperspace.obs.querylog.maxBytes"
OBS_QUERYLOG_MAX_BYTES_DEFAULT = 4 << 20  # 4 MiB per segment
OBS_QUERYLOG_MAX_FILES = "hyperspace.obs.querylog.maxFiles"
OBS_QUERYLOG_MAX_FILES_DEFAULT = 8

# Trace plane bounds (obs/trace.py): maxSpans caps the child spans
# recorded per trace (excess children are dropped and counted in the
# root's ``spans_dropped`` attr — a runaway per-bucket fan-out must not
# hold the whole serve's span set in RAM); retain caps the in-memory
# ring of finished traces kept for bench/test introspection.
OBS_TRACE_MAX_SPANS = "hyperspace.obs.trace.maxSpans"
OBS_TRACE_MAX_SPANS_DEFAULT = 512
OBS_TRACE_RETAIN = "hyperspace.obs.trace.retain"
OBS_TRACE_RETAIN_DEFAULT = 256

# JSONL event sink path for telemetry events (obs/metrics.py JsonlSink
# + telemetry.JsonlEventLogger): empty = next to the lake under
# <system.path>/_hyperspace_obs/events.<pid>.jsonl when the Jsonl
# logger is selected via hyperspace.eventLoggerClass.
OBS_EVENTLOG_PATH = "hyperspace.obs.eventlog.path"
OBS_EVENTLOG_PATH_DEFAULT = ""

# Opt-in replayable plan specs in the query log (obs/planspec.py): each
# record additionally carries a re-executable "replay" plan spec.
# Specs retain predicate LITERALS (unlike the scrubbed predicate
# shape), so this stays off unless the operator wants the advisor's
# what-if scoring and the replay harness (testing/replay.py) to work
# straight from production logs.
OBS_QUERYLOG_RECORD_PLANS = "hyperspace.obs.querylog.recordPlans"
OBS_QUERYLOG_RECORD_PLANS_DEFAULT = False

# Observability sidecar directory under the lake root (underscore-
# prefixed: invisible to data scans, like the quarantine/pins dirs).
HYPERSPACE_OBS_DIR = "_hyperspace_obs"

# -- workload advisor (hyperspace_tpu/advisor/, docs/advisor.md) --------------
# Workload-profile bound: the query-log aggregator groups records by
# literal-scrubbed predicate shape and keeps at most this many shape
# groups resident (further shapes fold into an overflow counter) — the
# profile is O(maxShapes), never O(records), whatever the log size
# (ALLOC_SITES const-bounded contract).
ADVISOR_PROFILE_MAX_SHAPES = "hyperspace.advisor.profile.maxShapes"
ADVISOR_PROFILE_MAX_SHAPES_DEFAULT = 256

# What-if search bound: at most this many candidate indexes are
# enumerated from the hot shapes and scored against the recorded
# workload per advise() pass (hottest shapes first, overflow logged).
ADVISOR_MAX_CANDIDATES = "hyperspace.advisor.maxCandidates"
ADVISOR_MAX_CANDIDATES_DEFAULT = 32

# Opt-in budgeted apply: advisor.apply() executes top recommendations
# through the lifecycle actions (lease-stamped like any maintenance,
# so fleet serve traffic sees the PR 10 protections) until either
# budget is exhausted. Off = advise-only, nothing touches the lake.
ADVISOR_APPLY_ENABLED = "hyperspace.advisor.apply.enabled"
ADVISOR_APPLY_ENABLED_DEFAULT = False
ADVISOR_APPLY_MAX_BYTES = "hyperspace.advisor.apply.maxBytes"
ADVISOR_APPLY_MAX_BYTES_DEFAULT = 1 << 30
ADVISOR_APPLY_MAX_SECONDS = "hyperspace.advisor.apply.maxSeconds"
ADVISOR_APPLY_MAX_SECONDS_DEFAULT = 300.0

# -- replicated serve fleet (serve/fleet.py, serve/bus.py) -------------------
# Master switch for fleet mode: N ServeFrontend processes over ONE index
# lake. Turns on (a) DURABLE query pins — each pinned snapshot is also
# published as a lease-expiring file under <index>/_hyperspace_pins/ so
# an orphan GC or vacuum running in ANOTHER process never deletes files
# under a live query; (b) the index-version fanout bus — lifecycle
# actions publish change events under <system.path>/_hyperspace_fleet/
# that peers poll to invalidate (or, for aggregate-plane state, install)
# their ServeCache entries instead of serving stale pins; (c) cross-
# process single-flight — identical plans submitted to several frontends
# elect one executor through a fingerprint-keyed claim file and share
# the answer through a bounded result spool. Off = the single-process
# PR 8 behavior everywhere (in-memory pins, no bus, no spool).
FLEET_ENABLED = "hyperspace.fleet.enabled"
FLEET_ENABLED_DEFAULT = False

# Durable pin lease: a fleet frontend's pin files are renewed every
# leaseMs/3 by a heartbeat thread; a pin whose lease expired belongs to
# a DEAD frontend (kill -9, OOM) and is reaped by the next GC/vacuum —
# the recovery plane's writer-lease discriminator applied to readers.
FLEET_PIN_LEASE_MS = "hyperspace.fleet.pin.leaseMs"
FLEET_PIN_LEASE_MS_DEFAULT = 30_000

# Fanout bus poll cadence: how often each subscribed frontend lists the
# bus directory for events published by its peers.
FLEET_BUS_POLL_MS = "hyperspace.fleet.bus.pollMs"
FLEET_BUS_POLL_MS_DEFAULT = 100

# Bus event retention: event files older than this are pruned by the
# next publisher (every subscriber that was alive at publish time has
# long since polled them; a frontend attaching later starts from the
# current state anyway).
FLEET_BUS_RETAIN_MS = "hyperspace.fleet.bus.retainMs"
FLEET_BUS_RETAIN_MS_DEFAULT = 60_000

# Cross-process single-flight: identical plans arriving at N frontends
# elect ONE executor via an atomic claim file keyed by the plan + pinned
# snapshot fingerprint; the losers wait up to waitMs for the winner's
# spooled result before executing locally (correctness never depends on
# the election — a timeout just forfeits the dedup win). claimMs bounds
# how long a dead winner's claim blocks peers.
FLEET_SINGLEFLIGHT_ENABLED = "hyperspace.fleet.singleflight.enabled"
FLEET_SINGLEFLIGHT_ENABLED_DEFAULT = True
FLEET_SINGLEFLIGHT_WAIT_MS = "hyperspace.fleet.singleflight.waitMs"
FLEET_SINGLEFLIGHT_WAIT_MS_DEFAULT = 5_000
FLEET_SINGLEFLIGHT_CLAIM_MS = "hyperspace.fleet.singleflight.claimMs"
FLEET_SINGLEFLIGHT_CLAIM_MS_DEFAULT = 10_000

# Result spool byte budget: the winner of a single-flight election
# publishes its answer as an Arrow IPC file under
# <system.path>/_hyperspace_fleet/spool/; writers prune the oldest
# results past this budget (results are version-addressed — a refresh
# re-keys every plan, so stale entries are unreachable, only unread).
FLEET_SPOOL_MAX_BYTES = "hyperspace.fleet.spool.maxBytes"
FLEET_SPOOL_MAX_BYTES_DEFAULT = 256 << 20  # 256 MiB

# Per-tenant SLO classes (prefix family, like hyperspace.faults.):
# hyperspace.fleet.class.<name>.maxConcurrency caps how many queries of
# class <name> RUN at once on a frontend (0 = unlimited; excess admits
# queue without occupying worker threads), and
# hyperspace.fleet.class.<name>.maxQueueDepth sheds class-<name>
# admissions past that backlog with a typed ServeOverloadedError —
# layered UNDER the global hyperspace.serve.maxQueueDepth bound, so a
# batch tier with a tight class budget sheds before the interactive
# tier feels any pressure. Queries submitted without a class (or with
# an unconfigured class name) see only the global bounds.
FLEET_CLASS_KEY_PREFIX = "hyperspace.fleet.class."

# -- fleet fast data plane (serve/fastbus.py, serve/router.py) ---------------
# The durable planes above coordinate through files and polling — always
# correct, but the polling tax dominates at small fleets (ROADMAP item
# 3). The fast plane layers a per-host push bus (Unix sockets announced
# through lease-stamped member files under _hyperspace_fleet/members/)
# and owner routing (rendezvous-hash the plan digest to one member, ship
# the plan spec, stream the Arrow result back — no claim election, no
# fsync'd spool round-trip) on top. Every fast-path message is
# idempotently replayable from the durable planes: a dropped push costs
# a poll interval, a dead owner costs one failed connect and a fallback
# to the claim/spool path — never a wrong answer. Off = PR 14 behavior.
FLEET_FAST_ENABLED = "hyperspace.fleet.fast.enabled"
FLEET_FAST_ENABLED_DEFAULT = True

# Owner routing sub-switch: with it off the fast plane still pushes
# fanout events, result-ready wakeups and SLO gossip, but single-flight
# stays on the claim/spool election (useful to isolate a routing bug in
# production without losing push latency).
FLEET_FAST_ROUTING_ENABLED = "hyperspace.fleet.fast.routing.enabled"
FLEET_FAST_ROUTING_ENABLED_DEFAULT = True

# Round-trip budget for one owner-routed execution request. A timeout
# (or any send/receive failure, including an armed fastbus_send fault)
# falls back to the durable single-flight plane — the budget bounds the
# p99 blip when an owner dies, it never forfeits the answer.
FLEET_FAST_REQUEST_TIMEOUT_MS = "hyperspace.fleet.fast.requestTimeoutMs"
FLEET_FAST_REQUEST_TIMEOUT_MS_DEFAULT = 2_000

# Member lease: each frontend announces its socket in a lease-expiring
# member file renewed every leaseMs/3 by the router maintenance thread;
# a member whose lease expired is a dead process (kill -9, OOM) — peers
# reap its member file AND its socket file, and rendezvous routing stops
# offering it work. The same discriminator as the writer and pin leases.
FLEET_FAST_MEMBER_LEASE_MS = "hyperspace.fleet.fast.memberLeaseMs"
FLEET_FAST_MEMBER_LEASE_MS_DEFAULT = 10_000

# Byte budget for the in-memory digest->result cache each member keeps
# (LRU, measured by Arrow table nbytes). Results are snapshot-addressed
# like the spool, so a cached entry can be stale only in the sense of
# unreachable — a refresh re-keys every digest. 0 disables the cache.
FLEET_FAST_RESULT_CACHE_BYTES = "hyperspace.fleet.fast.resultCacheBytes"
FLEET_FAST_RESULT_CACHE_BYTES_DEFAULT = 64 << 20  # 64 MiB

# Queue-depth gossip cadence: each member pushes its per-class
# running+pending depths to every live peer this often, feeding the
# fleet-wide SLO admission check. Entries older than ~10 gossip periods
# are ignored (a dead peer must not pin its last-known depth forever).
FLEET_FAST_GOSSIP_MS = "hyperspace.fleet.fast.gossipMs"
FLEET_FAST_GOSSIP_MS_DEFAULT = 50

# Fleet-wide SLO enforcement: when on, the per-tenant class queue-depth
# bound counts the gossiped depths of live peers too, so a batch tier
# saturating ONE process sheds fleet-wide before the interactive tier
# feels pressure on ANY process. Off = per-process depths (PR 14).
FLEET_FAST_SLO_FLEET_WIDE = "hyperspace.fleet.fast.sloFleetWide"
FLEET_FAST_SLO_FLEET_WIDE_DEFAULT = True

# Durable pin directory name (underscore-prefixed, next to the log —
# invisible to data scans like the quarantine dir).
HYPERSPACE_PINS_DIR = "_hyperspace_pins"

# Fleet coordination directory under the lake root (hyperspace.system.
# path): <root>/_hyperspace_fleet/bus/ event files +
# <root>/_hyperspace_fleet/spool/ single-flight claims and results.
HYPERSPACE_FLEET_DIR = "_hyperspace_fleet"

# ServeCache spill tier directory under the lake root:
# <root>/_hyperspace_spill/<sha>.spill files. Derived state — fully
# rebuildable from the index parquet — so the recovery plane's spill
# reaper deletes orphans past hyperspace.serve.spill.orphanTtlMs and
# gc_orphans/vacuum never quarantine the live dir (underscore-prefixed,
# invisible to data and index scans like the other sidecar dirs).
HYPERSPACE_SPILL_DIR = "_hyperspace_spill"
