"""OBS_SITES — the registry of observability instrumentation sites.

The SHARED_STATE / KERNEL_TWINS / COLLECTIVE_SITES doctrine applied to
the observability plane: every call site that CREATES spans
(``trace.root`` / ``trace.span`` / ``trace.stage``, and the build
plane's one stage hook ``covering_build.stage``), puts ATTRS on a span
it did not open (``trace.current()``: a pass that says what its
caller's stage's seconds went to) or REGISTERS
metrics (``registry.counter`` / ``gauge`` / ``labeled_counter`` /
``stage_timer`` / ``register_view`` / ``register_weak_view``) declares
itself HERE with a
one-line justification — so "what is instrumented, and why?" is a
mechanical question (``hslint`` HS9xx, ``analysis/obs.py``), not an
archaeology project, and a hot loop cannot silently grow a span per
row. Propagation shims (``trace.carry``/``activate``) and point events
(``trace.event``) are deliberately exempt: they create no spans.

Entry shape::

    "<dotted path of the function, method, or module>": (
        "<kind: span | metric | view | attr>",
        "<one-line justification — why this site is instrumented>",
    )

Paths name a module-level function
(``hyperspace_tpu.execution.join_exec._stage_add``), a method
(``hyperspace_tpu.serve.frontend.ServeFrontend.submit``), or a whole
module (``hyperspace_tpu.execution.join_exec`` — module-level
instrument registration). Calls in nested defs/lambdas attribute to
their outermost enclosing def, like the collective registry.

Stage-span VOCABULARY: HS902 rejects any constant stage/span name that
is not listed below — a misspelled span name would silently fork the
taxonomy the querylog, the benchmark's ``action_trace`` reader and
docs/observability.md all key on. A span is declared where the time
is, at a layer boundary — never per row, per bucket or per file:
repeated work is summarized as attrs on its enclosing span, and so is
what a stage's seconds went to (phases that add up to it, CPU beside
wall, waiting apart from work: docs/observability.md has the table).

Keep this module stdlib-only and import-cheap: the analyzer only ever
parses it, and the obs plane imports it for the vocabulary.
"""

from __future__ import annotations

from typing import Dict, Tuple

#: site kinds (HS903 rejects anything else)
KINDS = ("span", "metric", "view", "attr")

#: serve-side stage spans — the last_serve_breakdown keys plus the
#: frontend's admission stages (docs/observability.md "Span taxonomy")
SERVE_STAGES = (
    "queue_wait",
    "pin",
    "rewrite",
    "prune",
    "scan",
    "prepare",
    "match",
    "expand",
    "verify",
    "assemble",
    "delta",
    "agg",
    "finalize",
    "execute",
    # out-of-core serve (docs/out-of-core.md): one span per streaming
    # join wave, and the spill tier's demote/restore I/O
    "stream_wave",
    "spill_write",
    "spill_restore",
)

#: build/lifecycle spans (docs/observability.md "Span taxonomy"). Direct
#: children of an ``action.<Class>`` root: the protocol steps and the
#: stages ``covering_build.stage`` records (which are also the
#: ``last_build_breakdown`` keys); one level down, the parts of
#: ``hash_shuffle`` and ``sort``
BUILD_STAGES = (
    # the action protocol (actions/base.py)
    "validate",
    "begin_log",
    "log_entry",
    "log_commit",
    "publish_event",
    # build stages (covering_build.stage)
    "resolve",
    "scan",
    "hash_shuffle",
    "dict_probe",
    "sort",
    "write",
    "sidecar_capture",
    # under hash_shuffle: key reps, then the device round trip of
    # ops/hash.bucket_ids_np — or its host twin
    "key_reps",
    "split_words",
    "h2d",
    "kernel",
    "d2h",
    "host_hash",
    # under sort (the pipelined partition-first tail)
    "partition",
    "to_arrow",
    "bucket_sorts",
    # under hash_shuffle on a mesh (parallel/shuffle.py): the host's
    # plan and pack, the host-seen device leg — h2d / kernel / d2h under
    # it, the names the single-chip hash uses for the same three things
    # — and the host's unpack
    "exchange_plan",
    "pack",
    "exchange",
    "unpack",
    # a z-order build's stages (indexes/zorder._write_zordered), with
    # to_arrow and write as above: the order encodings + min/max, the
    # z-address planes (words = the host's scaling, stack and padding,
    # then h2d / kernel / d2h of ops/zorder), the global lexsort (h2d /
    # kernel / d2h of ops/sort.lexsort_perm's device arm under it), the
    # gather of the batch
    "zorder_encode",
    "zorder_interleave",
    "words",
    "zorder_sort",
    "take",
    # a data-skipping build (indexes/dataskipping): its one per-file
    # loop, with write as above
    "sketch",
)

#: advisor-side stage spans (advisor/: query-log mining and what-if
#: scoring under one "advisor.run" root — docs/advisor.md)
ADVISOR_STAGES = (
    "advisor.scan",
    "advisor.score",
)

#: root span names (constant ones; action roots are "action.<Class>")
ROOT_NAMES = ("serve.query", "advisor.run")

#: the full constant-name vocabulary HS902 checks against
STAGE_NAMES = tuple(
    sorted(set(SERVE_STAGES) | set(BUILD_STAGES) | set(ADVISOR_STAGES))
)

OBS_SITES: Dict[str, Tuple[str, str]] = {
    # -- serve plane ---------------------------------------------------------
    "hyperspace_tpu.serve.frontend.ServeFrontend.submit": (
        "span",
        "the query ROOT span starts at admission so queue-wait is "
        "attributable; one root per admitted query is the bench gate",
    ),
    "hyperspace_tpu.serve.frontend.ServeFrontend._pin": (
        "span",
        "snapshot pinning is a metadata read with its own retry loop — "
        "a slow pin must be distinguishable from a slow execute",
    ),
    "hyperspace_tpu.serve.frontend.ServeFrontend._run": (
        "span",
        "queue_wait closes when a worker picks the query up; the root "
        "span finishes (and the querylog row lands) here",
    ),
    "hyperspace_tpu.serve.frontend.ServeFrontend._execute_pinned": (
        "span",
        "rewrite vs execute split: index selection time must never be "
        "conflated with data-plane time",
    ),
    "hyperspace_tpu.serve.frontend.ServeFrontend.__init__": (
        "view",
        "the frontend's stats() counters export live through the "
        "registry (one owner, one lock — no counter forking)",
    ),
    "hyperspace_tpu.execution.serve_cache.ServeCache.__init__": (
        "view",
        "the memory governor's stats() export live through the "
        "registry, same single-owner discipline as the frontend",
    ),
    "hyperspace_tpu.execution.serve_cache.ServeCache._spill_demote": (
        "span",
        "spill_write is pickle + fsync'd publish outside every "
        "breakdown stage — unexplained serve tail time under memory "
        "pressure must be attributable to the spill tier",
    ),
    "hyperspace_tpu.execution.serve_cache.ServeCache._restore_from_spill": (
        "span",
        "spill_restore makes the cost of serving from the disk tier "
        "visible next to the scan/prepare stages it displaces",
    ),
    "hyperspace_tpu.serve.fleet": (
        "metric",
        "cross-process single-flight election attempts/wins/losses as "
        "process-global counters: election health is fleet-level "
        "telemetry every sink must export, not one frontend's stats()",
    ),
    "hyperspace_tpu.execution.join_exec": (
        "metric",
        "last_serve_breakdown IS this stage_timer's backing dict — the "
        "scattered serve snapshot absorbed as a registered instrument",
    ),
    "hyperspace_tpu.execution.join_exec._stage_add": (
        "span",
        "the ONE serve stage hook: the stage span and the breakdown "
        "increment are the same measurement, so they cannot disagree",
    ),
    "hyperspace_tpu.execution.executor._exec": (
        "span",
        "the agg stage (metadata lowering + fused pass + interpreted "
        "chain) is invisible to the join breakdown; its span closes the "
        "serve taxonomy",
    ),
    # -- build / lifecycle plane ---------------------------------------------
    "hyperspace_tpu.indexes.covering_build": (
        "metric",
        "last_build_breakdown IS this stage_timer's backing dict — the "
        "build snapshot absorbed as a registered instrument",
    ),
    "hyperspace_tpu.indexes.covering_build.stage": (
        "span",
        "the ONE build stage hook: the stage span (and its hs.<name> "
        "profiler annotation) and the breakdown increment are the same "
        "measurement, so they cannot disagree; it also reads the CPU "
        "clock at entry and exit — cpu_s (the process's, all threads) "
        "on every stage, thread_cpu_s (the task's own thread) on a "
        "span that names its shard — so a stage that waited is told "
        "from one that worked",
    ),
    "hyperspace_tpu.indexes.covering_build.prepare_covering_index": (
        "span",
        "resolve stage: schema resolution, lineage ids and per-file "
        "footer sizes are metadata reads that scale with the source's "
        "file count, not its rows",
    ),
    "hyperspace_tpu.indexes.covering_build.lazy_or_materialized": (
        "span",
        "scan stage: the projected source read; counts rows and source "
        "bytes at the boundary where they enter the build (files and "
        "rows as attrs beside what _scan_with_lineage adds)",
    ),
    "hyperspace_tpu.indexes.covering_build._scan_with_lineage": (
        "attr",
        "read_s / decode_s / lineage_s (the files' task seconds, thread "
        "seconds where the pool overlaps them), concat_s (the caller's "
        "wall outside the tasks), max_read_s, workers, and columns / "
        "direct (columns read, and those copied once into a "
        "preallocated column) on the span the scan runs under: whether "
        "a scan is I/O, decode, the lineage fill or the assembly decides "
        "its next cut, and the slowest file's read is the stalled "
        "build's tell — never a span a file. The reads are "
        "_read_source_files' (workers, and at most that many files read "
        "ahead of the consumer), which times each on its thread and "
        "records nothing itself: its callers, this scan and "
        "DataSkippingIndex.build_sketch_rows, sum its read_ns",
    ),
    "hyperspace_tpu.indexes.covering_build._hash_shuffle": (
        "span",
        "hash_shuffle stage, with key_reps split off so the hash's "
        "device round trip (ops/hash) is not confused with the host's "
        "key encoding; key_columns (the indexed columns of the key) on "
        "key_reps and, set, on the root: every host pass and the h2d "
        "go by it; copied on key_reps: the key columns whose bytes were "
        "copied on the way (0 where one int64 column is its own reps)",
    ),
    "hyperspace_tpu.indexes.covering_build.bucketize": (
        "span",
        "sort stage of the wave/legacy path (permutation + take)",
    ),
    "hyperspace_tpu.indexes.covering_build.write_bucketed": (
        "span",
        "dict_probe (the one encoding decision over the pre-sort input) "
        "and the legacy write stage",
    ),
    "hyperspace_tpu.indexes.covering_build._write_bucketed_pipelined": (
        "span",
        "sort (partition / to_arrow / bucket_sorts) and write of the "
        "pipelined tail: per-bucket sorts and per-file writes are "
        "summarized as buckets/sum_s/max_s/cpu_sum_s attrs (cpu_sum_s: "
        "the tasks' own threads' CPU seconds, so sum_s - cpu_sum_s is "
        "what they spent off a CPU), so a stalled thread shows without "
        "a span per bucket; bucket_sorts also says planes (order words "
        "a sort compares) and max_rows (the largest bucket), write "
        "take_s / encode_s (a file's gather against its parquet write, "
        "thread seconds from io/parquet.write_bucket_file) and columns",
    ),
    "hyperspace_tpu.indexes.covering_build._write_bucketed_sharded": (
        "span",
        "partition (order words) and to_arrow once before the shard "
        "pool, then one sort and one write span per SHARD tail (attr "
        "shard; thread_cpu_s and cpu_sum_s, take_s / encode_s / "
        "columns on write as above), carried onto the shard pool's "
        "threads",
    ),
    "hyperspace_tpu.parallel.shuffle._device_leg": (
        "span",
        "exchange and its h2d / kernel / d2h children: the device leg "
        "of every strategy that has one goes through this one function, "
        "so a 1.5 ms all_to_all inside seconds of host-seen exchange is "
        "told from its transfers; d2h is _fetch_shards (every output's "
        "copy_to_host_async before any read, then the shards in place) "
        "and carries shards / started / assembled_bytes beside bytes",
    ),
    "hyperspace_tpu.parallel.shuffle._timed": (
        "span",
        "exchange_plan / pack / exchange / unpack of every exchange "
        "strategy (flat, host, compact, both twostage legs): each "
        "span's seconds are also the exchange's account, so the host "
        "packing that outweighs the device leg (PERF.md) is readable "
        "apart from it; host has no device leg and no unpack; compact "
        "counts gathers_native/gathers_numpy on pack (payloads by the "
        "gather that moved them) and runs on unpack",
    ),
    "hyperspace_tpu.ops.hash.bucket_ids_np": (
        "span",
        "split_words / h2d / kernel / d2h (or host_hash): a 0.7 ms "
        "kernel inside a 1.2 s stage is explained only by separating "
        "the host's word split and each transfer from the kernel wait; "
        "split_words says words (the uint32 block's first dimension, "
        "two a key column) and native (1: the block was made by the one "
        "native pass, 0: by its numpy twin)",
    ),
    "hyperspace_tpu.ops.sort._order_words_np": (
        "attr",
        "native (1 | 0: the order words were made by the one native "
        "pass, or by the numpy twin) on the partition span of both "
        "build tails where the order words run under it, and on no "
        "other (the serve path sorts through here too): whether 0.7 s a key "
        "column of partition are five numpy passes or one native one "
        "is the first thing its seconds are read against — never a "
        "span of its own",
    ),
    "hyperspace_tpu.ops.zorder.ZOrderEncoder.planes_from_encodings": (
        "span",
        "words / h2d / kernel / d2h: the z-address planes are host word "
        "scaling, two transfers and a device program; the 0.15 s kernel "
        "inside seconds of zorder_interleave is told from them; words "
        "names its passes as attrs (scale_s, stack_s, pad_s) and counts "
        "the rows the native pass wrote (native, of columns)",
    ),
    "hyperspace_tpu.ops.zorder.ZOrderEncoder.fit": (
        "attr",
        "order_s / minmax_s (sums over the columns) on the span the fit "
        "runs under, zorder_encode in a build: which pass holds the "
        "stage's seconds decides between fused numpy passes and a "
        "native kernel — never a span a column; native (of columns) "
        "counts the encodings the native pass made",
    ),
    "hyperspace_tpu.ops.sort.lexsort_perm": (
        "span",
        "h2d / kernel / d2h of the device arm only (the z-order build's "
        "global sort); the host arms, which the per-bucket sorts take, "
        "open nothing",
    ),
    "hyperspace_tpu.indexes.zorder._write_zordered": (
        "span",
        "zorder_encode / zorder_interleave / zorder_sort / take / "
        "to_arrow: the z-order build's data plane, a third of the "
        "build, had no span at all (PERF.md, PR 34)",
    ),
    "hyperspace_tpu.indexes.zorder._write_parts": (
        "span",
        "write of a z-sorted table's files, one after another: files / "
        "bytes / rows as attrs, never a span a file",
    ),
    "hyperspace_tpu.indexes.zorder._write_zordered_streaming": (
        "span",
        "the same stage names where the streamed build's spill and "
        "merge run the same steps, once a wave or a z-range",
    ),
    "hyperspace_tpu.indexes.dataskipping.DataSkippingIndex.build_sketch_rows": (
        "span",
        "sketch: the data-skipping build's one loop over the source "
        "files (each file's table from _read_source_files, then each "
        "sketch, file after file) is one span a build; files / rows / "
        "workers and what its seconds went to, perf_counter deltas "
        "summed over the files, as attrs (read_s the read's task "
        "seconds as on scan, minmax_s, bloom_s and inside it words_s / "
        "device_s / set_bits_s, table_s), the bloom's h2d_bytes / "
        "d2h_bytes from shapes on the root — never a span a file or a "
        "sketch",
    ),
    "hyperspace_tpu.indexes.dataskipping.DataSkippingIndex.write": (
        "span",
        "write of the one sketch file, files / bytes as attrs",
    ),
    "hyperspace_tpu.indexes.aggindex.capture_index_dir": (
        "span",
        "sidecar_capture (aggstate): build-tail I/O that re-reads every "
        "file just written, one pool task a file — or a range of a "
        "file's row groups where the files are fewer than the workers: "
        "tasks (units handed to the pool), split_files (files cut into "
        "ranges); files/workers/files_s "
        "(the pool's wall), what the tasks' seconds went to, timed where "
        "a task puts the turn down and takes it up (aggindex._outside, "
        "_take) and summed over the tasks — python_s (turn held: "
        "serial, a floor of files_s), read_s and sweep_s (outside it), "
        "turn_wait_s — sweeps_native/sweeps_twin/early_rejects "
        "(row-group passes by the implementation that ran them) and "
        "publish_s/bytes as attrs, never a span per file",
    ),
    "hyperspace_tpu.indexes.zonemaps.capture_index_dir": (
        "span",
        "sidecar_capture (zonemap): the footer pass and its publish, "
        "the same stage name with its own sidecar attr; on a z-order "
        "directory row_groups with zspans_from_write / zspans_reread "
        "(row groups by where their span came from) and, where the "
        "re-read ran, its parts (zspan_fit_s, zspan_planes_s, "
        "zspan_minmax_s) are attrs",
    ),
    "hyperspace_tpu.actions.base.Action.run": (
        "span",
        "the lifecycle-action ROOT span, always recorded — every action "
        "is explainable after the fact, whatever the outcome and "
        "whatever the serve-plane switch says; counter cpu_s (the "
        "process's CPU seconds over the action), and the root it opens "
        "is what registers the compile listener (obs/trace."
        "_listen_for_compiles, once a process where jax is imported): "
        "compiles / compile_s / compile_cache_hits on the span a "
        "compile ran under and on this root, so a warm-up build says "
        "how many of its seconds were XLA's and under which stage",
    ),
    "hyperspace_tpu.actions.base.Action._run_protocol": (
        "span",
        "validate / begin_log / log_entry / log_commit / publish_event: "
        "metadata-plane time must be separable from data-plane op() "
        "time, and op() has no span so uncovered time stays visible",
    ),
    "hyperspace_tpu.actions.base.Action._run_coordinated": (
        "span",
        "the coordinator-side protocol spans on multi-process jobs (the "
        "same seams, behind the rendezvous protocol)",
    ),
    "hyperspace_tpu.actions.base.Action._run_data_plane": (
        "span",
        "the workers' validate span (they write no log entries)",
    ),
    # -- workload advisor (advisor/, docs/advisor.md) ------------------------
    "hyperspace_tpu.advisor.recommend.advise": (
        "span",
        "the advisor.run ROOT span — one trace per advise() pass, so "
        "mining + what-if time is explainable in the same plane it "
        "consumes",
    ),
    "hyperspace_tpu.advisor.profile.build_profile": (
        "span",
        "advisor.scan stage: query-log union + shape aggregation time, "
        "separable from scoring (a huge log must be visible as a scan "
        "cost, not a mystery)",
    ),
    "hyperspace_tpu.advisor.whatif.score_workload": (
        "span",
        "advisor.score stage: one span per candidate's workload pass — "
        "what-if cost scales with candidates x shapes and must be "
        "attributable",
    ),
    "hyperspace_tpu.advisor.profile": (
        "metric",
        "advisor health counters (profiles built, shape-cap overflows) "
        "— the convergence loop's own telemetry rides the registry",
    ),
    "hyperspace_tpu.testing.replay": (
        "metric",
        "replay harness instruments (queries replayed/skipped/failed) — "
        "the bench replay gate asserts on these, same plane as the "
        "querylog counters",
    ),
}
