"""Action protocol: validate / begin / op / end.

Reference: ``actions/Action.scala:34-108``. The id arithmetic (`:35-36`):
``baseId`` = latest existing log id (0 if none); begin writes ``baseId+1``
(transient), end writes ``baseId+2`` (final) and recreates the
``latestStable`` pointer. A concurrent writer loses the ``write_log``
create-if-absent race — and, since the recovery plane (PR 10), retries
from a fresh snapshot with backoff instead of aborting on the first
collision. ``NoChangesException`` from ``validate`` makes the whole
action a graceful no-op (refresh/optimize with nothing to do).

Crash safety (``metadata/recovery.py``, docs/recovery.md): ``run()``
first repairs any dead writer's leavings at the log tip
(``ensure_recovered`` — rollback of lease-expired transient entries,
latestStable healing), re-snapshots ``base_id`` (the ``__init__``-time
read is advisory only; a queued action must see the tip as of *run*,
not construction), stamps a writer lease into the begin entry, and
heartbeats that lease while ``op()`` runs so a slow writer is never
mistaken for a dead one. The named crash points
(``testing/faults.py``: after_begin_log / after_data_write /
after_end_log here; mid_data_write / mid_vacuum_delete at the data
seams) let the test matrix kill the writer between any two protocol
steps and assert recovery.

Multi-process jobs (docs/MULTIHOST.md "collective symmetry doctrine"):
the metadata plane stays single-writer — only the coordinator
(``MeshRuntime.is_coordinator``, process 0) runs recovery, the OCC
begin/commit log writes (:func:`_publish_log`) and the latestStable
publish (:func:`_publish_latest_stable`), via
:meth:`Action._run_coordinated`; every other process runs the
data-plane replica (:meth:`Action._run_data_plane`): the same snapshot
+ validate discipline, then ``op()`` — whose exchange collectives and
``_global_written`` barrier every process must reach identically.
Three ABORT-AWARE rendezvous (:func:`_action_rendezvous`, a registered
``per-host-lane`` collective site: an allgather of per-process step
verdicts) order the protocol and make every one-sided failure a
job-wide typed error instead of a hang: workers snapshot only after
the coordinator's recovery repair (``recovered``), workers finish
validating before the coordinator's begin entry exists (``validate`` —
a worker must never see its own action's transient state; a no-op
verdict must be unanimous), and no worker enters the data plane before
the begin entry is durable (``begin`` — a crash mid-op must leave a
rollbackable transient tip, and a begin-write OCC loss aborts the
workers instead of stranding them). One action at a time per
multi-process job: the OCC retry loop is disabled on the coordinator
because a silent re-validate on one process would desynchronize the
rendezvous program.
"""

from __future__ import annotations

import abc
import time
from typing import Optional

from hyperspace_tpu.exceptions import (
    ConcurrentWriteException,
    HyperspaceException,
    NoChangesException,
)
from hyperspace_tpu.metadata.entry import IndexLogEntry
from hyperspace_tpu.metadata.log_manager import IndexLogManager
from hyperspace_tpu.obs import trace as obs_trace
from hyperspace_tpu.telemetry import HyperspaceEvent
from hyperspace_tpu.testing import faults


def _multiprocess() -> bool:
    import jax

    return jax.process_count() > 1


#: per-process step verdicts exchanged at each rendezvous
_STEP_FAIL, _STEP_PROCEED, _STEP_NOOP = 0, 1, 2


def _action_rendezvous(step: str, verdict: int) -> int:
    """Abort-aware cross-process rendezvous of the action protocol:
    allgather every process's verdict for ``step`` and return the
    unanimous one. Any process reporting failure — or a proceed/no-op
    disagreement — raises on EVERY process, so a one-sided exception
    (a begin-write OCC loss on the coordinator, a validate error on one
    worker) becomes a job-wide typed abort instead of peers blocking
    forever in a barrier. Registered in ``COLLECTIVE_SITES`` as
    ``per-host-lane``: same sequence position on every process, each
    carrying its own verdict payload. Callers guard with
    :func:`_multiprocess` — a single-process job has no peers to meet."""
    import numpy as np
    from jax.experimental import multihost_utils as mhu

    flags = np.asarray(
        mhu.process_allgather(np.asarray([verdict], dtype=np.int32))
    ).ravel()
    if (flags == _STEP_FAIL).any() or len(set(flags.tolist())) > 1:
        raise ConcurrentWriteException(
            f"multi-process action aborted at step {step!r}: per-process "
            f"verdicts {flags.tolist()} (0=failed, 1=proceed, 2=no-op)"
        )
    return int(flags[0])


def _publish_log(log_manager: IndexLogManager, log_id: int, entry) -> bool:
    """Coordinator-gated OCC log write (``COLLECTIVE_SITES``): the
    operation log has exactly one writer per action — on a multi-process
    job only the coordinator ever reaches this seam."""
    return log_manager.write_log(log_id, entry)


def _publish_latest_stable(log_manager: IndexLogManager, log_id: int) -> bool:
    """Coordinator-gated latestStable pointer publish — the same
    single-writer metadata seam as the log entries themselves."""
    return log_manager.create_latest_stable_log(log_id)


class Action(abc.ABC):
    transient_state: str = ""
    final_state: str = ""

    def __init__(self, session, log_manager: IndexLogManager):
        self.session = session
        self.log_manager = log_manager
        self.base_id: int = log_manager.get_latest_id() or 0

    # -- protocol pieces ----------------------------------------------------
    def validate(self) -> None:
        """Raise HyperspaceException on an illegal state, or
        NoChangesException to make the action a no-op."""

    @abc.abstractmethod
    def op(self) -> None:
        """The data-plane work (device pipeline / file IO)."""

    @abc.abstractmethod
    def log_entry(self) -> IndexLogEntry:
        """The final log entry content (state is stamped by run())."""

    def begin_log_entry(self) -> IndexLogEntry:
        """Entry written at begin; defaults to log_entry(). Actions whose
        content only exists after op() (create/refresh) override this."""
        return self.log_entry()

    def event(self, success: bool, message: str = "") -> Optional[HyperspaceEvent]:
        return None

    def _resnapshot(self) -> None:
        """Re-read every log-derived member off the CURRENT tip.

        ``__init__`` snapshots ``base_id`` (and, in subclasses, the
        previous entry / version dir / tracker), but an action may run
        long after construction — and the OCC retry loop re-enters here
        after a collision. Subclasses that cache more than ``base_id``
        extend this; nothing outside ``run()`` may rely on the
        construction-time snapshot."""
        self.base_id = self.log_manager.get_latest_id() or 0

    # -- driver (Action.run:84-105 + recovery/retry) ------------------------
    def run(self) -> None:
        """The protocol under one ROOT span, ``action.<Class>``: the
        action's account, recorded whatever ``hyperspace.obs.enabled``
        says (that switch gates the serve plane) and finished whatever
        the outcome, so every action is explainable after the fact
        (docs/observability.md). Its DIRECT children are the protocol
        steps — ``validate`` (recovery repair + re-snapshot +
        ``validate()``), ``begin_log``, ``log_entry``, ``log_commit``,
        ``publish_event`` — and whatever stages ``op()`` records through
        ``covering_build.stage`` (a covering create: ``resolve``,
        ``scan``, ``hash_shuffle``, ``dict_probe``, ``sort``, ``write``,
        ``sidecar_capture``). ``op()`` itself has no span: what the
        root's children leave uncovered IS the unnamed time
        (``root.duration_s - root.children_union_s()``). The root's
        counter ``cpu_s`` is the process's CPU seconds over the action
        (``covering_build.stage`` puts the same on every stage)."""
        # configure, not just set_enabled: action-only processes (build
        # workers with no frontend) must still honor the trace bounds
        obs_trace.configure(self.session.conf)
        index_name = getattr(self, "index_name", "") or getattr(
            getattr(self, "index_config", None), "index_name", ""
        )
        root = obs_trace.root(
            f"action.{type(self).__name__}", always=True, index=str(index_name)
        )
        cpu0 = time.process_time_ns()
        with obs_trace.activate(root):
            try:
                self._run_protocol()
                root.set("status", "ok")
            except BaseException:
                root.set("status", "failed")
                raise
            finally:
                # the process's CPU seconds over the action, all threads:
                # beside the root's seconds, the cores the action kept busy
                root.set(
                    "cpu_s", round((time.process_time_ns() - cpu0) / 1e9, 6)
                )
                root.finish()

    def _run_protocol(self) -> None:
        from hyperspace_tpu.metadata import recovery

        if _multiprocess():
            if self.session.runtime.is_coordinator:
                self._run_coordinated()
            else:
                self._run_data_plane()
            return
        conf = self.session.conf
        recovery_on = conf.recovery_enabled
        attempts = conf.recovery_retry_max_attempts if recovery_on else 1
        backoff = conf.recovery_retry_backoff_ms / 1000.0
        lease_ms = conf.recovery_lease_ms
        owner = recovery.new_owner_id()
        begin = None
        for attempt in range(1, attempts + 1):
            if attempt > 1 and backoff > 0:
                time.sleep(backoff * (1 << (attempt - 2)))
            # fix a dead writer's leavings BEFORE snapshotting: a
            # stranded transient tip rolls back (appending an entry), a
            # stale latestStable pointer heals — then the snapshot below
            # sees the repaired log
            try:
                with obs_trace.span("validate"):
                    if recovery_on:
                        recovery.ensure_recovered(self.log_manager, lease_ms)
                    self._resnapshot()
                    self.validate()
            except NoChangesException:
                self._log_event(True, "No-op action")
                return
            with obs_trace.span("begin_log"):
                begin = self.begin_log_entry().with_state(self.transient_state)
                if recovery_on:
                    recovery.stamp_lease(begin, owner, lease_ms)
                begin.id = self.base_id + 1
                published = _publish_log(
                    self.log_manager, self.base_id + 1, begin
                )
            if published:
                break
            if attempt >= attempts:
                raise ConcurrentWriteException(
                    f"Another operation is in progress (log id "
                    f"{self.base_id + 1} already exists after {attempts} "
                    f"attempts)"
                )
        faults.crash("after_begin_log", type(self).__name__)
        heartbeat = None
        if recovery_on:
            heartbeat = recovery.LeaseHeartbeat(
                self.log_manager, self.base_id + 1, begin, owner, lease_ms
            ).start()
        try:
            self.op()
            faults.crash("after_data_write", type(self).__name__)
            with obs_trace.span("log_entry"):
                final = self.log_entry().with_state(self.final_state)
                final.id = self.base_id + 2
            with obs_trace.span("log_commit"):
                if not _publish_log(self.log_manager, self.base_id + 2, final):
                    # the end id exists already: a cancel()/recovery
                    # rolled our transient entry back under us — the
                    # data work must not be published over their write
                    raise ConcurrentWriteException(
                        f"Concurrent write at log id {self.base_id + 2}"
                    )
                faults.crash("after_end_log", type(self).__name__)
                _publish_latest_stable(self.log_manager, self.base_id + 2)
        except Exception as e:
            self._log_event(False, str(e))
            raise
        finally:
            # stopped on every in-process exit, incl. SimulatedCrash —
            # mirroring reality: when the process dies the heartbeat
            # thread dies with it, and the lease starts aging
            if heartbeat is not None:
                heartbeat.stop()
        with obs_trace.span("publish_event"):
            self._publish_fleet_event(final)
            self._log_event(True)

    def _rendezvous_step(self, step: str, fn) -> int:
        """Run one protocol step locally, then rendezvous on its
        verdict. The local exception (if any) wins over the collective
        abort, so the failing process reports its own root cause while
        its peers get the typed ConcurrentWriteException instead of
        blocking forever."""
        verdict, err = _STEP_PROCEED, None
        try:
            fn()
        except NoChangesException:
            verdict = _STEP_NOOP
        # deliberate catch-all: the verdict must reach the peers (they
        # are entering the same allgather) BEFORE this process unwinds
        except Exception as e:  # hslint: disable=HS402
            verdict, err = _STEP_FAIL, e
        try:
            return _action_rendezvous(step, verdict)
        except ConcurrentWriteException:
            if err is not None:
                raise err
            raise

    def _run_coordinated(self) -> None:
        """The coordinator side of a multi-process action: the
        single-writer metadata plane plus the shared data plane, with an
        abort-aware rendezvous at each protocol step (module
        docstring). ONE begin-write attempt — an OCC loss aborts the
        whole job symmetrically at the ``begin`` rendezvous rather than
        silently re-validating out of sync with the workers (one action
        at a time per multi-process job)."""
        from hyperspace_tpu.metadata import recovery

        conf = self.session.conf
        recovery_on = conf.recovery_enabled
        lease_ms = conf.recovery_lease_ms
        owner = recovery.new_owner_id()

        def repair():
            # a dead writer's leavings repair BEFORE anyone snapshots:
            # the rendezvous orders every worker's snapshot after this
            if recovery_on:
                recovery.ensure_recovered(self.log_manager, lease_ms)

        self._rendezvous_step("recovered", repair)

        def snapshot_validate():
            with obs_trace.span("validate"):
                self._resnapshot()
                self.validate()

        if self._rendezvous_step("validate", snapshot_validate) == _STEP_NOOP:
            self._log_event(True, "No-op action")
            return

        begin_box = []

        def begin_write():
            # only now may the transient entry appear — every worker
            # has finished validating (the rendezvous above), so none
            # can mistake our own begin entry for a concurrent writer
            with obs_trace.span("begin_log"):
                begin = self.begin_log_entry().with_state(self.transient_state)
                if recovery_on:
                    recovery.stamp_lease(begin, owner, lease_ms)
                begin.id = self.base_id + 1
                if not _publish_log(self.log_manager, self.base_id + 1, begin):
                    raise ConcurrentWriteException(
                        f"Another operation is in progress (log id "
                        f"{self.base_id + 1} already exists)"
                    )
            begin_box.append(begin)

        self._rendezvous_step("begin", begin_write)
        heartbeat = None
        if recovery_on:
            heartbeat = recovery.LeaseHeartbeat(
                self.log_manager, self.base_id + 1, begin_box[0], owner,
                lease_ms,
            ).start()
        try:
            self.op()
            with obs_trace.span("log_entry"):
                final = self.log_entry().with_state(self.final_state)
                final.id = self.base_id + 2
            with obs_trace.span("log_commit"):
                if not _publish_log(self.log_manager, self.base_id + 2, final):
                    raise ConcurrentWriteException(
                        f"Concurrent write at log id {self.base_id + 2}"
                    )
                _publish_latest_stable(self.log_manager, self.base_id + 2)
        except Exception as e:
            self._log_event(False, str(e))
            raise
        finally:
            if heartbeat is not None:
                heartbeat.stop()
        # coordinator-only, like every other metadata-plane write: the
        # fanout is plain file I/O, one publisher per action
        with obs_trace.span("publish_event"):
            self._publish_fleet_event(final)
            self._log_event(True)

    def _run_data_plane(self) -> None:
        """The non-coordinator replica of :meth:`_run_coordinated`: the
        identical rendezvous program and the identical ``op()``
        collective program, but NO log writes, no recovery, no lease —
        the coordinator owns the metadata plane (ROADMAP item 4; this
        process already receives the global file list through
        ``_global_written``'s barrier + union listing)."""
        self._rendezvous_step("recovered", lambda: None)

        def snapshot_validate():
            # ordered AFTER the coordinator's recovery repair by the
            # rendezvous above: both sides validate the repaired log
            with obs_trace.span("validate"):
                self._resnapshot()
                self.validate()

        if self._rendezvous_step("validate", snapshot_validate) == _STEP_NOOP:
            self._log_event(True, "No-op action")
            return
        self._rendezvous_step("begin", lambda: None)
        try:
            self.op()
        except Exception as e:
            self._log_event(False, str(e))
            raise
        self._log_event(True)

    def _publish_fleet_event(self, entry: Optional[IndexLogEntry]) -> None:
        """Fan the committed action out to peer serve frontends
        (``serve/bus.py``; no-op outside fleet mode, never raises — the
        commit already happened, a failed fanout only costs peers a lazy
        re-read)."""
        if not self.session.conf.fleet_enabled:
            return
        from hyperspace_tpu.serve import bus

        bus.publish_action_event(
            self.session,
            getattr(self, "index_name", ""),
            self.log_manager.index_path,
            type(self).__name__,
            entry,
        )

    def _log_event(self, success: bool, message: str = "") -> None:
        ev = self.event(success, message)
        if ev is not None:
            self.session.event_logging.log_event(ev)
