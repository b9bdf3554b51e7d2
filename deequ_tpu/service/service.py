"""VerificationService: the always-on, multi-tenant facade.

Composition (docs/SERVICE.md has the architecture picture):

- ``submit()`` validates quotas, wraps the suite in a ``RunTicket``
  (deadline budget pinned at submit — queue wait burns it, matching
  the admission controller), and returns a ``RunHandle``;
- the ``Scheduler``'s workers pop by priority and drive the run
  through ``VerificationSuite.do_verification_run`` — i.e. through
  the runner's admission layer (``max_concurrent_runs`` +
  ``memory_watermark_bytes`` still gate device admission underneath;
  the service NEVER calls ``engine.run_scan`` directly, enforced by
  tools/telemetry_lint.py);
- the shared ``DatasetCache`` hands every run of the same table the
  same resident handle (one ``device_put`` for N tenants), pinned for
  the run's duration;
- ``warmup()`` precompiles the submitted suites' fused plans at
  startup via the ``tools/warmup.py`` machinery and records the warmed
  plan tokens in the ``PlanCache`` ledger, so steady state shows zero
  recompiles.

Shutdown: ``stop(drain=True)`` finishes queued work; ``drain(reason)``
(also wired to SIGTERM when ``start(install_sigterm=True)``) cancels
QUEUED runs cleanly while RUNNING runs finish under the engine's
graceful-shutdown supervision — checkpointed, partial metrics, the
same contract as a direct bounded run.
"""

from __future__ import annotations

import collections
import pickle
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence

from deequ_tpu.engine.deadline import (
    MonotonicClock,
    RunBudget,
    shutdown_token,
)
from deequ_tpu.engine.subproc import CrashLoopError, IsolatedRunner
from deequ_tpu.io.state_provider import ScanCheckpointer
from deequ_tpu.service.caches import DatasetCache, PlanCache
from deequ_tpu.service.journal import RunJournal
from deequ_tpu.service.queue import (
    Priority,
    QuotaExceeded,
    RunHandle,
    RunQueue,
    RunState,
    RunTicket,
)
from deequ_tpu.service.fleet import epoch_fence_check
from deequ_tpu.service.preempt import run_cancel_token
from deequ_tpu.service.scheduler import Scheduler
from deequ_tpu.telemetry import get_telemetry


class ServiceOverloaded(RuntimeError):
    """A BATCH submission was shed at the edge (queue depth or crash
    rate over the ``service_shed_*`` thresholds). ``retry_after_s`` is
    the caller's resubmission hint — failing FAST with a hint beats
    accepting work that will deadline-expire silently in the queue."""

    def __init__(self, message: str, *, retry_after_s: float):
        super().__init__(message)
        self.retry_after_s = max(0.0, float(retry_after_s))


@dataclass
class RunRequest:
    """One suite submission. ``dataset_key`` + ``dataset_factory``
    address the shared dataset cache (same key -> same resident
    handle); pass a ``dataset`` directly to bypass sharing (it becomes
    a single-use factory keyed by object id)."""

    tenant: str
    checks: Sequence[Any]
    dataset_key: Optional[str] = None
    dataset_factory: Optional[Callable[[], Any]] = None
    dataset: Optional[Any] = None
    required_analyzers: Sequence[Any] = ()
    priority: int = Priority.STANDARD
    deadline_s: Optional[float] = None
    metrics_repository: Any = None
    result_key: Any = None
    #: egress.RowLevelSink — stream this run's row-level outcomes to a
    #: clean/quarantine parquet split (docs/EGRESS.md). Sink runs never
    #: coalesce (the artifact is per-run) but otherwise ride the full
    #: resilience stack: they checkpoint/resume through the durable
    #: span segments, execute in the spawn child under crash isolation
    #: (the child writes the artifact dir directly and streams egress
    #: progress frames back), and are preemptible when the service has
    #: a checkpoint path (docs/EGRESS.md "Durable egress").
    row_level_sink: Any = None
    #: explicit device-footprint estimate (bytes) for the elastic
    #: placement policy; None = derive from ``dataset`` at admit when
    #: one was passed (factory-only submissions place at the policy's
    #: default slice unless this is set)
    estimated_bytes: Optional[int] = None

    def __post_init__(self):
        if self.dataset is not None and self.dataset_factory is None:
            ds = self.dataset
            self.dataset_factory = lambda: ds
            if self.dataset_key is None:
                # content-derived default: two submissions of the SAME
                # in-memory table share the cache entry and may
                # coalesce — an id()-based key defeated both (every
                # rebuilt Dataset object was its own cache universe)
                try:
                    self.dataset_key = f"dataset-{ds.fingerprint()}"
                except Exception:  # noqa: BLE001 — unfingerprintable
                    self.dataset_key = f"dataset-{id(ds):x}"
        if self.dataset_key is None or self.dataset_factory is None:
            raise ValueError(
                "RunRequest needs dataset_key + dataset_factory "
                "(or a dataset)"
            )


class VerificationService:
    """Long-lived multi-tenant verification daemon. All knobs default
    from ``config.options()`` (service_* options); ``clock`` is
    injectable for fake-time tests and drives every scheduling
    decision."""

    def __init__(
        self,
        workers: Optional[int] = None,
        interactive_reserve: Optional[int] = None,
        clock: Any = None,
        dataset_watermark_bytes: Optional[int] = None,
        tenant_max_pending: Optional[int] = None,
        tenant_max_active: Optional[int] = None,
        execute: Optional[Callable[[RunTicket], Any]] = None,
        journal_dir: Optional[str] = None,
        isolated: Optional[bool] = None,
        shed_queue_depth: Optional[int] = None,
        shed_crash_rate: Optional[int] = None,
        shed_crash_window_s: Optional[float] = None,
        coalesce: Optional[bool] = None,
        coalesce_window_s: Optional[float] = None,
        coalesce_max_members: Optional[int] = None,
        execute_group: Optional[
            Callable[[List[RunTicket]], List[Any]]
        ] = None,
        elastic_placement: Optional[bool] = None,
        placer: Optional[Any] = None,
        trace: Optional[bool] = None,
        metrics_port: Optional[int] = None,
        slo_objectives: Optional[str] = None,
        preemption: Optional[bool] = None,
        autoscale: Optional[bool] = None,
        process_label: str = "",
        fleet_dir: Optional[str] = None,
        replica_id: Optional[str] = None,
        adopt_resolve: Optional[Callable[[Dict[str, Any]], Any]] = None,
    ):
        import os

        from deequ_tpu import config

        opts = config.options()
        self.clock = clock or MonotonicClock()
        # end-to-end tracing (docs/OBSERVABILITY.md "Tracing"): when on,
        # the queue mints a TraceContext per submission and the
        # scheduler/engine/spawn layers hang the run's span tree off it
        self.trace_enabled = bool(
            opts.service_trace if trace is None else trace
        )
        self.process_label = process_label
        # live plane: explicit metrics_port serves (0 = ephemeral bind);
        # None defers to config, where 0 means NO endpoint thread
        self._metrics_port: Optional[int] = (
            int(metrics_port)
            if metrics_port is not None
            else (
                int(opts.service_metrics_port)
                if opts.service_metrics_port > 0
                else None
            )
        )
        self.metrics_server: Optional[Any] = None
        # per-class/per-tenant latency SLOs over the queue-wait
        # histograms; "" = no tracker, no snapshot persistence
        slo_spec = (
            opts.service_slo_objectives
            if slo_objectives is None
            else slo_objectives
        )
        self.slo: Optional[Any] = None
        if slo_spec:
            from deequ_tpu.telemetry import SloTracker, parse_slo_objectives

            objectives = parse_slo_objectives(slo_spec)
            if objectives:
                self.slo = SloTracker(objectives)
        journal_dir = (
            journal_dir
            if journal_dir is not None
            else opts.service_journal_dir
        )
        self.journal: Optional[RunJournal] = (
            RunJournal(journal_dir) if journal_dir else None
        )
        self._checkpoint_path: Optional[str] = (
            journal_dir.rstrip("/") + "/checkpoints" if journal_dir else None
        )
        # fleet failover (docs/SERVICE.md "Fleet failover"): a shared
        # fleet dir turns this replica into a fleet member — heartbeat
        # lease, peer watch, orphan adoption, epoch fencing. Requires a
        # journal (the journal IS what a peer adopts); checkpoints move
        # to the SHARED fleet dir so an adopted run's durable cursors
        # are readable by whichever replica resumes it.
        fleet_dir = (
            fleet_dir if fleet_dir is not None else opts.service_fleet_dir
        )
        self.fleet: Optional[Any] = None
        self._adopt_resolve = adopt_resolve
        self._adopted_handles: List[RunHandle] = []
        #: journal dirs whose adoption replay is on the current call
        #: stack — finishing a dead adopter's intents re-enters
        #: ``_adopt_replica``, and a cyclic intent graph (two dead
        #: adopters pointing at each other) must not recurse forever
        self._adopting: set = set()
        if fleet_dir and self.journal is not None:
            from deequ_tpu.service.fleet import FleetSupervisor

            self._checkpoint_path = (
                fleet_dir.rstrip("/") + "/checkpoints"
            )
            replica = (
                replica_id
                or opts.service_fleet_replica
                or f"replica-{os.getpid()}"
            )
            self.fleet = FleetSupervisor(
                fleet_dir,
                replica,
                journal_dir=journal_dir,
                clock=self.clock,
                heartbeat_s=opts.service_fleet_heartbeat_s,
                lease_timeout_s=opts.service_fleet_lease_timeout_s,
                poison_replicas=opts.service_fleet_poison_replicas,
                on_adopt=self._adopt_replica,
                on_adopt_intent=self._journal_adopt_intent,
                on_adopt_lost=self._journal_adopt_lost,
            )
            self.journal.record_epoch(
                replica, self.fleet.epoch, reason="register"
            )
        self.isolated = (
            bool(opts.isolated_execution) if isolated is None else bool(isolated)
        )
        self.shed_queue_depth = int(
            opts.service_shed_queue_depth
            if shed_queue_depth is None
            else shed_queue_depth
        )
        self.shed_crash_rate = int(
            opts.service_shed_crash_rate
            if shed_crash_rate is None
            else shed_crash_rate
        )
        self.shed_crash_window_s = float(
            opts.service_shed_crash_window_s
            if shed_crash_window_s is None
            else shed_crash_window_s
        )
        self._crash_times: collections.deque = collections.deque()
        self._crash_lock = threading.Lock()
        watermark = (
            dataset_watermark_bytes
            if dataset_watermark_bytes is not None
            else (
                opts.service_dataset_watermark_bytes
                or opts.device_cache_bytes
            )
        )
        self.datasets = DatasetCache(watermark_bytes=watermark)
        self.plans = PlanCache()
        self.queue = RunQueue(
            clock=self.clock,
            tenant_max_pending=(
                tenant_max_pending
                if tenant_max_pending is not None
                else opts.service_tenant_max_pending
            ),
            tenant_max_active=(
                tenant_max_active
                if tenant_max_active is not None
                else opts.service_tenant_max_active
            ),
            trace_enabled=self.trace_enabled,
            process_label=self.process_label,
        )
        # scan coalescing (docs/SERVICE.md "Scan coalescing"): opt-in;
        # the group executor defaults to the service's own ONLY when
        # the solo executor is also the service's own — an injected
        # `execute=` stub (fake-clock tests) keeps strict solo
        # semantics unless it injects `execute_group=` too
        coalesce_on = bool(
            opts.service_coalesce if coalesce is None else coalesce
        )
        if execute_group is None and execute is None:
            execute_group = self._execute_group
        self.coalesce_policy = None
        if coalesce_on and execute_group is not None:
            from deequ_tpu.service.coalesce import CoalescePolicy

            self.coalesce_policy = CoalescePolicy(
                enabled=True,
                window_s=float(
                    opts.service_coalesce_window_s
                    if coalesce_window_s is None
                    else coalesce_window_s
                ),
                max_members=int(
                    opts.service_coalesce_max_members
                    if coalesce_max_members is None
                    else coalesce_max_members
                ),
            )
        # elastic device placement (docs/SERVICE.md "Elastic
        # placement"): opt-in like coalescing; an injected placer wins
        # over the flag (fake-pool tests)
        elastic_on = bool(
            opts.service_elastic_placement
            if elastic_placement is None
            else elastic_placement
        )
        self.placer = placer
        if self.placer is None and elastic_on:
            from deequ_tpu.service.placement import ElasticPlacer

            self.placer = ElasticPlacer(clock=self.clock)
        # checkpoint-conserving preemption (docs/SERVICE.md "Preemption
        # and autoscaling"): opt-in; OFF (the default) keeps the
        # scheduler/queue paths bit-identical to the pre-preemption
        # service — no controller, no per-attempt tokens, no skips
        preempt_on = bool(
            opts.service_preemption if preemption is None else preemption
        )
        self.preemption = None
        if preempt_on:
            from deequ_tpu.service.preempt import PreemptionController

            self.preemption = PreemptionController(
                clock=self.clock,
                max_preemptions_per_run=(
                    opts.service_preempt_max_per_run
                ),
                # sink runs are admissible victims only when their
                # egress cursor is durable (checkpointing service)
                durable_egress=self._checkpoint_path is not None,
            )
        self.scheduler = Scheduler(
            self.queue,
            execute if execute is not None else self._execute,
            workers=(
                workers if workers is not None else opts.service_workers
            ),
            interactive_reserve=(
                interactive_reserve
                if interactive_reserve is not None
                else opts.service_interactive_reserve
            ),
            clock=self.clock,
            execute_group=execute_group,
            coalesce=self.coalesce_policy,
            placer=self.placer,
            slo_tenants=(
                self.slo.tenant_objectives().keys()
                if self.slo is not None
                else None
            ),
            preemption=self.preemption,
            on_preempted=self._journal_preempted,
            on_resumed=self._journal_resumed,
            fence=(
                self._scheduler_fence if self.fleet is not None else None
            ),
        )
        # queue-driven autoscaling: the control loop over the per-class
        # queue-wait histograms and SLO burn (service/autoscale.py)
        autoscale_on = bool(
            opts.service_autoscale if autoscale is None else autoscale
        )
        self.autoscaler: Optional[Any] = None
        if autoscale_on:
            from deequ_tpu.service.autoscale import AutoscaleController

            self.autoscaler = AutoscaleController(
                self.scheduler,
                clock=self.clock,
                interval_s=opts.service_autoscale_interval_s,
                min_workers=opts.service_autoscale_min_workers,
                max_workers=opts.service_autoscale_max_workers,
                target_interactive_p99_s=(
                    opts.service_autoscale_target_interactive_p99_s
                ),
                slo=self.slo,
            )
        self._run_seq = 0
        self._handles: Dict[str, RunHandle] = {}
        self._handles_lock = threading.Lock()
        self._uninstall_sigterm: Optional[Callable[[], None]] = None
        self._sigterm_watcher: Optional[threading.Thread] = None
        self._watcher_stop = threading.Event()

    # -- lifecycle ------------------------------------------------------

    def start(self, install_sigterm: bool = False) -> "VerificationService":
        if install_sigterm:
            from deequ_tpu.engine.deadline import install_graceful_shutdown

            self._uninstall_sigterm = install_graceful_shutdown()
            self._watcher_stop.clear()
            # lint-ok: thread-discipline: service-scoped watcher joined
            # in stop(); not part of a scan, so the ingest probe (which
            # tier-1 asserts empty between scans) must not see it
            self._sigterm_watcher = threading.Thread(
                target=self._watch_shutdown,
                daemon=True,
                name="deequ-tpu-service-shutdown-watch",
            )
            self._sigterm_watcher.start()
        self.scheduler.start()
        if self.autoscaler is not None:
            self.autoscaler.start()
        if self.fleet is not None:
            self.fleet.start()
        if self._metrics_port is not None and self.metrics_server is None:
            from deequ_tpu.telemetry import serve_metrics

            self.metrics_server = serve_metrics(
                self._metrics_port, health=self.health
            )
        get_telemetry().event(
            "service_started",
            workers=self.scheduler.workers,
            interactive_reserve=self.scheduler.interactive_reserve,
        )
        return self

    def _watch_shutdown(self) -> None:
        token = shutdown_token()
        while not self._watcher_stop.is_set():
            # Event.wait on the token — event-driven, not a time poll;
            # the short timeout only lets a stopped service reclaim the
            # watcher thread
            if token.wait(timeout=0.1):
                self.drain(token.reason or "shutdown requested")
                return

    def stop(
        self, drain: bool = True, timeout: Optional[float] = 30.0
    ) -> None:
        """Shut the service down. ``drain=True`` finishes everything
        already queued first; ``drain=False`` cancels queued runs
        (running ones still finish — workers are cooperative, not
        preemptive)."""
        if drain:
            self.wait_idle(timeout=timeout)
        self.queue.close()
        if not drain:
            self.queue.drain_queued("service stopping")
        self._watcher_stop.set()
        if self.autoscaler is not None:
            self.autoscaler.stop()
        self.scheduler.stop(timeout=timeout)
        if self.fleet is not None:
            # retire the lease only AFTER the scheduler drain: peers
            # skip a retired chain, so retiring while runs are still
            # in flight would forfeit failover coverage for exactly
            # the crash-during-shutdown the journal otherwise survives
            self.fleet.stop(retire=True)
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None
        if self._uninstall_sigterm is not None:
            self._uninstall_sigterm()
            self._uninstall_sigterm = None
        get_telemetry().event("service_stopped", drained=drain)

    def drain(self, reason: str = "shutdown requested") -> int:
        """SIGTERM semantics: refuse new work, cancel QUEUED runs with
        ``reason``, let RUNNING runs finish under the engine's
        supervision (checkpoint + partial metrics). Returns the number
        of queued runs drained."""
        self.queue.close()
        drained = self.queue.drain_queued(reason)
        get_telemetry().event(
            "service_drained", reason=reason, drained=drained
        )
        return drained

    def wait_idle(self, timeout: Optional[float] = None) -> bool:
        """Block until nothing is queued or running (best-effort;
        returns False on timeout). Poll cadence comes from the clock so
        fake-time tests spin fast."""
        deadline = (
            None if timeout is None else self.clock.now() + timeout
        )
        while True:
            snap = self.queue.snapshot()
            active = sum(snap["active_by_tenant"].values())
            if snap["depth"] == 0 and active == 0:
                return True
            if deadline is not None and self.clock.now() > deadline:
                return False
            self.queue.wait_event(self.clock.queue_poll_s())

    # -- submission -----------------------------------------------------

    def submit(self, request: RunRequest) -> RunHandle:
        """Queue one suite run; returns immediately with the handle.
        Raises ``QuotaExceeded`` when the tenant is over its pending
        quota and ``ServiceOverloaded`` when a BATCH submission hits a
        shed threshold. The deadline budget starts NOW — time spent
        queued counts against it."""
        self._maybe_shed(request)
        with self._handles_lock:
            self._run_seq += 1
            run_id = f"run-{self._run_seq}"
        return self._admit(request, run_id)

    def _admit(
        self, request: RunRequest, run_id: str, journal: bool = True
    ) -> RunHandle:
        """Build the handle/ticket for ``run_id`` and push it. Journal
        ordering is write-ahead: the submitted record lands durably
        BEFORE the ticket can be scheduled, so a crash between the two
        loses an unacknowledged submission, never an acknowledged one."""
        if not epoch_fence_check(self.fleet):
            # a fenced zombie must not ACCEPT work either: its journal
            # now belongs to the adopter, so an admission here would be
            # an unadoptable run
            from deequ_tpu.service.fleet import FencedReplica

            raise FencedReplica(
                "this replica's lease epoch was superseded by an "
                "adopter; restart the process to rejoin the fleet"
            )
        handle = RunHandle(run_id, request.tenant, request.priority)
        budget = None
        if request.deadline_s is not None:
            budget = RunBudget(
                deadline_s=float(request.deadline_s), clock=self.clock
            )
        surface = None
        if self.coalesce_policy is not None:
            # submit-time capture: the coalescer only groups tickets
            # whose config-derived plan-key surfaces are EQUAL, so a
            # config.configure(...) change between two submissions
            # can't smuggle differently-planned runs into one scan
            from deequ_tpu.engine.scan import coalesce_key_surface

            surface = coalesce_key_surface()
        estimated = 0
        if request.estimated_bytes is not None:
            estimated = max(0, int(request.estimated_bytes))
        elif self.placer is not None and request.dataset is not None:
            # submit-time footprint for the placement policy — the SAME
            # coarse estimate the admission watermark gates on (module
            # function: the service never builds an engine here)
            from deequ_tpu.engine.scan import estimated_run_bytes

            try:
                estimated = int(estimated_run_bytes(request.dataset))
            except Exception:  # noqa: BLE001 — estimate is advisory
                estimated = 0
        ticket = RunTicket(
            seq=0,  # assigned by the queue
            handle=handle,
            payload=request,
            budget=budget,
            estimated_bytes=estimated,
            dataset_key=request.dataset_key,
            coalesce_surface=surface,
        )
        tm = get_telemetry()
        if self.journal is not None:
            if journal:
                self.journal.record_submitted(
                    run_id,
                    tenant=request.tenant,
                    priority=int(request.priority),
                    deadline_s=request.deadline_s,
                    dataset_key=request.dataset_key,
                )
            handle.on_terminal = self._journal_terminal
        try:
            self.queue.push(ticket)  # raises QuotaExceeded pre-registration
        except QuotaExceeded:
            if self.journal is not None:
                self.journal.record_terminal(
                    run_id, RunState.REJECTED, reason="tenant quota"
                )
            raise
        with self._handles_lock:
            self._handles[run_id] = handle
        tm.counter("service.submitted").inc()
        tm.counter(f"service.tenant.{request.tenant}.submitted").inc()
        tm.event(
            "service_run_submitted",
            run_id=run_id,
            tenant=request.tenant,
            priority=Priority.name(request.priority),
            dataset_key=request.dataset_key,
            deadline_s=request.deadline_s,
        )
        if (
            self.preemption is not None
            and request.priority == Priority.INTERACTIVE
        ):
            # the admission IS the demand signal: if no worker (or no
            # device slice) can serve this run, the youngest solo
            # BATCH run yields at its next batch boundary
            self.scheduler.note_interactive_demand(run_id)
        return handle

    # -- load shedding ---------------------------------------------------

    def _maybe_shed(self, request: RunRequest) -> None:
        """Reject a BATCH submission fast when the service is drowning
        (deep queue or crashing children) — INTERACTIVE/STANDARD work is
        never shed, matching the scheduler's reserve semantics."""
        if request.priority < Priority.BATCH:
            return
        reason = None
        retry_after = 0.0
        if self.shed_queue_depth > 0:
            depth = self.queue.depth()
            if depth >= self.shed_queue_depth:
                reason = (
                    f"queue depth {depth} >= shed threshold "
                    f"{self.shed_queue_depth}"
                )
                # rough drain estimate: today's depth at one run per
                # worker-second — a HINT, not a promise
                retry_after = depth / max(1, self.scheduler.workers)
        if reason is None and self.shed_crash_rate > 0:
            now = self.clock.now()
            with self._crash_lock:
                while self._crash_times and (
                    now - self._crash_times[0] > self.shed_crash_window_s
                ):
                    self._crash_times.popleft()
                crashes = len(self._crash_times)
                oldest = self._crash_times[0] if self._crash_times else now
            if crashes >= self.shed_crash_rate:
                reason = (
                    f"{crashes} child crashes in the last "
                    f"{self.shed_crash_window_s:.0f}s"
                )
                retry_after = max(
                    0.0, self.shed_crash_window_s - (now - oldest)
                )
        if reason is None:
            return
        tm = get_telemetry()
        tm.counter("service.submissions_shed").inc()
        tm.event(
            "service_submission_shed",
            tenant=request.tenant,
            priority=Priority.name(request.priority),
            reason=reason,
            retry_after_s=retry_after,
        )
        raise ServiceOverloaded(
            f"service overloaded ({reason}); retry in {retry_after:.1f}s",
            retry_after_s=retry_after,
        )

    def _note_crash(self) -> None:
        with self._crash_lock:
            self._crash_times.append(self.clock.now())

    # -- journal hooks ---------------------------------------------------

    def _journal_terminal(self, handle: RunHandle) -> None:
        if self.journal is None:
            return
        if not epoch_fence_check(self.fleet):
            return  # the adopter owns this run's journal now
        state, error = handle.terminal_info()
        if state is None:
            return
        self.journal.record_terminal(
            handle.run_id,
            state,
            error=(
                f"{type(error).__name__}: {error}"[:500]
                if error is not None
                else None
            ),
        )

    def _journal_preempted(self, ticket: RunTicket, evidence: Any) -> None:
        """Write-ahead preemption record: lands BEFORE the ticket
        re-enters the queue, so a process death in between still sees
        the run as pending (and preempted) at recovery."""
        if self.journal is None:
            return
        if not epoch_fence_check(self.fleet):
            return
        self.journal.record_preempted(
            ticket.handle.run_id,
            reason=getattr(evidence, "reason", None),
            batch_index=int(getattr(evidence, "batch_index", 0) or 0),
            row_offset=int(getattr(evidence, "row_offset", 0) or 0),
            checkpointed=bool(getattr(evidence, "checkpointed", False)),
        )

    def _journal_resumed(self, ticket: RunTicket) -> None:
        if self.journal is None:
            return
        if not epoch_fence_check(self.fleet):
            return
        self.journal.record_resumed(
            ticket.handle.run_id, preemptions=int(ticket.preemptions)
        )

    # -- restart recovery ------------------------------------------------

    def recover(
        self,
        resolve: Optional[
            Callable[[str, Dict[str, Any]], Optional[RunRequest]]
        ] = None,
    ) -> List[RunHandle]:
        """Re-admit every journaled run that never reached a terminal
        state — call ONCE on a fresh service over the journal dir of a
        dead one, before accepting new traffic.

        Journal records are JSON (checks/datasets hold closures that do
        not serialize), so ``resolve(run_id, entry)`` rebuilds each
        ``RunRequest`` from the journaled fields (tenant, priority,
        deadline_s, dataset_key, started, last_checkpoint). Returning
        None declares the run unresolvable: it is journaled FAILED
        instead of silently dropped. Priority and deadline come from the
        JOURNAL (the submit-pinned envelope), not the resolver. Runs
        that already started resume mid-scan from their durable
        checkpoint cursors the moment they re-execute."""
        if self.journal is None:
            return []
        if not epoch_fence_check(self.fleet):
            return []
        tm = get_telemetry()
        pending = self.journal.pending_runs()
        # continue run numbering past every journaled id — a recovered
        # service must never mint a colliding run_id
        top = 0
        for run_id in pending:
            tail = run_id.rsplit("-", 1)[-1]
            if tail.isdigit():
                top = max(top, int(tail))
        with self._handles_lock:
            self._run_seq = max(self._run_seq, top)
        recovered: List[RunHandle] = []
        for run_id, entry in pending.items():
            request = resolve(run_id, entry) if resolve is not None else None
            if request is None:
                self.journal.record_terminal(
                    run_id,
                    RunState.FAILED,
                    error="unresolvable at recovery (no RunRequest)",
                )
                tm.event(
                    "service_run_unrecoverable",
                    run_id=run_id,
                    tenant=entry.get("tenant"),
                )
                continue
            if entry.get("priority") is not None:
                request.priority = int(entry["priority"])
            if entry.get("deadline_s") is not None:
                request.deadline_s = float(entry["deadline_s"])
            handle = self._admit(request, run_id, journal=False)
            recovered.append(handle)
            tm.event(
                "service_run_recovered",
                run_id=run_id,
                tenant=entry.get("tenant"),
                started=bool(entry.get("started")),
                last_checkpoint=entry.get("last_checkpoint"),
                preempted=bool(entry.get("preempted")),
                preempt_count=int(entry.get("preempt_count") or 0),
                # a re-admitted sink run resumes MID-ARTIFACT: its
                # durable span segments + egress cursor survive the
                # restart alongside the scan checkpoint
                egress=bool(request.row_level_sink is not None),
            )
        if recovered:
            tm.counter("service.runs_recovered").inc(len(recovered))
        if self.fleet is not None:
            # a restarted replica also finishes its own half-done
            # adoptions: an intent with no done record means a claim
            # CAS may have won without its replay completing — the
            # claimed chain is terminal and never re-polled, so this
            # is those runs' only road back
            for intent in self.journal.pending_adoptions():
                self._finish_adoption(self.journal, intent)
        self.journal.compact()
        return recovered

    # -- fleet adoption --------------------------------------------------

    def _scheduler_fence(self) -> bool:
        """Scheduler hook: True while this replica may finish runs."""
        return epoch_fence_check(self.fleet)

    def _journal_adopt_intent(self, adoption: Any) -> None:
        """FleetSupervisor ``on_adopt_intent`` hook, fired BEFORE the
        claim CAS: durably record in OUR journal which chain we are
        about to claim and where its journal lives. A claimed chain is
        terminal — nothing re-polls it — so without this write-ahead
        an adopter dying between the CAS win and the replay would
        strand the orphan's runs forever; with it, whoever adopts (or
        recovers) THIS journal finds the intent and finishes the
        adoption. Raising aborts the claim."""
        if not epoch_fence_check(self.fleet):
            from deequ_tpu.service.fleet import FencedReplica

            raise FencedReplica(
                "fenced: this replica must not claim peer chains"
            )
        self.journal.record_adoption_intent(
            adoption.replica, adoption.journal_dir, adoption.epoch
        )

    def _journal_adopt_lost(self, adoption: Any) -> None:
        """FleetSupervisor ``on_adopt_lost`` hook: another survivor
        won the claim CAS — close our intent so nobody replays a race
        we lost."""
        if not epoch_fence_check(self.fleet):
            return
        self.journal.record_adoption_done(
            adoption.replica, adoption.epoch, status="race_lost"
        )

    def _adopt_replica(self, adoption: Any) -> List[RunHandle]:
        """FleetSupervisor callback after WINNING the lease CAS on a
        dead peer's chain: replay the orphan journal's pending runs
        into OUR queue through the recover() resolve contract
        (``adopt_resolve(entry) -> RunRequest | None``).

        Ordering per run: (1) write-ahead ``submitted`` record in OUR
        journal under a fresh run id carrying ``adopted_from``, (2)
        admit, (3) mark the run ``adopted`` (terminal) in the ORPHAN
        journal. The whole replay runs under the adoption intent this
        replica journaled before the CAS (``_journal_adopt_intent``)
        and is closed by an ``adoption_done`` record at the end — an
        adopter dying ANYWHERE in between leaves a pending intent that
        its own adopter (or its restarted self, via ``recover()``)
        finishes: at-least-once across a double failure, exactly-once
        otherwise (the fence keeps the zombie original from ever
        double-persisting). A replica that finds itself fenced after
        the CAS win hands the claim back (``release_claim``) so the
        chain stays adoptable by a live survivor.

        Started runs resume from their durable cursors automatically:
        checkpoints live under the SHARED fleet dir keyed by plan
        token, not by replica or run id."""
        if not epoch_fence_check(self.fleet):
            self.fleet.release_claim(adoption.replica, adoption.epoch)
            return []
        if (
            adoption.journal_dir in self._adopting
            or adoption.journal_dir == self.journal.path
        ):
            # cyclic intent graph (dead adopters pointing at each
            # other) or a self-claim: nothing to replay that is not
            # already being replayed higher up this call stack
            self.fleet.release_claim(adoption.replica, adoption.epoch)
            return []
        self._adopting.add(adoption.journal_dir)
        try:
            return self._replay_orphan(adoption)
        finally:
            self._adopting.discard(adoption.journal_dir)

    def _replay_orphan(self, adoption: Any) -> List[RunHandle]:
        """The adoption replay body (see ``_adopt_replica`` for the
        ordering contract; the caller holds the re-entrancy guard and
        has already passed the epoch fence)."""
        if not epoch_fence_check(self.fleet):
            self.fleet.release_claim(adoption.replica, adoption.epoch)
            return []
        tm = get_telemetry()
        from deequ_tpu.service.journal import RunJournal as _Journal

        orphan = _Journal(adoption.journal_dir)
        orphan.record_epoch(
            self.fleet.replica_id,
            adoption.epoch,
            reason="adopted",
            stale_for_s=round(adoption.stale_for_s, 3),
        )
        adopted: List[RunHandle] = []
        for run_id, entry in orphan.pending_runs().items():
            # same key shape the isolated runner's breaker (and the
            # crash-loop ledger writes above) use
            plan_key = (
                f"dataset:{entry['dataset_key']}"
                if entry.get("dataset_key")
                else run_id
            )
            if self.fleet.quarantined(plan_key):
                # poison: this run already crashed enough DISTINCT
                # replicas — quarantine instead of walking the fleet
                tm.counter("service.fleet.poisoned_runs").inc()
                tm.event(
                    "fleet_run_poisoned",
                    run_id=run_id,
                    plan_key=plan_key,
                    replicas=self.fleet.crashed_replicas(plan_key),
                )
                orphan.record_terminal(
                    run_id,
                    RunState.FAILED,
                    error=(
                        "fleet poison quarantine: crashed "
                        f"{len(self.fleet.crashed_replicas(plan_key))} "
                        "distinct replicas"
                    ),
                )
                continue
            request = (
                self._adopt_resolve(entry)
                if self._adopt_resolve is not None
                else None
            )
            if request is None:
                orphan.record_terminal(
                    run_id,
                    RunState.FAILED,
                    error="unresolvable at adoption (no RunRequest)",
                )
                tm.event(
                    "service_run_unrecoverable",
                    run_id=run_id,
                    tenant=entry.get("tenant"),
                )
                continue
            if entry.get("priority") is not None:
                request.priority = int(entry["priority"])
            if entry.get("deadline_s") is not None:
                request.deadline_s = float(entry["deadline_s"])
            with self._handles_lock:
                self._run_seq += 1
                new_id = f"run-{self._run_seq}"
            self.journal.record_submitted(
                new_id,
                tenant=request.tenant,
                priority=int(request.priority),
                deadline_s=request.deadline_s,
                dataset_key=request.dataset_key,
                adopted_from=run_id,
                adopted_replica=adoption.replica,
            )
            handle = self._admit(request, new_id, journal=False)
            adopted.append(handle)
            orphan.record_terminal(
                run_id,
                "adopted",
                adopted_as=new_id,
                adopter=self.fleet.replica_id,
            )
            tm.counter("service.fleet.runs_adopted").inc()
            tm.event(
                "service_run_adopted",
                run_id=new_id,
                adopted_from=run_id,
                replica=adoption.replica,
                tenant=entry.get("tenant"),
                started=bool(entry.get("started")),
                last_checkpoint=entry.get("last_checkpoint"),
            )
        # finish the DEAD replica's own half-done adoptions: its
        # journal may hold intents with no done record — chains it
        # claimed whose replay never completed. Those chains are
        # terminally "adopted" and never re-polled, so this replay is
        # their runs' only road back.
        for intent in orphan.pending_adoptions():
            self._finish_adoption(orphan, intent)
        # close OUR intent for this chain: the replay is complete, a
        # later adopter of this journal has nothing left to finish
        self.journal.record_adoption_done(
            adoption.replica,
            adoption.epoch,
            status="adopted",
            runs=len(adopted),
        )
        # journal hygiene: the orphan log is now all-terminal — shrink
        # it (and our own) so the next scan is O(live runs)
        orphan.compact()
        self.journal.compact()
        with self._handles_lock:
            self._adopted_handles.extend(adopted)
        return adopted

    def _finish_adoption(
        self, journal: Any, intent: Dict[str, Any]
    ) -> None:
        """Complete a half-done adoption found in ``journal`` (ours at
        ``recover()``, a dead adopter's during replay): re-claim the
        nested orphan chain at ITS next epoch — the claim CAS keeps
        finishers unique however many replicas walk the same intent
        chain — and replay whatever runs are still pending in that
        journal (runs the dead adopter already re-admitted are
        terminal there and stay put). The intent is then closed in the
        journal that held it, which this replica now owns."""
        if not epoch_fence_check(self.fleet):
            return
        replica = str(intent.get("replica") or "")
        journal_dir = str(intent.get("journal_dir") or "")
        if not replica or not journal_dir:
            return
        if (
            replica != self.fleet.replica_id
            and journal_dir != self.journal.path
            and journal_dir not in self._adopting
        ):
            # re-claiming fires the full adoption cycle: our own
            # intent lands first, then the CAS, then _adopt_replica
            if self.fleet.adopt_chain(replica, journal_dir) is not None:
                get_telemetry().counter(
                    "service.fleet.adoptions_finished"
                ).inc()
        journal.record_adoption_done(
            replica,
            int(intent.get("epoch") or 0),
            status="finished",
            finisher=self.fleet.replica_id,
        )

    def adopted_runs(self) -> List[RunHandle]:
        """Handles of every run this replica adopted from dead peers."""
        with self._handles_lock:
            return list(self._adopted_handles)

    def handle(self, run_id: str) -> Optional[RunHandle]:
        with self._handles_lock:
            return self._handles.get(run_id)

    # -- warmup ---------------------------------------------------------

    def warmup(
        self,
        schema: Dict[str, str],
        suite: bool = True,
        nullable=(False, True),
        **kwargs,
    ) -> List[str]:
        """Precompile the fused plans production suites will need
        (tools/warmup.py machinery) and record the warmed plan tokens.
        Returns the tokens; after this, matching submissions execute
        with zero recompiles (the acceptance telemetry in
        examples/verification_service.py)."""
        warm_plans = _load_warm_plans()
        if self.placer is not None and "mesh_shapes" not in kwargs:
            # elastic placement: warm EVERY slice shape the policy can
            # choose, so a pool-pressure-driven resize never compiles
            shapes: List[int] = []
            ndev = 1
            while ndev <= self.placer.pool.max_slice:
                shapes.append(ndev)
                ndev *= 2
            kwargs["mesh_shapes"] = shapes
        report = warm_plans(
            schema, suite=suite, nullable=nullable, **kwargs
        )
        self.plans.note_warmed(report.get("tokens", []))
        return list(report.get("tokens", []))

    # -- the real executor ----------------------------------------------

    def _build_engine(self, lease: Any, run_id: str):
        """The per-run ``AnalysisEngine``, or None when neither a
        durable checkpoint path nor a placement lease calls for one.
        A leased run executes on its slice's mesh — the engine's
        placement feeds the shape-keyed plan cache, so every slice of
        the same size replays the warmed plan."""
        mesh = getattr(lease, "mesh", None) if lease is not None else None
        if self._checkpoint_path is None and mesh is None:
            return None
        from deequ_tpu.engine.scan import AnalysisEngine

        kwargs: Dict[str, Any] = {}
        if self._checkpoint_path is not None:
            kwargs["checkpointer"] = _JournalingCheckpointer(
                self._checkpoint_path, self.journal, run_id,
                fleet=self.fleet,
            )
        if mesh is not None:
            kwargs["mesh"] = mesh
        return AnalysisEngine(**kwargs)

    def _execute(self, ticket: RunTicket):
        request: RunRequest = ticket.payload
        if self.journal is not None and epoch_fence_check(self.fleet):
            self.journal.record_started(
                ticket.handle.run_id, tenant=request.tenant
            )
        return self._execute_solo(ticket)

    def _execute_solo(self, ticket: RunTicket):
        """Drive one already-journaled ticket (the solo path, and the
        per-member fallback of a failed superset scan)."""
        if self.isolated:
            payload = self._isolation_payload(ticket)
            if payload is not None:
                return self._execute_isolated(ticket, payload)
            get_telemetry().counter(
                "service.isolation_inline_fallbacks"
            ).inc()
            get_telemetry().event(
                "service_isolation_fallback",
                run_id=ticket.handle.run_id,
                reason="request does not pickle (closures in "
                "checks/dataset_factory); executing in-process",
            )
        return self._execute_inline(ticket)

    def _execute_inline(self, ticket: RunTicket):
        from deequ_tpu.verification.suite import VerificationSuite

        request: RunRequest = ticket.payload
        dataset, hit = self.datasets.lease(
            request.dataset_key, request.dataset_factory
        )
        get_telemetry().event(
            "service_dataset_leased",
            run_id=ticket.handle.run_id,
            dataset_key=request.dataset_key,
            cache_hit=hit,
        )
        engine = self._build_engine(
            ticket.lease, run_id=ticket.handle.run_id
        )
        try:
            result = VerificationSuite.do_verification_run(
                dataset,
                request.checks,
                required_analyzers=request.required_analyzers,
                engine=engine,
                metrics_repository=request.metrics_repository,
                save_or_append_results_with_key=request.result_key,
                deadline=ticket.budget,
                cancel=run_cancel_token(ticket),
                row_level_sink=request.row_level_sink,
            )
        finally:
            self.datasets.release(request.dataset_key)
        # per-run plan-cache accounting from the run's own telemetry
        # summary (counter deltas) — recompiles-after-warmup is THE
        # steady-state health signal
        self.plans.record_run(getattr(result, "telemetry", None))
        if (
            self.slo is not None
            and request.metrics_repository is not None
            and request.result_key is not None
        ):
            _persist_slo_records(
                request.metrics_repository,
                request.result_key,
                self.slo,
                fleet=self.fleet,
            )
        return result

    # -- isolated (child-process) execution ------------------------------

    def _isolation_payload(
        self, ticket: RunTicket
    ) -> Optional[Dict[str, Any]]:
        """The spawn-safe payload for this run, or None when the request
        holds closures that cannot cross a process boundary (the caller
        then falls back to in-process execution, loudly)."""
        request: RunRequest = ticket.payload
        payload = {
            "run_id": ticket.handle.run_id,
            "dataset_key": request.dataset_key,
            "dataset_factory": request.dataset_factory,
            "checks": list(request.checks),
            "required_analyzers": list(request.required_analyzers),
            "checkpoint_path": self._checkpoint_path,
            # the sink dataclass is spawn-safe (the child builds its
            # own QuarantineWriter over the artifact dir); the child's
            # EgressReport rides back on result.row_level_egress and is
            # re-stamped onto the SUBMITTING process's sink object by
            # _execute_isolated
            "row_level_sink": request.row_level_sink,
            "deadline_s": (
                ticket.budget.remaining()
                if ticket.budget is not None
                else None
            ),
            # slice SIZE crosses the boundary, not the lease: the child
            # owns its own jax runtime, so it rebuilds an ndev mesh
            # over its own first local devices (the lease still bounds
            # parent-side concurrency for the run's duration)
            "placement_ndev": (
                ticket.lease.ndev if ticket.lease is not None else None
            ),
        }
        try:
            pickle.dumps(payload)
        except Exception:  # noqa: BLE001 — any closure anywhere inside
            return None
        return payload

    def _execute_isolated(self, ticket: RunTicket, payload: Dict[str, Any]):
        from deequ_tpu.engine.subproc import checkpoint_progress_probe

        request: RunRequest = ticket.payload
        probe = (
            checkpoint_progress_probe(self._checkpoint_path)
            if self._checkpoint_path is not None
            else None
        )
        runner = IsolatedRunner(
            key=f"dataset:{request.dataset_key}",
            progress_probe=probe,
            timeout_s=(
                ticket.budget.remaining()
                if ticket.budget is not None
                else None
            ),
            clock=self.clock,
            # preemption (and client cancel) crosses the spawn boundary
            # as ONE control message; the child exits cleanly through
            # its checkpoint path — never terminated mid-batch
            cancel_token=run_cancel_token(ticket),
            epoch_guard=(
                self.fleet.child_guard() if self.fleet is not None else None
            ),
        )
        try:
            result = runner.run(_isolated_execute, payload)
        except CrashLoopError as exc:
            self._note_crash()
            if self.fleet is not None:
                # shared breaker ledger: a crash loop HERE becomes
                # fleet-visible, so the run cannot walk the fleet via
                # adoption once poison_replicas distinct hosts crashed
                self.fleet.note_crash_loop(
                    f"dataset:{request.dataset_key}"
                )
            from deequ_tpu import config

            policy = config.options().degradation_policy
            if policy == "fail":
                raise
            # warn/tolerate flooring: a crash loop yields NO partial
            # data, so the floored result is an empty one that carries
            # the crash provenance instead of failing the handle
            return _crash_loop_result(exc, policy)
        if request.row_level_sink is not None:
            # the child ran with a pickled COPY of the sink — land the
            # report on the submitting process's object, where callers
            # (and docs) expect it
            request.row_level_sink.report = getattr(
                result, "row_level_egress", None
            )
        self.plans.record_run(getattr(result, "telemetry", None))
        return result

    # -- coalesced (superset-scan) execution -----------------------------

    def _execute_group(self, tickets: List[RunTicket]) -> List[Any]:
        """Execute a coalesced group: ONE superset scan over the shared
        dataset, each member's ``VerificationResult`` sliced back out.
        Returns one outcome per ticket in order (a result, or an
        exception instance for a member that failed individually). A
        superset-scan failure degrades to independent per-member
        execution; a crash-looped isolated superset floors EVERY member
        with the crash provenance."""
        tm = get_telemetry()
        host = tickets[0]
        run_ids = [t.handle.run_id for t in tickets]
        if self.journal is not None and epoch_fence_check(self.fleet):
            for ticket in tickets:
                self.journal.record_started(
                    ticket.handle.run_id, tenant=ticket.payload.tenant
                )
        tm.counter("service.coalesced_scans").inc()
        tm.counter("service.runs_coalesced").inc(len(tickets))
        # the whole point, as a counter: K runs, K-1 traversals NOT made
        tm.counter("service.scan_passes_saved").inc(len(tickets) - 1)
        waits = [
            max(0.0, (t.handle.started_at or 0.0) - t.submitted_at)
            for t in tickets
        ]
        tm.event(
            "runs_coalesced",
            dataset_key=host.dataset_key,
            members=len(tickets),
            run_ids=",".join(run_ids),
            tenants=",".join(
                sorted({t.payload.tenant for t in tickets})
            ),
            queue_wait_s_max=round(max(waits), 6) if waits else 0.0,
        )
        if self.isolated:
            payload = self._group_isolation_payload(tickets)
            if payload is not None:
                return self._execute_group_isolated(tickets, payload)
            tm.counter("service.isolation_inline_fallbacks").inc()
            tm.event(
                "service_isolation_fallback",
                run_id=",".join(run_ids),
                reason="coalesced group does not pickle; executing "
                "in-process",
            )
        return self._execute_group_inline(tickets)

    def _execute_group_inline(self, tickets: List[RunTicket]) -> List[Any]:
        from deequ_tpu.verification.suite import VerificationSuite

        host = tickets[0]
        request: RunRequest = host.payload
        dataset, hit = self.datasets.lease(
            request.dataset_key, request.dataset_factory
        )
        get_telemetry().event(
            "service_dataset_leased",
            run_id=host.handle.run_id,
            dataset_key=request.dataset_key,
            cache_hit=hit,
            coalesced_members=len(tickets),
        )
        engine = self._build_engine(
            host.lease, run_id=host.handle.run_id
        )
        try:
            # the superset scan runs under the HOST's envelope (best
            # priority, earliest seq). Member deadlines governed queue
            # wait (resolved at pop); a member cancel landing after
            # the scan began does NOT stop the group — the member
            # still receives its complete sliced result
            results = VerificationSuite.do_coalesced_verification_run(
                dataset,
                [
                    (
                        list(t.payload.checks),
                        list(t.payload.required_analyzers),
                    )
                    for t in tickets
                ],
                engine=engine,
                deadline=host.budget,
            )
        # lint-ok: interrupt-swallow: degradation to independent
        # per-member execution — each member's own path re-raises into
        # its outcome slot, nothing is lost
        except BaseException as exc:  # noqa: BLE001
            return self._execute_members_independently(tickets, exc)
        finally:
            self.datasets.release(request.dataset_key)
        for ticket, result in zip(tickets, results):
            _scope_member_telemetry(ticket, result)
            member: RunRequest = ticket.payload
            if (
                member.metrics_repository is not None
                and member.result_key is not None
            ):
                _persist_member_result(
                    member.metrics_repository,
                    member.result_key,
                    result,
                    slo=self.slo,
                    fleet=self.fleet,
                )
        self.plans.record_run(getattr(results[0], "telemetry", None))
        return list(results)

    def _execute_members_independently(
        self, tickets: List[RunTicket], cause: BaseException
    ) -> List[Any]:
        """Superset-scan failure fan-out: re-run every member solo so
        one bad union never fails N tenants. Per-member outcomes are
        results or that member's OWN exception."""
        tm = get_telemetry()
        tm.counter("service.coalesce_fallbacks").inc()
        tm.event(
            "coalesce_fallback",
            dataset_key=tickets[0].dataset_key,
            members=len(tickets),
            error=repr(cause)[:500],
        )
        outcomes: List[Any] = []
        for ticket in tickets:
            try:
                outcomes.append(self._execute_solo(ticket))
            # lint-ok: interrupt-swallow: the outcome slot is the error
            # channel — the scheduler fans it into the member's handle
            except BaseException as exc:  # noqa: BLE001
                outcomes.append(exc)
        return outcomes

    def _group_isolation_payload(
        self, tickets: List[RunTicket]
    ) -> Optional[Dict[str, Any]]:
        host: RunRequest = tickets[0].payload
        payload = {
            "run_ids": [t.handle.run_id for t in tickets],
            "dataset_key": host.dataset_key,
            "dataset_factory": host.dataset_factory,
            "members": [
                {
                    "checks": list(t.payload.checks),
                    "required_analyzers": list(
                        t.payload.required_analyzers
                    ),
                }
                for t in tickets
            ],
            "checkpoint_path": self._checkpoint_path,
            "deadline_s": (
                tickets[0].budget.remaining()
                if tickets[0].budget is not None
                else None
            ),
            "placement_ndev": (
                tickets[0].lease.ndev
                if tickets[0].lease is not None
                else None
            ),
        }
        try:
            pickle.dumps(payload)
        except Exception:  # noqa: BLE001 — any closure anywhere inside
            return None
        return payload

    def _execute_group_isolated(
        self, tickets: List[RunTicket], payload: Dict[str, Any]
    ) -> List[Any]:
        from deequ_tpu.engine.subproc import checkpoint_progress_probe

        host = tickets[0]
        request: RunRequest = host.payload
        probe = (
            checkpoint_progress_probe(self._checkpoint_path)
            if self._checkpoint_path is not None
            else None
        )
        runner = IsolatedRunner(
            key=f"dataset:{request.dataset_key}",
            progress_probe=probe,
            timeout_s=(
                host.budget.remaining()
                if host.budget is not None
                else None
            ),
            clock=self.clock,
            epoch_guard=(
                self.fleet.child_guard() if self.fleet is not None else None
            ),
        )
        try:
            results = runner.run(_isolated_execute_coalesced, payload)
        except CrashLoopError as exc:
            self._note_crash()
            if self.fleet is not None:
                self.fleet.note_crash_loop(
                    f"dataset:{request.dataset_key}"
                )
            from deequ_tpu import config

            policy = config.options().degradation_policy
            # crash-loop flooring lands on EVERY member with the same
            # provenance: under "fail" each handle fails with the
            # CrashLoopError; under warn/tolerate each member gets its
            # own floored empty result carrying the crash record
            if policy == "fail":
                return [exc for _ in tickets]
            return [_crash_loop_result(exc, policy) for _ in tickets]
        # lint-ok: interrupt-swallow: degradation to independent
        # per-member execution; member paths re-raise into outcome slots
        except BaseException as exc:  # noqa: BLE001
            return self._execute_members_independently(tickets, exc)
        for ticket, result in zip(tickets, results):
            if isinstance(result, Exception):
                continue
            _scope_member_telemetry(ticket, result)
            member: RunRequest = ticket.payload
            if (
                member.metrics_repository is None
                or member.result_key is None
            ):
                continue
            _persist_member_result(
                member.metrics_repository,
                member.result_key,
                result,
                slo=self.slo,
                fleet=self.fleet,
            )
        if results and not isinstance(results[0], Exception):
            self.plans.record_run(getattr(results[0], "telemetry", None))
        return list(results)

    # -- introspection --------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        snap = {
            "queue": self.queue.snapshot(),
            "datasets": self.datasets.snapshot(),
            "plans": self.plans.snapshot(),
        }
        if self.placer is not None:
            snap["placement"] = self.placer.snapshot()
        if self.fleet is not None:
            snap["fleet"] = self.fleet.snapshot()
        return snap

    def health(self) -> Dict[str, Any]:
        """The ``/healthz`` payload of the live plane: queue depths,
        active slices, breaker states, shed counts — everything the
        future autoscaler (ROADMAP item 2) reads, in one place."""
        from deequ_tpu.engine.subproc import breaker_states

        tm = get_telemetry()
        queue_snap = self.queue.snapshot()
        counters = tm.metrics.counters_snapshot()
        payload: Dict[str, Any] = {
            "status": "ok" if self.scheduler.running else "stopped",
            "queue": queue_snap,
            "workers": self.scheduler.workers,
            "breakers": breaker_states(),
            "shed": {
                "submissions_shed": counters.get(
                    "service.submissions_shed", 0
                ),
                "drained_queued": counters.get(
                    "service.drained_queued", 0
                ),
                "quota_rejections": counters.get(
                    "service.quota_rejections", 0
                ),
            },
        }
        if self.placer is not None:
            placement = self.placer.snapshot()
            payload["placement"] = placement
            payload["slices_active"] = placement.get("active_slices")
        if self.preemption is not None:
            preempt = self.preemption.snapshot()
            preempt["preemptions"] = counters.get(
                "service.preemptions", 0
            )
            preempt["requeues"] = counters.get(
                "service.preempt_requeues", 0
            )
            preempt["resumes"] = counters.get(
                "service.preempt_resumes", 0
            )
            preempt["batches_conserved"] = counters.get(
                "service.preempted_batches_conserved", 0
            )
            payload["preemption"] = preempt
        if self.autoscaler is not None:
            payload["autoscale"] = self.autoscaler.snapshot()
        if self.slo is not None:
            payload["slo"] = self.slo.snapshot()
        if self.fleet is not None:
            fleet = self.fleet.snapshot()
            fleet["fenced_writes"] = counters.get(
                "service.fleet.fenced_writes", 0
            )
            fleet["runs_adopted"] = counters.get(
                "service.fleet.runs_adopted", 0
            )
            fleet["poisoned_runs"] = counters.get(
                "service.fleet.poisoned_runs", 0
            )
            payload["fleet"] = fleet
        return payload


class _JournalingCheckpointer(ScanCheckpointer):
    """A ``ScanCheckpointer`` that also appends a journal ``checkpoint``
    record per save, so replay knows how far a dead run had progressed
    (the cursor itself lives in the checkpoint blob — the journal only
    records THAT progress happened, and where)."""

    def __init__(
        self,
        path: str,
        journal: Optional[RunJournal],
        run_id: str,
        every_batches: Optional[int] = None,
        fleet: Optional[Any] = None,
    ):
        super().__init__(path, every_batches)
        self._journal = journal
        self._run_id = run_id
        self._fleet = fleet

    def save(self, cursor, plan_token, states, host_accs, degradation):
        if not epoch_fence_check(self._fleet):
            # fenced mid-run: the adopter's resumed copy owns the
            # cursor now — a zombie save here could rewind it
            return
        super().save(cursor, plan_token, states, host_accs, degradation)
        if self._journal is not None:
            self._journal.record_checkpoint(
                self._run_id,
                batch_index=int(cursor.batch_index),
                row_offset=int(cursor.row_offset),
                plan_token=plan_token,
            )


class _EpochFencedCheckpointer(ScanCheckpointer):
    """Child-side checkpointer: before every save, re-read the lease
    chain named by the shipped epoch guard (``CHILD_EPOCH_ENV``,
    engine/subproc.py) — a child whose PARENT was fenced while the
    child kept scanning must also stop persisting cursors, or the
    zombie pair would rewind the adopter's progress. The guard check
    is a couple of small reads per checkpoint interval, not per
    batch."""

    def save(self, cursor, plan_token, states, host_accs, degradation):
        from deequ_tpu.engine.subproc import child_epoch_fenced

        if child_epoch_fenced():
            get_telemetry().counter(
                "service.fleet.child_checkpoint_drops"
            ).inc()
            return
        super().save(cursor, plan_token, states, host_accs, degradation)


def _child_engine(payload: Dict[str, Any]):
    """Rebuild the child-side ``AnalysisEngine`` from a spawn payload:
    a checkpointer over the durable path when journaling, and — for a
    leased run — a mesh over the child's own first ``placement_ndev``
    local devices (a lease object cannot cross a spawn boundary; the
    SIZE reproduces the parent's placement shape, so the child hits the
    same shape-keyed plan entry its warmup compiled)."""
    kwargs: Dict[str, Any] = {}
    if payload.get("checkpoint_path"):
        kwargs["checkpointer"] = _EpochFencedCheckpointer(
            payload["checkpoint_path"]
        )
    ndev = payload.get("placement_ndev")
    if ndev:
        import jax
        import numpy as np
        from jax.sharding import Mesh

        devices = jax.devices()
        if len(devices) < int(ndev):
            raise RuntimeError(
                f"run was placed on {ndev} devices but this child sees "
                f"{len(devices)}; refusing to run it unsharded"
            )
        kwargs["mesh"] = Mesh(np.array(devices[: int(ndev)]), ("dp",))
    if not kwargs:
        return None
    from deequ_tpu.engine.scan import AnalysisEngine

    return AnalysisEngine(**kwargs)


def _isolated_execute(payload: Dict[str, Any]):
    """Child-process entry for one isolated verification run (module
    level: spawn pickles it by reference). Rebuilds the dataset from
    its factory, attaches a checkpointer over the service's durable
    checkpoint path — so a relaunched child resumes mid-scan (a
    row-level sink resumes mid-ARTIFACT via its durable span cursor) —
    and strips ``_data`` from the result (device buffers do not cross
    the pipe). The run listens on
    the child-side cancel token: a parent-sent preemption (or client
    cancel) exits the scan cleanly at the next batch boundary, final
    cursor persisted."""
    from deequ_tpu.engine.subproc import child_cancel_token
    from deequ_tpu.verification.suite import VerificationSuite

    engine = _child_engine(payload)
    dataset = payload["dataset_factory"]()
    result = VerificationSuite.do_verification_run(
        dataset,
        payload["checks"],
        required_analyzers=payload["required_analyzers"],
        engine=engine,
        deadline=payload.get("deadline_s"),
        cancel=child_cancel_token(),
        # the sink writes the artifact dir directly from this child;
        # durable span segments + the checkpoint's egress cursor let a
        # relaunched child resume the artifact mid-write
        row_level_sink=payload.get("row_level_sink"),
    )
    result._data = None
    return result


def _isolated_execute_coalesced(payload: Dict[str, Any]) -> List[Any]:
    """Child-process entry for one coalesced superset scan (module
    level: spawn pickles it by reference). Rebuilds the shared dataset
    ONCE, runs the single superset traversal, and returns the member
    results in order — each stripped of ``_data`` (device buffers do
    not cross the pipe)."""
    from deequ_tpu.verification.suite import VerificationSuite

    engine = _child_engine(payload)
    dataset = payload["dataset_factory"]()
    results = VerificationSuite.do_coalesced_verification_run(
        dataset,
        [
            (member["checks"], member["required_analyzers"])
            for member in payload["members"]
        ],
        engine=engine,
        deadline=payload.get("deadline_s"),
    )
    for result in results:
        result._data = None
    return results


def _persist_member_result(
    repository, key, result, slo=None, fleet=None
) -> None:
    """Append one coalesced member's sliced result to its metrics
    repository — the same load/combine/save (with operational records)
    that ``do_analysis_run`` performs for a solo run. The coalesced
    path cannot delegate persistence to the superset run: each member
    owns a DIFFERENT repository/key pair and only its own slice. When
    the service tracks SLOs, the current attainment snapshot rides
    along as ``slo.*`` operational records under the same key."""
    if not epoch_fence_check(fleet):
        return  # fenced: the adopter persists this member's result
    from deequ_tpu.analyzers.runner import AnalyzerContext
    from deequ_tpu.repository.base import AnalysisResult

    context = AnalyzerContext(
        dict(result.metrics),
        run_metadata=result.run_metadata,
        telemetry=result.telemetry,
        degradation=result.degradation,
        interruption=result.interruption,
    )
    current = repository.load_by_key(key)
    combined = (
        current.analyzer_context + context
        if current is not None
        else context
    )
    summary = result.telemetry
    if summary is not None:
        from deequ_tpu.telemetry.oprecords import operational_metrics

        op = operational_metrics(summary)
        if op:
            combined = combined + AnalyzerContext(op)
    if slo is not None:
        from deequ_tpu.telemetry.oprecords import slo_metrics

        sm = slo_metrics(slo.snapshot())
        if sm:
            combined = combined + AnalyzerContext(sm)
    repository.save(AnalysisResult(key, combined))


def _persist_slo_records(repository, key, slo, fleet=None) -> None:
    """Append the service's current SLO attainment snapshot as
    operational records under a run's ``ResultKey`` — error-budget
    burn becomes one more metric series the existing anomaly
    strategies can trend, with zero new query machinery."""
    if not epoch_fence_check(fleet):
        return
    from deequ_tpu.analyzers.runner import AnalyzerContext
    from deequ_tpu.repository.base import AnalysisResult
    from deequ_tpu.telemetry.oprecords import slo_metrics

    records = slo_metrics(slo.snapshot())
    if not records:
        return
    context = AnalyzerContext(records)
    current = repository.load_by_key(key)
    combined = (
        current.analyzer_context + context
        if current is not None
        else context
    )
    repository.save(AnalysisResult(key, combined))


def _scope_member_telemetry(ticket, result) -> None:
    """Re-scope a coalesced member's telemetry provenance: the
    superset scan executed ONCE under the host ticket's trace, but
    each member's sliced result must carry spans attributed to its OWN
    trace_id — otherwise every member's persisted summary points at
    the host run and a fleet timeline double-attributes the work."""
    trace = getattr(ticket, "trace", None)
    summary = getattr(result, "telemetry", None)
    if trace is None or not isinstance(summary, dict):
        return
    scoped = dict(summary)
    scoped["trace_id"] = trace.trace_id
    scoped["spans"] = [
        dict(sp, trace_id=trace.trace_id)
        for sp in (summary.get("spans") or [])
    ]
    result.telemetry = scoped


def _crash_loop_result(exc: CrashLoopError, policy: str):
    """The floored result of a crash-looped run under a non-"fail"
    degradation policy: empty metrics, status WARNING ("warn") or
    SUCCESS ("tolerate"), with the crash provenance riding the
    degradation record."""
    from deequ_tpu.checks import CheckStatus
    from deequ_tpu.engine.resilience import BatchFailure, ScanDegradation
    from deequ_tpu.verification.suite import VerificationResult

    status = (
        CheckStatus.WARNING if policy == "warn" else CheckStatus.SUCCESS
    )
    result = VerificationResult(status, {}, {})
    degradation = ScanDegradation()
    degradation.failures.append(
        BatchFailure(
            batch_index=-1,
            rows=0,
            error_class=type(exc).__name__,
            message=str(exc)[:500],
            attempts=int(exc.launches),
        )
    )
    result.degradation = degradation
    return result


def _load_warm_plans():
    """Resolve ``tools.warmup.warm_plans`` without requiring ``tools``
    to be an installed package: try the repo-layout import first, then
    load the module straight off the file next to this package."""
    try:
        from tools.warmup import warm_plans  # type: ignore

        return warm_plans
    except ImportError:
        pass
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))),
        "tools",
        "warmup.py",
    )
    spec = importlib.util.spec_from_file_location(
        "deequ_tpu_tools_warmup", path
    )
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load warmup module from {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.warm_plans
