"""Multi-tenant verification service (PR 7): queue discipline, tenant
quotas, deadline/cancel envelopes, shared caches, and the ScanPlan
compile/execute split — all scheduling behavior asserted on
``ManualClock`` fake time with stub executors (no device work unless a
test is explicitly about plans)."""

import threading
import time

import numpy as np
import pytest

from deequ_tpu.engine.deadline import (
    DeadlineExceeded,
    ManualClock,
    RunBudget,
    RunCancelled,
)
from deequ_tpu.service import (
    DatasetCache,
    PlanCache,
    Priority,
    QuotaExceeded,
    RunHandle,
    RunQueue,
    RunRequest,
    RunState,
    RunTicket,
    VerificationService,
)


def _ticket(
    tenant="acme",
    priority=Priority.STANDARD,
    budget=None,
    run_id="run-x",
    payload=None,
):
    handle = RunHandle(run_id, tenant, priority)
    return RunTicket(seq=0, handle=handle, payload=payload, budget=budget)


def _spin_until(predicate, timeout_s=10.0):
    """Real-time wait for a cross-thread condition (the clocks under
    test are fake; thread scheduling is not)."""
    deadline = time.monotonic() + timeout_s
    while not predicate():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.005)
    return True


class _FakeInterruption:
    def __init__(self, kind, reason="stopped"):
        self.kind = kind
        self.reason = reason


class _FakeResult:
    """Duck-typed VerificationResult: only what the scheduler reads."""

    def __init__(self, interruption=None, telemetry=None):
        self.interruption = interruption
        self.telemetry = telemetry


class TestRunQueue:
    def test_priority_order_fifo_within_class(self):
        q = RunQueue(clock=ManualClock())
        batch = _ticket(priority=Priority.BATCH, run_id="b")
        std1 = _ticket(priority=Priority.STANDARD, run_id="s1")
        std2 = _ticket(priority=Priority.STANDARD, run_id="s2")
        inter = _ticket(priority=Priority.INTERACTIVE, run_id="i")
        for t in (batch, std1, std2, inter):
            q.push(t)
        order = [
            q.pop(should_stop=lambda: True).handle.run_id
            for _ in range(4)
        ]
        assert order == ["i", "s1", "s2", "b"]

    def test_reserved_worker_never_takes_batch(self):
        q = RunQueue(clock=ManualClock())
        q.push(_ticket(priority=Priority.BATCH, run_id="b"))
        assert q.pop(
            max_priority=Priority.INTERACTIVE, should_stop=lambda: True
        ) is None
        q.push(_ticket(priority=Priority.INTERACTIVE, run_id="i"))
        got = q.pop(
            max_priority=Priority.INTERACTIVE, should_stop=lambda: True
        )
        assert got is not None and got.handle.run_id == "i"
        # the batch ticket is still there for a general worker
        assert q.depth() == 1

    def test_pending_quota_rejects_at_push(self):
        q = RunQueue(clock=ManualClock(), tenant_max_pending=2)
        q.push(_ticket(tenant="acme", run_id="1"))
        q.push(_ticket(tenant="acme", run_id="2"))
        with pytest.raises(QuotaExceeded):
            q.push(_ticket(tenant="acme", run_id="3"))
        # another tenant is unaffected by acme's quota
        q.push(_ticket(tenant="globex", run_id="4"))
        assert q.depth() == 3

    def test_active_quota_skips_tenant_not_queue(self):
        q = RunQueue(clock=ManualClock(), tenant_max_active=1)
        first = _ticket(tenant="acme", run_id="a1")
        second = _ticket(tenant="acme", run_id="a2")
        other = _ticket(tenant="globex", run_id="g1")
        q.push(first)
        q.push(second)
        q.push(other)
        t1 = q.pop(should_stop=lambda: True)
        assert t1.handle.run_id == "a1"
        # acme is at its active quota: a2 (earlier seq) is SKIPPED and
        # globex's ticket runs instead — one tenant can't wedge the
        # queue
        t2 = q.pop(should_stop=lambda: True)
        assert t2.handle.run_id == "g1"
        q.task_done(t1)
        t3 = q.pop(should_stop=lambda: True)
        assert t3.handle.run_id == "a2"

    def test_deadline_expired_while_queued_rejected(self):
        clock = ManualClock()
        q = RunQueue(clock=clock)
        ticket = _ticket(
            budget=RunBudget(deadline_s=5.0, clock=clock), run_id="late"
        )
        q.push(ticket)  # budget starts here: queue wait burns deadline
        clock.advance(10.0)
        assert q.pop(should_stop=lambda: True) is None
        handle = ticket.handle
        assert handle.status == RunState.REJECTED and handle.done
        with pytest.raises(DeadlineExceeded):
            handle.result(timeout=0)
        assert q.depth() == 0

    def test_cancel_while_queued_dropped_at_pop(self):
        q = RunQueue(clock=ManualClock())
        ticket = _ticket(run_id="gone")
        q.push(ticket)
        ticket.handle.cancel("changed my mind")
        assert q.pop(should_stop=lambda: True) is None
        assert ticket.handle.status == RunState.CANCELLED
        with pytest.raises(RunCancelled, match="changed my mind"):
            ticket.handle.result(timeout=0)

    def test_drain_queued_terminates_with_reason(self):
        q = RunQueue(clock=ManualClock())
        tickets = [_ticket(run_id=f"r{i}") for i in range(3)]
        for t in tickets:
            q.push(t)
        assert q.drain_queued("sigterm: rollout") == 3
        for t in tickets:
            assert t.handle.status == RunState.CANCELLED
            with pytest.raises(RunCancelled, match="sigterm"):
                t.handle.result(timeout=0)
        assert q.depth() == 0

    def test_result_timeout_while_queued(self):
        q = RunQueue(clock=ManualClock())
        ticket = _ticket(run_id="waiting")
        q.push(ticket)
        with pytest.raises(TimeoutError):
            ticket.handle.result(timeout=0.01)


class TestServiceScheduling:
    """VerificationService with stub executors: real worker threads,
    fake scheduling clock."""

    def _request(self, tenant="acme", priority=Priority.STANDARD,
                 dataset_key="shared", deadline_s=None):
        return RunRequest(
            tenant=tenant,
            checks=(),
            dataset_key=dataset_key,
            dataset_factory=lambda: None,
            priority=priority,
            deadline_s=deadline_s,
        )

    def test_interactive_reserve_prevents_starvation(self):
        release = threading.Event()

        def execute(ticket):
            if ticket.payload.dataset_key == "block":
                assert release.wait(timeout=30)
            return _FakeResult()

        svc = VerificationService(
            workers=2, interactive_reserve=1,
            clock=ManualClock(), execute=execute,
            tenant_max_pending=0, tenant_max_active=0,
        ).start()
        try:
            # the ONE general worker gets occupied by a long batch run
            blocker = svc.submit(self._request(
                priority=Priority.BATCH, dataset_key="block"
            ))
            assert _spin_until(
                lambda: blocker.status == RunState.RUNNING
            )
            # a second batch run can only wait behind it
            parked = svc.submit(self._request(
                priority=Priority.BATCH, dataset_key="block"
            ))
            # the interactive run lands on the reserve worker and
            # finishes while both batch runs still hold/want the
            # general worker — no priority inversion
            quick = svc.submit(self._request(
                tenant="globex", priority=Priority.INTERACTIVE
            ))
            assert quick.wait(timeout=10)
            assert quick.status == RunState.DONE
            assert blocker.status == RunState.RUNNING
            assert parked.status == RunState.QUEUED
            release.set()
            assert blocker.wait(timeout=10)
            assert parked.wait(timeout=10)
            assert parked.status == RunState.DONE
        finally:
            release.set()
            svc.stop(drain=False, timeout=10)

    def test_cancel_running_returns_partial_result(self):
        def execute(ticket):
            assert ticket.handle.cancel_token.wait(timeout=30)
            return _FakeResult(
                interruption=_FakeInterruption("cancelled", "client")
            )

        svc = VerificationService(
            workers=1, interactive_reserve=0,
            clock=ManualClock(), execute=execute,
        ).start()
        try:
            handle = svc.submit(self._request())
            assert _spin_until(
                lambda: handle.status == RunState.RUNNING
            )
            handle.cancel("client")
            assert handle.wait(timeout=10)
            # cancelled WHILE RUNNING: terminal CANCELLED, but the
            # partial result is still delivered (same contract as a
            # direct bounded run)
            assert handle.status == RunState.CANCELLED
            assert isinstance(handle.result(timeout=0), _FakeResult)
        finally:
            svc.stop(drain=False, timeout=10)

    def test_deadline_interruption_is_still_done(self):
        # a run that the ENGINE stopped at its deadline completed its
        # envelope: the service reports DONE with the partial result,
        # not CANCELLED
        svc = VerificationService(
            workers=1, interactive_reserve=0, clock=ManualClock(),
            execute=lambda t: _FakeResult(
                interruption=_FakeInterruption("deadline", "budget")
            ),
        ).start()
        try:
            handle = svc.submit(self._request(deadline_s=60.0))
            assert handle.wait(timeout=10)
            assert handle.status == RunState.DONE
        finally:
            svc.stop(drain=False, timeout=10)

    def test_executor_failure_lands_on_handle(self):
        def execute(ticket):
            raise ValueError("boom")

        svc = VerificationService(
            workers=1, interactive_reserve=0,
            clock=ManualClock(), execute=execute,
        ).start()
        try:
            handle = svc.submit(self._request())
            assert handle.wait(timeout=10)
            assert handle.status == RunState.FAILED
            with pytest.raises(ValueError, match="boom"):
                handle.result(timeout=0)
            # the worker survived the failure and serves the next run
            ok = svc.submit(self._request())
            assert ok.wait(timeout=10)
            assert ok.status == RunState.FAILED  # same stub raises
        finally:
            svc.stop(drain=False, timeout=10)

    def test_tenant_pending_quota_at_submit(self):
        release = threading.Event()

        def execute(ticket):
            assert release.wait(timeout=30)
            return _FakeResult()

        svc = VerificationService(
            workers=1, interactive_reserve=0,
            clock=ManualClock(), execute=execute,
            tenant_max_pending=1,
        ).start()
        try:
            svc.submit(self._request(tenant="acme"))
            with pytest.raises(QuotaExceeded):
                svc.submit(self._request(tenant="acme"))
            # other tenants unaffected
            svc.submit(self._request(tenant="globex"))
        finally:
            release.set()
            svc.stop(drain=False, timeout=10)

    def test_drain_cancels_queued_lets_running_finish(self):
        release = threading.Event()

        def execute(ticket):
            assert release.wait(timeout=30)
            return _FakeResult()

        svc = VerificationService(
            workers=1, interactive_reserve=0,
            clock=ManualClock(), execute=execute,
        ).start()
        try:
            running = svc.submit(self._request())
            assert _spin_until(
                lambda: running.status == RunState.RUNNING
            )
            queued = svc.submit(self._request())
            drained = svc.drain("sigterm: deploy")
            assert drained == 1
            assert queued.status == RunState.CANCELLED
            with pytest.raises(RunCancelled, match="sigterm"):
                queued.result(timeout=0)
            # the running run is untouched by drain and finishes
            assert running.status == RunState.RUNNING
            release.set()
            assert running.wait(timeout=10)
            assert running.status == RunState.DONE
            # a drained service refuses new work
            with pytest.raises(RuntimeError):
                svc.submit(self._request())
        finally:
            release.set()
            svc.stop(drain=False, timeout=10)

    def test_sigterm_token_drains_service(self):
        from deequ_tpu.engine.deadline import (
            reset_shutdown_token,
            shutdown_token,
        )

        release = threading.Event()

        def execute(ticket):
            assert release.wait(timeout=30)
            return _FakeResult()

        reset_shutdown_token()
        svc = VerificationService(
            workers=1, interactive_reserve=0,
            clock=ManualClock(), execute=execute,
        )
        try:
            svc.start(install_sigterm=True)
            running = svc.submit(self._request())
            assert _spin_until(
                lambda: running.status == RunState.RUNNING
            )
            queued = svc.submit(self._request())
            # what the installed SIGTERM handler does, minus the signal
            # plumbing: fire the process-wide shutdown token
            shutdown_token().cancel("sigterm: shutting down")
            assert _spin_until(lambda: queued.done)
            assert queued.status == RunState.CANCELLED
            release.set()
            assert running.wait(timeout=10)
            assert running.status == RunState.DONE
        finally:
            release.set()
            svc.stop(drain=False, timeout=10)
            reset_shutdown_token()

    def test_wait_idle_and_graceful_stop(self):
        svc = VerificationService(
            workers=2, interactive_reserve=1,
            clock=ManualClock(),
            execute=lambda t: _FakeResult(),
        ).start()
        handles = [svc.submit(self._request()) for _ in range(4)]
        svc.stop(drain=True, timeout=20)
        assert all(h.status == RunState.DONE for h in handles)
        assert not svc.scheduler.running


class TestDatasetCache:
    class _FakeDataset:
        def __init__(self, nbytes):
            self.nbytes = nbytes
            self.cleared = False

        def clear_device_cache(self):
            self.cleared = True

    @pytest.fixture(autouse=True)
    def _weigh_by_nbytes(self, monkeypatch):
        monkeypatch.setattr(
            "deequ_tpu.engine.scan.estimated_run_bytes",
            lambda ds, engine=None: ds.nbytes,
        )

    def test_lease_shares_one_handle(self):
        cache = DatasetCache(watermark_bytes=0)
        builds = []

        def factory():
            ds = self._FakeDataset(10)
            builds.append(ds)
            return ds

        a, hit_a = cache.lease("t", factory)
        b, hit_b = cache.lease("t", factory)
        assert a is b and not hit_a and hit_b
        assert len(builds) == 1
        snap = cache.snapshot()
        assert snap["entries"]["t"]["pins"] == 2
        cache.release("t")
        cache.release("t")
        assert cache.snapshot()["entries"]["t"]["pins"] == 0

    def test_watermark_evicts_lru_unpinned_only(self):
        cache = DatasetCache(watermark_bytes=100)
        a, _ = cache.lease("a", lambda: self._FakeDataset(60))
        cache.release("a")
        b, _ = cache.lease("b", lambda: self._FakeDataset(60))
        # a (unpinned LRU) was evicted to fit b under the watermark
        assert a.cleared
        assert "a" not in cache.snapshot()["entries"]
        # b stays pinned: adding c goes over watermark but never
        # evicts a leased handle
        c, _ = cache.lease("c", lambda: self._FakeDataset(60))
        assert not b.cleared
        assert cache.snapshot()["total_bytes"] == 120
        # releasing b makes it evictable; release() re-runs eviction
        cache.release("b")
        assert b.cleared
        assert not c.cleared
        assert cache.snapshot()["total_bytes"] == 60

    def test_clear_clears_device_caches(self):
        cache = DatasetCache(watermark_bytes=0)
        a, _ = cache.lease("a", lambda: self._FakeDataset(5))
        cache.clear()
        assert a.cleared
        assert cache.snapshot()["entries"] == {}


class TestPlanCacheLedger:
    def test_note_warmed_dedups(self):
        plans = PlanCache()
        plans.note_warmed(["t1", "t2"])
        plans.note_warmed(["t2", "t3", None])
        assert plans.warmed_tokens == ["t1", "t2", "t3"]

    def test_record_run_accounting(self):
        plans = PlanCache()
        plans.record_run(
            {"counters": {"engine.plan_cache.misses": 1}}
        )
        plans.record_run({"counters": {"engine.plan_cache.hits": 2}})
        plans.record_run(None)  # a run without telemetry still counts
        snap = plans.snapshot()
        assert snap["runs"] == 3
        assert snap["recompile_runs"] == 1
        assert snap["warm_runs"] == 1


class TestScanPlan:
    """The compile/execute split in engine/scan.py: plans are
    first-class, cacheable, and shareable."""

    def _pairs(self, ds, analyzers):
        # what the runner does before handing pairs to the engine:
        # vouch for each op's closure purity so the plan is cacheable
        from deequ_tpu.analyzers.base import (
            CACHE_TOKEN_AUTO,
            make_cache_token,
        )

        pairs = []
        for a in analyzers:
            ops = a.make_ops(ds)
            if ops.cache_token is CACHE_TOKEN_AUTO:
                ops.cache_token = make_cache_token(
                    a, ds, predicates=(getattr(a, "where", None),)
                )
            pairs.append((a, ops))
        return pairs

    def test_prepare_then_execute_matches_run_scan(self):
        from deequ_tpu import Dataset
        from deequ_tpu.analyzers import Maximum, Mean, Sum
        from deequ_tpu.engine import AnalysisEngine

        ds = Dataset.from_pydict(
            {"x": [float(i) for i in range(2000)]}
        )
        analyzers = [Mean("x"), Sum("x"), Maximum("x")]
        engine = AnalysisEngine()
        plan = engine.prepare_scan(ds, self._pairs(ds, analyzers))
        assert plan is not None
        assert plan.mode in ("resident", "streaming")
        assert plan.batch_size == 2000
        states = engine.execute_plan(plan, ds)
        reference = AnalysisEngine().run_scan(
            ds, self._pairs(ds, analyzers)
        )
        import jax

        flat = jax.tree_util.tree_leaves(states)
        ref_flat = jax.tree_util.tree_leaves(reference)
        assert len(flat) == len(ref_flat) > 0
        for got, want in zip(flat, ref_flat):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want)
            )

    def test_plan_is_reusable_and_cache_visible(self):
        from deequ_tpu import Dataset
        from deequ_tpu.analyzers import Sum
        from deequ_tpu.engine import AnalysisEngine
        from deequ_tpu.engine.scan import plan_cache_snapshot

        # a column name unique to this test -> a fresh structural key
        ds = Dataset.from_pydict(
            {"svc_plan_probe": [float(i) for i in range(512)]}
        )
        engine = AnalysisEngine()
        pairs = self._pairs(ds, [Sum("svc_plan_probe")])
        plan = engine.prepare_scan(ds, pairs)
        assert plan.cache_key is not None
        assert plan.token is not None
        assert not plan.compiled
        engine.execute_plan(plan, ds)
        # the jitted executable is now resident under the plan's token
        assert plan.compiled
        assert plan.token in plan_cache_snapshot()
        # resubmission: same plan object executes again as a warm hit
        engine.execute_plan(plan, ds)
        assert engine.plan_cache_hit
        # and a SEPARATE engine preparing the same structure shares it
        other = AnalysisEngine()
        again = other.prepare_scan(ds, pairs)
        assert again.cache_key == plan.cache_key
        assert again.compiled
        other.execute_plan(again, ds)
        assert other.plan_cache_hit

    def test_empty_prepare_is_none(self):
        from deequ_tpu import Dataset
        from deequ_tpu.engine import AnalysisEngine

        ds = Dataset.from_pydict({"x": [1.0]})
        engine = AnalysisEngine()
        assert engine.prepare_scan(ds, []) is None
        assert engine.run_scan(ds, []) == []

    def test_module_level_estimated_run_bytes(self):
        from deequ_tpu import Dataset
        from deequ_tpu.engine import AnalysisEngine
        from deequ_tpu.engine.scan import estimated_run_bytes

        ds = Dataset.from_pydict(
            {"x": [1.0, 2.0], "y": [3.0, 4.0]}
        )
        assert estimated_run_bytes(ds) == AnalysisEngine(
        ).estimated_run_bytes(ds) > 0


class TestWarmPlans:
    def test_warm_plans_reports_tokens_then_idempotent(self):
        from tools.warmup import warm_plans

        schema = {"svc_warm_v": "float32"}
        report = warm_plans(
            schema, suite=False, batch_size=1024, nullable=(False,)
        )
        assert report["passes"] >= 1
        assert report["total_s"] >= 0
        assert len(report["tokens"]) >= 1
        again = warm_plans(
            schema, suite=False, batch_size=1024, nullable=(False,)
        )
        assert again["tokens"] == []  # everything already resident
        assert again["already_warm"] >= len(report["tokens"])

    def test_exact_suite_warmup_means_zero_recompiles(self):
        # the service's startup path: warm the EXACT production checks
        # against a synthetic dataset, then the real run's telemetry
        # shows plan-cache hits and zero misses
        from deequ_tpu import Check, CheckLevel, VerificationSuite
        from tools.warmup import synthetic_dataset, warm_plans

        schema = {"svc_zero_x": "float32"}
        check = (
            Check(CheckLevel.ERROR, "svc-zero")
            .is_complete("svc_zero_x")
            .is_non_negative("svc_zero_x")
        )
        warm_plans(
            schema, batch_size=1024, nullable=(False,),
            checks=[check], profile=False,
        )
        ds = synthetic_dataset(
            schema, rows=1024, nullable=False, wide_ints=False, seed=3
        )
        result = (
            VerificationSuite().on_data(ds).add_check(check).run()
        )
        counters = (result.telemetry or {}).get("counters", {})
        assert counters.get("engine.plan_cache.misses", 0) == 0
        assert counters.get("engine.plan_cache.hits", 0) >= 1


class TestObsReportServiceSection:
    def test_render_service_section(self):
        from tools.obs_report import render_service

        records = [
            {"type": "event", "event": "service_plans_warmed",
             "tokens": ["tok1", "tok2"]},
            {"type": "event", "event": "service_run_started",
             "run_id": "run-1", "tenant": "acme",
             "priority": "interactive", "queue_wait_s": 0.01},
            {"type": "event", "event": "service_run_started",
             "run_id": "run-2", "tenant": "globex",
             "priority": "batch", "queue_wait_s": 0.5},
            {"type": "event", "event": "service_run_finished",
             "run_id": "run-1", "tenant": "acme", "status": "success"},
            {"type": "event", "event": "service_run_finished",
             "run_id": "run-2", "tenant": "globex", "status": "success"},
            {"type": "event", "event": "service_run_rejected",
             "run_id": "run-3", "tenant": "acme",
             "reason": "deadline expired while queued"},
            {"type": "event", "event": "service_dataset_leased",
             "run_id": "run-1", "dataset_key": "orders",
             "cache_hit": False},
            {"type": "event", "event": "service_dataset_leased",
             "run_id": "run-2", "dataset_key": "orders",
             "cache_hit": True},
            {"type": "run_summary", "run_id": 1, "counters":
                {"engine.plan_cache.hits": 2,
                 "engine.plan_cache.misses": 1}},
        ]
        out = render_service(records)
        assert out.startswith("service:")
        assert "acme" in out and "globex" in out
        assert "rejected=1" in out
        assert "p50=" in out and "p99=" in out
        assert "hits=2 compiles=1" in out
        assert "warmed 2 plan(s)" in out
        assert "hits=1 placements=1 evictions=0" in out
        assert "deadline-expired while queued: 1" in out

    def test_render_service_empty_without_events(self):
        from tools.obs_report import render_service

        assert render_service([{"type": "span"}]) == ""


# --------------------------------------------------------------------------
# end-to-end run tracing: the service-side span tree (docs/OBSERVABILITY.md)
# --------------------------------------------------------------------------


def _trace_table():
    """Module-level dataset factory: pickles by reference, so traced
    requests survive the spawn boundary under ``isolated=True``."""
    from deequ_tpu.data import Dataset

    rng = np.random.default_rng(29)
    return Dataset.from_pydict(
        {
            "a": rng.integers(0, 50, 2_000, dtype=np.int64).tolist(),
            "b": rng.normal(5.0, 2.0, 2_000).tolist(),
        }
    )


class _TraceSink:
    """Capture every finished span record on the process telemetry."""

    def __init__(self):
        from deequ_tpu.telemetry import get_telemetry

        self.records = []
        self._tm = get_telemetry()

    def __enter__(self):
        self._tm.add_span_sink(self.records.append)
        return self.records

    def __exit__(self, *exc):
        self._tm.remove_span_sink(self.records.append)


def _trace_tree(records, trace_id):
    """(spans, root) of one trace; asserts it is a SINGLE connected
    tree — every span reaches one root."""
    spans = [r for r in records if r.get("trace_id") == trace_id]
    assert spans, f"no spans for trace {trace_id}"
    ids = {r["span_id"] for r in spans}
    roots = [r for r in spans if r.get("parent_id") not in ids]
    assert len(roots) == 1, [(r["name"], r["parent_id"]) for r in roots]
    return spans, roots[0]


class TestRunTracing:
    def _trace_of(self, records, handle):
        ids = {
            r["trace_id"]
            for r in records
            if r.get("trace_id", "").startswith(handle.run_id + "-")
        }
        assert len(ids) == 1, (handle.run_id, ids)
        return ids.pop()

    def test_worker_run_one_tree_stages_sum_to_wall(self):
        """The differential pin: a scheduler-worker run yields one
        connected tree under one trace_id, and the critical-path stage
        decomposition sums to the root wall within 5% on ManualClock."""
        from tools.trace_report import STAGES, _Tree, decompose, load_traces

        clock = ManualClock()

        def execute(ticket):
            clock.advance(3.0)
            return _FakeResult()

        svc = VerificationService(
            workers=1, clock=clock, execute=execute,
            tenant_max_pending=0, tenant_max_active=0, trace=True,
        ).start()
        try:
            with _TraceSink() as records:
                handle = svc.submit(
                    RunRequest(
                        tenant="acme", checks=(), dataset_key="d",
                        dataset_factory=lambda: None,
                        priority=Priority.STANDARD,
                    )
                )
                assert _spin_until(lambda: handle.done)
                assert _spin_until(
                    lambda: any(
                        r["name"] == "ticket" for r in records
                    )
                )
        finally:
            svc.stop(drain=False, timeout=30)
        trace_id = self._trace_of(records, handle)
        spans, root = _trace_tree(records, trace_id)
        assert root["name"] == "ticket"
        names = {r["name"] for r in spans}
        assert {"queue_wait", "execute"} <= names
        trees = {
            tid: _Tree(sp) for tid, sp in load_traces(records).items()
        }
        decomp = decompose(trace_id, trees)
        assert decomp["wall_s"] >= 3.0
        assert set(decomp["stages"]) <= set(STAGES)
        total = sum(decomp["stages"].values())
        assert abs(total - decomp["wall_s"]) <= 0.05 * decomp["wall_s"]

    def test_coalesced_group_member_traces_link_to_host(self):
        """Each member of a coalesced group gets its OWN connected
        tree; non-host members carry a ``coalesced_scan`` link span
        pointing into the host's execute span."""
        from deequ_tpu.analyzers import Completeness, Mean

        svc = VerificationService(
            workers=1, coalesce=True, coalesce_window_s=0.0, trace=True,
        )
        with _TraceSink() as records:
            handles = [
                svc.submit(
                    RunRequest(
                        tenant=f"t{i}",
                        checks=(),
                        required_analyzers=[Completeness("a"), Mean("b")],
                        dataset_key="shared/traced",
                        dataset_factory=_trace_table,
                        priority=Priority.BATCH,
                    )
                )
                for i in range(3)
            ]
            svc.start()
            try:
                results = [h.result(timeout=300) for h in handles]
            finally:
                svc.stop(drain=False, timeout=30)
        trace_ids = [self._trace_of(records, h) for h in handles]
        assert len(set(trace_ids)) == 3
        link_targets = []
        execute_traces = []
        for trace_id in trace_ids:
            spans, root = _trace_tree(records, trace_id)
            assert root["name"] == "ticket"
            names = {r["name"] for r in spans}
            if "execute" in names:
                execute_traces.append(trace_id)
            for r in spans:
                if r["name"] == "coalesced_scan":
                    attrs = r.get("attributes") or {}
                    link_targets.append(
                        (attrs.get("link_trace_id"),
                         attrs.get("link_span_id"))
                    )
        # ONE host ran the superset scan; the other two link into it
        assert len(execute_traces) == 1
        host_trace = execute_traces[0]
        host_spans, _ = _trace_tree(records, host_trace)
        host_execute = next(
            r for r in host_spans if r["name"] == "execute"
        )
        assert len(link_targets) == 2
        assert all(
            target == (host_trace, host_execute["span_id"])
            for target in link_targets
        )
        # the host tree carries the real engine spans
        host_names = {r["name"] for r in host_spans}
        assert "run:coalesced_analysis" in host_names or any(
            n.startswith("run:") for n in host_names
        )
        assert any(n.startswith("pass:") for n in host_names)
        # every member's sliced result is scoped to its own trace
        for handle, result, trace_id in zip(handles, results, trace_ids):
            assert result.telemetry["trace_id"] == trace_id

    def test_isolated_run_replays_child_spans_into_tree(self):
        """A spawn-child run is still ONE connected tree: the child's
        spans stream back, re-root under the parent's launch span, and
        carry the child process tag."""
        from deequ_tpu.analyzers import Completeness, Mean

        svc = VerificationService(
            workers=1, isolated=True, coalesce=False, trace=True,
        )
        with _TraceSink() as records:
            handle = svc.submit(
                RunRequest(
                    tenant="acme",
                    checks=(),
                    required_analyzers=[Completeness("a"), Mean("b")],
                    dataset_key="iso/traced",
                    dataset_factory=_trace_table,
                    priority=Priority.STANDARD,
                )
            )
            svc.start()
            try:
                result = handle.result(timeout=300)
            finally:
                svc.stop(drain=False, timeout=30)
        assert result.telemetry is not None
        trace_id = self._trace_of(records, handle)
        spans, root = _trace_tree(records, trace_id)
        assert root["name"] == "ticket"
        child_spans = [r for r in spans if r.get("process") == "child"]
        assert child_spans, "no child-process spans replayed"
        assert any(
            r["name"].startswith("run:") for r in child_spans
        )

    def test_endpoints_live_while_running(self):
        """/metrics and /healthz answer DURING a run — stdlib urllib,
        ephemeral port, no new deps."""
        import json as _json
        import urllib.request

        started = threading.Event()
        release = threading.Event()

        def execute(ticket):
            started.set()
            release.wait(10)
            return _FakeResult()

        svc = VerificationService(
            workers=1, clock=ManualClock(), execute=execute,
            tenant_max_pending=0, tenant_max_active=0,
            trace=True, metrics_port=0,
            slo_objectives="interactive=1.0,standard=5.0",
        ).start()
        try:
            assert svc.metrics_server is not None
            assert svc.metrics_server.port > 0
            handle = svc.submit(
                RunRequest(
                    tenant="acme", checks=(), dataset_key="d",
                    dataset_factory=lambda: None,
                    priority=Priority.STANDARD,
                )
            )
            assert started.wait(10)
            base = svc.metrics_server.url
            metrics = urllib.request.urlopen(
                base + "/metrics", timeout=10
            ).read().decode()
            assert "deequ_tpu_service_submitted" in metrics
            health = _json.loads(
                urllib.request.urlopen(
                    base + "/healthz", timeout=10
                ).read().decode()
            )
            assert health["status"] == "ok"
            assert health["workers"] >= 1
            assert "queue" in health and "breakers" in health
            assert "shed" in health
            assert set(health["slo"]["classes"]) == {
                "interactive", "standard",
            }
            release.set()
            assert _spin_until(lambda: handle.done)
        finally:
            release.set()
            svc.stop(drain=False, timeout=30)
        # the endpoint dies with the service — no leaked thread
        assert svc.metrics_server is None

    def test_zero_cost_when_trace_and_port_off(self):
        """Default config: no endpoint thread, no TraceContext, no span
        records at all from a stub service run."""
        def execute(ticket):
            return _FakeResult()

        svc = VerificationService(
            workers=1, clock=ManualClock(), execute=execute,
            tenant_max_pending=0, tenant_max_active=0,
        ).start()
        try:
            assert svc.metrics_server is None
            with _TraceSink() as records:
                handle = svc.submit(
                    RunRequest(
                        tenant="acme", checks=(), dataset_key="d",
                        dataset_factory=lambda: None,
                        priority=Priority.STANDARD,
                    )
                )
                assert _spin_until(lambda: handle.done)
                svc.wait_idle(timeout=10)
        finally:
            svc.stop(drain=False, timeout=30)
        assert records == []
