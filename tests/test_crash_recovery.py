"""Crash isolation and restart recovery (docs/RESILIENCE.md,
docs/SERVICE.md): subprocess run isolation (engine/subproc.py), the
durable run journal (service/journal.py), service restart recovery, and
load shedding.

The load-bearing differentials here cross a REAL process boundary: a
child hard-crashes (SIGSEGV/SIGKILL via testing/faults.py — no
exception, no unwinding) and the relaunched child must resume from the
durable checkpoint cursor and finish BIT-IDENTICAL to an uninterrupted
run, on the resident, streaming and mesh paths alike. Every child
function in this module is module-level (spawn pickles by reference);
crash-once semantics cross the relaunch boundary via fsync'd token
marker files, never in-memory state. The autouse reap fixture asserts
no test leaves a zombie child behind.
"""

import json
import multiprocessing
import signal
import threading
import time

import numpy as np
import pytest

from deequ_tpu import config
from deequ_tpu.analyzers import (
    AnalysisRunner,
    ApproxQuantile,
    Completeness,
    Mean,
    Size,
    Uniqueness,
)
from deequ_tpu.checks import Check, CheckLevel, CheckStatus
from deequ_tpu.data import Dataset
from deequ_tpu.engine.deadline import ManualClock
from deequ_tpu.engine.resilience import TransientScanError
from deequ_tpu.engine.subproc import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    BreakerOpen,
    CircuitBreaker,
    CrashLoopError,
    IsolatedRunner,
    ProcessCrashed,
    checkpoint_progress_probe,
    reset_breakers,
)
from deequ_tpu.service import (
    Priority,
    RunRequest,
    RunState,
    ServiceOverloaded,
    VerificationService,
)
from deequ_tpu.service import service as service_module
from deequ_tpu.service.journal import RunJournal
from deequ_tpu.telemetry import get_telemetry
from deequ_tpu.verification.suite import VerificationSuite


@pytest.fixture(autouse=True)
def _reaped_and_reset():
    """Every test must reap its children (no zombies — the contract the
    subprocess-discipline static rule enforces in the product tree) and
    must not leak breaker state into the next test."""
    reset_breakers()
    yield
    assert multiprocessing.active_children() == []
    reset_breakers()


def _table_data(n=1000, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": rng.normal(size=n).tolist(),
        "g": (np.arange(n) % 7).tolist(),
    }


def _analyzers():
    return [
        Size(),
        Completeness("a"),
        Mean("a"),
        ApproxQuantile("a", 0.5),
        Uniqueness(["g"]),
    ]


def _checks(n=1000):
    return [
        Check(CheckLevel.ERROR, "crash-recovery")
        .has_size(lambda s, n=n: s == n)
        .is_complete("a")
    ]


def _result_values(result):
    out = []
    for analyzer, metric in result.metrics.items():
        assert metric.value.is_success, (analyzer, metric.value)
        out.append((str(analyzer), metric.value.get()))
    return sorted(out)


# --------------------------------------------------------------------------
# Spawn-child entry points (module level: pickled by reference; the
# child imports this module via the inherited sys.path)
# --------------------------------------------------------------------------


def _child_ok(payload):
    return {"doubled": payload["x"] * 2}


def _child_raise(payload):
    raise ValueError(payload["message"])


def _child_crash(payload):
    from deequ_tpu.testing.faults import hard_crash

    hard_crash(payload.get("signum"))


def _child_sleep(payload):
    time.sleep(payload.get("seconds", 600))


def _scan_child(payload):
    """Run the resilience-suite scan in a child: mode-specific engine,
    optional token-gated hard-crash fault, checkpointer over a durable
    path — exactly the shape ``IsolatedRunner`` relaunches."""
    from deequ_tpu.engine.scan import AnalysisEngine
    from deequ_tpu.io.state_provider import ScanCheckpointer
    from deequ_tpu.testing.faults import FaultInjectingDataset

    engine_kwargs = {}
    if payload["mode"] == "mesh":
        import jax
        from jax.sharding import Mesh

        engine_kwargs["mesh"] = Mesh(
            np.array(jax.devices("cpu")[:8]), ("dp",)
        )
    ds = Dataset.from_pydict(payload["data"])
    if payload.get("crash_at_batch") is not None:
        ds = FaultInjectingDataset(
            ds,
            crash_at_batch=payload["crash_at_batch"],
            crash_token_path=payload["crash_token_path"],
        )
    opts = dict(
        checkpoint_every_batches=3,
        batch_size=104,
        device_cache_bytes=(1 << 30) if payload["mode"] == "resident" else 0,
    )
    with config.configure(**opts):
        ctx = AnalysisRunner.do_analysis_run(
            ds,
            _analyzers(),
            engine=AnalysisEngine(
                checkpointer=ScanCheckpointer(payload["ckpt_path"]),
                **engine_kwargs,
            ),
        )
    out = []
    for analyzer in _analyzers():
        value = ctx.metric(analyzer).value
        assert value.is_success, (analyzer, value)
        out.append((str(analyzer), value.get()))
    return out


def _service_victim(payload):
    """A whole service daemon that dies by SIGKILL mid-run: submits one
    journaled run over a dataset that hard-crashes the PROCESS at batch
    7 — after the write-ahead submitted record, the started record and
    two checkpoint records have landed durably. Never returns."""
    from deequ_tpu.testing.faults import FaultInjectingDataset

    data = payload["data"]
    ds = FaultInjectingDataset(
        Dataset.from_pydict(data),
        crash_at_batch=7,
        crash_signum=signal.SIGKILL,
    )
    svc = VerificationService(
        workers=1, isolated=False, journal_dir=payload["journal_dir"]
    ).start()
    with config.configure(
        checkpoint_every_batches=3, batch_size=104, device_cache_bytes=0
    ):
        handle = svc.submit(
            RunRequest(
                tenant="acme",
                checks=_checks(),
                dataset=ds,
                priority=Priority.STANDARD,
            )
        )
        handle.wait(timeout=120)  # the SIGKILL lands first
    return "unreachable"


# --------------------------------------------------------------------------
# RunJournal
# --------------------------------------------------------------------------


class TestRunJournal:
    def test_round_trip_and_pending_semantics(self, tmp_path):
        journal = RunJournal(str(tmp_path))
        journal.record_submitted(
            "run-1", tenant="acme", priority=1, deadline_s=30.0,
            dataset_key="ds-a",
        )
        journal.record_submitted("run-2", tenant="beta", priority=2,
                                 deadline_s=None, dataset_key="ds-b")
        journal.record_started("run-1", tenant="acme")
        journal.record_checkpoint("run-1", batch_index=6)
        journal.record_checkpoint("run-1", batch_index=9)
        journal.record_terminal("run-2", RunState.DONE)

        records = journal.replay()
        assert [r["type"] for r in records] == [
            "submitted", "submitted", "started", "checkpoint",
            "checkpoint", "terminal",
        ]
        assert [r["seq"] for r in records] == list(range(1, 7))

        pending = journal.pending_runs()
        assert list(pending) == ["run-1"]  # run-2 reached terminal
        entry = pending["run-1"]
        assert entry["tenant"] == "acme"
        assert entry["priority"] == 1
        assert entry["deadline_s"] == 30.0
        assert entry["started"] is True
        # the LATEST checkpoint wins
        assert entry["last_checkpoint"] == {"batch_index": 9}

    def test_torn_tail_truncates_replay(self, tmp_path):
        journal = RunJournal(str(tmp_path))
        journal.record_submitted("run-1", tenant="acme")
        torn_seq = journal.record_started("run-1")
        journal.record_terminal("run-1", RunState.DONE)
        # corrupt the middle record in place: everything after it is
        # untrusted (truncation semantics), so run-1 reads as pending
        rec = tmp_path / f"runlog-{torn_seq:010d}.rec"
        rec.write_bytes(b"deadbeef\n{not json")
        with get_telemetry().run("torn-tail") as cap:
            replayed = RunJournal(str(tmp_path)).replay()
        assert [r["type"] for r in replayed] == ["submitted"]
        truncations = [
            e for e in cap.final["events"]
            if e.get("event") == "journal_truncated"
        ]
        assert len(truncations) == 1
        assert truncations[0]["at_seq"] == torn_seq
        assert list(RunJournal(str(tmp_path)).pending_runs()) == ["run-1"]

    def test_sequence_continues_across_instances(self, tmp_path):
        first = RunJournal(str(tmp_path))
        first.record_submitted("run-1", tenant="acme")
        first.record_started("run-1")
        reopened = RunJournal(str(tmp_path))
        assert reopened.record_checkpoint("run-1", batch_index=3) == 3
        assert [r["seq"] for r in reopened.replay()] == [1, 2, 3]

    def test_compact_drops_terminal_runs(self, tmp_path):
        journal = RunJournal(str(tmp_path))
        journal.record_submitted("run-1", tenant="acme")
        journal.record_submitted("run-2", tenant="acme")
        journal.record_started("run-1")
        journal.record_terminal("run-1", RunState.DONE)
        assert journal.compact() == 3  # run-1's whole story
        assert list(journal.pending_runs()) == ["run-2"]
        # appended records keep climbing past the compacted tail
        assert journal.record_started("run-2") > 4


# --------------------------------------------------------------------------
# IsolatedRunner basics
# --------------------------------------------------------------------------


class TestIsolatedRunner:
    def test_result_crosses_the_pipe(self):
        runner = IsolatedRunner(key="ok", use_breaker=False)
        assert runner.run(_child_ok, {"x": 21}) == {"doubled": 42}

    def test_in_band_exception_passes_through(self):
        """An ordinary exception is NOT a crash: it ships back over the
        pipe and re-raises in the parent, with no relaunch."""
        tm = get_telemetry()
        crashes_before = tm.counter("engine.child_crashes").value
        runner = IsolatedRunner(key="raise", use_breaker=False)
        with pytest.raises(ValueError, match="decode exploded"):
            runner.run(_child_raise, {"message": "decode exploded"})
        assert tm.counter("engine.child_crashes").value == crashes_before

    def test_sigsegv_classified_and_crash_loop_bounded(self):
        tm = get_telemetry()
        crashes_before = tm.counter("engine.child_crashes").value
        relaunches_before = tm.counter("engine.child_relaunches").value
        loops_before = tm.counter("engine.crash_loops").value
        runner = IsolatedRunner(
            key="poison", max_relaunches=2, use_breaker=False
        )
        with pytest.raises(CrashLoopError) as excinfo:
            runner.run(_child_crash, {"signum": signal.SIGSEGV})
        exc = excinfo.value
        assert exc.launches == 2
        assert exc.last_signal == "SIGSEGV"
        assert isinstance(exc.__cause__, ProcessCrashed)
        assert isinstance(exc.__cause__, TransientScanError)
        assert tm.counter("engine.child_crashes").value - crashes_before == 2
        assert (
            tm.counter("engine.child_relaunches").value - relaunches_before
            == 1
        )
        assert tm.counter("engine.crash_loops").value - loops_before == 1

    def test_timeout_terminates_and_classifies(self):
        runner = IsolatedRunner(
            key="hung", max_relaunches=1, timeout_s=10.0, use_breaker=False
        )
        with pytest.raises(CrashLoopError) as excinfo:
            runner.run(_child_sleep, {"seconds": 600})
        assert excinfo.value.last_signal == "timeout"

    @pytest.mark.parametrize(
        "child_platform, parent_holds_tpu, refused",
        [
            (None, True, True),  # child would default to the held chip
            ("tpu", True, True),
            ("cpu", True, False),  # child pinned off the chip
            (None, False, False),  # parent never started a backend
        ],
    )
    def test_one_process_per_chip(
        self, monkeypatch, child_platform, parent_holds_tpu, refused
    ):
        """A parent holding the TPU refuses, before any spawn, to start
        a child that would reach for it (docs/RESILIENCE.md)."""
        import jax
        from jax._src import xla_bridge

        from deequ_tpu.engine import subproc

        monkeypatch.setattr(
            xla_bridge, "backends_are_initialized", lambda: parent_holds_tpu
        )
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(subproc, "_parent_platform", lambda: child_platform)
        launched = []
        monkeypatch.setattr(
            IsolatedRunner, "_launch_once",
            lambda self, fn, payload, launches: launched.append(1) or 7,
        )
        runner = IsolatedRunner(key="chip", use_breaker=False)
        if refused:
            with pytest.raises(subproc.ChipHeldByParent):
                runner.run(_child_ok, {"x": 1})
            assert not launched
        else:
            assert runner.run(_child_ok, {"x": 1}) == 7
            assert launched == [1]


# --------------------------------------------------------------------------
# Crash → relaunch → bit-identical resume (the differential)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["resident", "streaming", "mesh"])
class TestCrashResumeDifferential:
    def test_crash_then_relaunch_bit_identical(self, mode, tmp_path):
        data = _table_data()
        tm = get_telemetry()
        ref = _scan_child(
            {
                "mode": mode,
                "data": data,
                "ckpt_path": str(tmp_path / "ref-ckpt"),
            }
        )
        ckpt_path = str(tmp_path / "ckpt")
        resumes_before = tm.counter("engine.resumes").value
        crashes_before = tm.counter("engine.child_crashes").value
        crash_resumes_before = tm.counter("engine.crash_resumes").value
        runner = IsolatedRunner(
            key=f"scan:{mode}",
            max_relaunches=3,
            timeout_s=300.0,
            progress_probe=checkpoint_progress_probe(ckpt_path),
            use_breaker=False,
        )
        got = runner.run(
            _scan_child,
            {
                "mode": mode,
                "data": data,
                "ckpt_path": ckpt_path,
                # batch 7 of 10 (104-row batches over 1000 rows), past
                # the cursor the child checkpointed after batch 5
                "crash_at_batch": 7,
                "crash_token_path": str(tmp_path / "crash-token"),
            },
        )
        assert got == ref
        assert tm.counter("engine.child_crashes").value - crashes_before == 1
        assert (
            tm.counter("engine.crash_resumes").value - crash_resumes_before
            == 1
        )
        # the relaunched child's own resume counter folds into the
        # parent's telemetry stream (child summary merge)
        assert tm.counter("engine.resumes").value - resumes_before == 1


# --------------------------------------------------------------------------
# Crash-loop breaker
# --------------------------------------------------------------------------


class TestCrashLoopBreaker:
    def test_loop_opens_fast_fails_then_half_open_probe_closes(self):
        tm = get_telemetry()
        trips_before = tm.counter("engine.breaker_trips").value
        clock = ManualClock()
        breaker = CircuitBreaker(cooldown_s=60.0, clock=clock)
        runner = IsolatedRunner(
            key="plan:poison", max_relaunches=2, breaker=breaker
        )
        with pytest.raises(CrashLoopError):
            runner.run(_child_crash, {"signum": signal.SIGSEGV})
        assert breaker.state == OPEN
        assert tm.counter("engine.breaker_trips").value - trips_before == 1

        # fast-fail while open: no child is spawned at all
        crashes_before = tm.counter("engine.child_crashes").value
        with pytest.raises(BreakerOpen) as excinfo:
            IsolatedRunner(key="plan:poison", breaker=breaker).run(
                _child_ok, {"x": 1}
            )
        assert 0.0 < excinfo.value.retry_after_s <= 60.0
        assert excinfo.value.key == "plan:poison"
        assert tm.counter("engine.child_crashes").value == crashes_before

        # past the cooldown ONE half-open probe is admitted; its
        # success closes the breaker
        clock.advance(61.0)
        probe_runner = IsolatedRunner(key="plan:poison", breaker=breaker)
        assert probe_runner.run(_child_ok, {"x": 2}) == {"doubled": 4}
        assert breaker.state == CLOSED

    def test_half_open_admits_exactly_one_probe(self):
        clock = ManualClock()
        breaker = CircuitBreaker(cooldown_s=30.0, clock=clock)
        breaker.record_crash_loop("k")
        clock.advance(31.0)
        breaker.admit("k")  # the probe slot
        assert breaker.state == HALF_OPEN
        with pytest.raises(BreakerOpen):
            breaker.admit("k")  # concurrent launch during the probe
        breaker.record_success("k")
        assert breaker.state == CLOSED
        breaker.admit("k")  # closed again: free passage

    def test_disabled_by_config(self):
        from deequ_tpu.engine.subproc import breaker_for

        with config.configure(crash_breaker_cooldown_s=0):
            assert breaker_for("any-key") is None


# --------------------------------------------------------------------------
# Service crash-loop flooring (degradation_policy)
# --------------------------------------------------------------------------


def _force_isolation(monkeypatch, svc):
    """Route every run of ``svc`` through the REAL isolated path with a
    crashing child entry: the payload is trivially picklable and the
    module-level crash function replaces ``_isolated_execute`` (looked
    up at call time, pickled by reference to THIS module)."""
    monkeypatch.setattr(
        svc, "_isolation_payload", lambda ticket: {"signum": None}
    )
    monkeypatch.setattr(service_module, "_isolated_execute", _child_crash)


class TestServiceCrashLoopFlooring:
    def _submit_crashing_run(self):
        svc = VerificationService(workers=1, isolated=True)
        svc.start()
        handle = svc.submit(
            RunRequest(
                tenant="acme",
                checks=_checks(),
                dataset=Dataset.from_pydict(_table_data(n=8)),
            )
        )
        return svc, handle

    def test_policy_fail_fails_the_handle(self, monkeypatch):
        with config.configure(
            degradation_policy="fail",
            crash_max_relaunches=1,
            crash_breaker_cooldown_s=0,
        ):
            svc, handle = self._submit_crashing_run()
            _force_isolation(monkeypatch, svc)
            try:
                assert handle.wait(timeout=120)
                assert handle.status == RunState.FAILED
                with pytest.raises(CrashLoopError):
                    handle.result(timeout=0)
            finally:
                svc.stop(drain=False, timeout=10)

    def test_policy_warn_floors_with_provenance(self, monkeypatch):
        with config.configure(
            degradation_policy="warn",
            crash_max_relaunches=1,
            crash_breaker_cooldown_s=0,
        ):
            svc, handle = self._submit_crashing_run()
            _force_isolation(monkeypatch, svc)
            try:
                assert handle.wait(timeout=120)
                assert handle.status == RunState.DONE
                result = handle.result(timeout=0)
            finally:
                svc.stop(drain=False, timeout=10)
        assert result.status == CheckStatus.WARNING
        assert result.metrics == {}
        failure = result.degradation.failures[0]
        assert failure.error_class == "CrashLoopError"
        assert failure.batch_index == -1
        assert failure.attempts >= 1


# --------------------------------------------------------------------------
# Service restart recovery (the journal end-to-end)
# --------------------------------------------------------------------------


class TestServiceRestartRecovery:
    def test_sigkilled_service_recovers_and_resumes(self, tmp_path):
        """The whole daemon dies by SIGKILL mid-run; a fresh service
        over the same journal dir re-admits the run, resumes it from
        the durable checkpoint cursor (content fingerprints match), and
        finishes with the exact metrics of an uninterrupted run."""
        data = _table_data()
        journal_dir = str(tmp_path / "journal")
        victim = IsolatedRunner(
            key="victim", max_relaunches=1, timeout_s=300.0,
            use_breaker=False,
        )
        with pytest.raises(CrashLoopError) as excinfo:
            victim.run(
                _service_victim, {"data": data, "journal_dir": journal_dir}
            )
        assert excinfo.value.last_signal == "SIGKILL"

        # the write-ahead journal survived the kill: submitted +
        # started + checkpoint records, no terminal
        pending = RunJournal(journal_dir).pending_runs()
        assert len(pending) == 1
        (run_id, entry), = pending.items()
        assert entry["started"] is True
        assert entry["last_checkpoint"] is not None

        tm = get_telemetry()
        resumes_before = tm.counter("engine.resumes").value
        recovered_before = tm.counter("service.runs_recovered").value
        with config.configure(
            checkpoint_every_batches=3, batch_size=104, device_cache_bytes=0
        ):
            oracle = VerificationSuite.do_verification_run(
                Dataset.from_pydict(data), _checks()
            )
            svc = VerificationService(
                workers=1, isolated=False, journal_dir=journal_dir
            )
            recovered = svc.recover(
                resolve=lambda rid, e: RunRequest(
                    tenant=e["tenant"],
                    checks=_checks(),
                    dataset=Dataset.from_pydict(data),
                )
            )
            assert [h.run_id for h in recovered] == [run_id]
            assert (
                tm.counter("service.runs_recovered").value
                - recovered_before
                == 1
            )
            svc.start()
            try:
                handle = recovered[0]
                assert handle.wait(timeout=120)
                assert handle.status == RunState.DONE
                result = handle.result(timeout=0)
            finally:
                svc.stop(drain=False, timeout=10)
        # resumed from the DEAD run's cursor, not restarted: the clean
        # dataset's content fingerprint matches the victim's
        assert tm.counter("engine.resumes").value - resumes_before == 1
        assert result.status == CheckStatus.SUCCESS
        assert _result_values(result) == _result_values(oracle)
        # the finished run reached its terminal journal record
        assert RunJournal(journal_dir).pending_runs() == {}

    def test_unresolvable_run_fails_loudly(self, tmp_path):
        journal_dir = str(tmp_path / "journal")
        RunJournal(journal_dir).record_submitted(
            "run-9", tenant="ghost", priority=1, deadline_s=None,
            dataset_key="gone",
        )
        svc = VerificationService(
            workers=1, isolated=False, journal_dir=journal_dir,
            execute=lambda ticket: None,
        )
        assert svc.recover(resolve=lambda rid, e: None) == []
        journal = RunJournal(journal_dir)
        assert journal.pending_runs() == {}
        # a fresh service must not mint run ids that collide with
        # journaled ones
        handle = svc.submit(
            RunRequest(
                tenant="acme",
                checks=[],
                dataset=Dataset.from_pydict({"a": [1.0]}),
            )
        )
        assert int(handle.run_id.rsplit("-", 1)[-1]) > 9


# --------------------------------------------------------------------------
# Load shedding
# --------------------------------------------------------------------------


class TestLoadShedding:
    def test_deep_queue_sheds_batch_not_standard(self):
        release = threading.Event()
        started = threading.Event()

        def _blocking_execute(ticket):
            started.set()
            release.wait(timeout=30)
            return None

        tm = get_telemetry()
        shed_before = tm.counter("service.submissions_shed").value
        svc = VerificationService(
            workers=1,
            execute=_blocking_execute,
            shed_queue_depth=2,
            shed_crash_rate=0,
        )
        svc.start()
        try:
            def _req(priority):
                return RunRequest(
                    tenant="acme",
                    checks=[],
                    dataset=Dataset.from_pydict({"a": [1.0]}),
                    priority=priority,
                )

            svc.submit(_req(Priority.STANDARD))
            assert started.wait(timeout=10)
            svc.submit(_req(Priority.STANDARD))
            svc.submit(_req(Priority.STANDARD))  # queue depth now >= 2
            with pytest.raises(ServiceOverloaded) as excinfo:
                svc.submit(_req(Priority.BATCH))
            assert excinfo.value.retry_after_s >= 0.0
            assert (
                tm.counter("service.submissions_shed").value - shed_before
                == 1
            )
            # INTERACTIVE/STANDARD are never shed
            svc.submit(_req(Priority.STANDARD))
            svc.submit(_req(Priority.INTERACTIVE))
        finally:
            release.set()
            svc.stop(drain=False, timeout=10)

    def test_crash_rate_sheds_until_window_drains(self):
        clock = ManualClock()
        svc = VerificationService(
            workers=1,
            clock=clock,
            execute=lambda ticket: None,
            shed_queue_depth=0,
            shed_crash_rate=2,
            shed_crash_window_s=60.0,
        )

        def _req(priority=Priority.BATCH):
            return RunRequest(
                tenant="acme",
                checks=[],
                dataset=Dataset.from_pydict({"a": [1.0]}),
                priority=priority,
            )

        svc._note_crash()
        svc._note_crash()
        with pytest.raises(ServiceOverloaded) as excinfo:
            svc.submit(_req())
        assert 0.0 < excinfo.value.retry_after_s <= 60.0
        # the window drains on the service clock: old crashes expire
        clock.advance(61.0)
        handle = svc.submit(_req())
        assert handle is not None


# --------------------------------------------------------------------------
# Bench harness (crash-proof rounds: probe + autosize, no spawns here)
# --------------------------------------------------------------------------


class TestBenchHarness:
    def test_probe_host_shape(self):
        import bench

        probe = bench.probe_host()
        assert probe["cpu_count"] >= 1
        assert "mem_available_mb" in probe

    def test_autosize_small_host_caps_streamed_rows(self, monkeypatch):
        import bench

        monkeypatch.delenv("DEEQU_TPU_BENCH_SCALE", raising=False)
        sizing = bench.autosize({"cpu_count": 1, "mem_available_mb": 2048})
        assert sizing["row_scale"] == 0.125
        assert sizing["streaming_row_cap"] == 800_000
        # streamed configs stay under the documented crash threshold
        assert bench._sized(100_000_000, sizing, streamed=True) == 800_000
        # and nothing sizes below the statistical floor
        assert bench._sized(200_000, sizing) == 100_000

    def test_autosize_env_override_wins(self, monkeypatch):
        import bench

        monkeypatch.setenv("DEEQU_TPU_BENCH_SCALE", "1.0")
        sizing = bench.autosize({"cpu_count": 1, "mem_available_mb": 1024})
        assert sizing["row_scale"] == 1.0
        assert sizing["streaming_row_cap"] is None

    def test_registry_covers_child_dispatch(self):
        import bench

        assert "profiler" in bench.CONFIG_REGISTRY
        assert all(callable(fn) for fn in bench.CONFIG_REGISTRY.values())

    def test_probe_host_starts_no_backend(self, monkeypatch):
        """The parent of spawned configs must stay off the chip."""
        import jax

        import bench

        def touched(*_a, **_k):
            raise AssertionError("probe_host touched a JAX backend")

        for name in ("default_backend", "devices", "device_count"):
            monkeypatch.setattr(jax, name, touched)
        assert "jax_backend" not in bench.probe_host()

    @pytest.mark.parametrize("headline_ok, rc", [(True, 0), (False, 1)])
    def test_main_exit_code_follows_headline(
        self, monkeypatch, capsys, headline_ok, rc
    ):
        import bench

        def profiler(_args):
            if not headline_ok:
                raise RuntimeError("headline config failed")
            return {
                "rows_per_sec": 1.0,
                "link_mb_per_sec": 1.0,
                "resident_rows_per_sec": 1.0,
            }

        monkeypatch.setitem(bench.CONFIG_REGISTRY, "profiler", profiler)
        assert bench.main(["--quick", "--inline", "--budget", "60"]) == rc
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert ("error" in line) != headline_ok
