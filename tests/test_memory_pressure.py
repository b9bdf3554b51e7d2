"""Memory-pressure resilience (docs/RESILIENCE.md "Memory pressure"):
OOM classification, adaptive batch backoff, spill downgrade chain, and
watermark admission.

The load-bearing differential: a scan that backs off to a smaller
effective batch size after an (injected) allocation failure must
produce BIT-IDENTICAL metrics to a run natively configured at that
batch size — same jit specializations, same update/fold sequence. All
faults fire through the ``oom_probe`` protocol (testing/faults.py) with
zero real allocation pressure, and no test here sleeps wall-clock time.
"""

import os
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
from deequ_tpu.engine.deadline import AdmissionController, admission_controller
from deequ_tpu.engine.memory import (
    AdaptiveBatchBackoff,
    BackoffExhausted,
    MemoryPressureError,
    SimulatedResourceExhausted,
    classify_memory_pressure,
    make_backoff,
    simulated_device_oom,
)
from deequ_tpu.engine.resilience import RetryPolicy, ScanKilled, is_transient
from deequ_tpu.engine.scan import AnalysisEngine
from deequ_tpu.io.state_provider import ScanCheckpointer
from deequ_tpu.telemetry import get_telemetry
from deequ_tpu.testing.faults import FaultInjectingDataset
from deequ_tpu.verification.suite import VerificationSuite


def _no_sleep(_s: float) -> None:
    pass


FAST_RETRY = RetryPolicy(max_attempts=3, sleep=_no_sleep)

# protection on, aggressive floor, healing off — the deterministic
# setting for the differential tests (heal would change the partition)
BACKOFF_OPTS = dict(min_batch_rows=8, memory_heal_after_batches=0)


def _table_data(n=1000, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": rng.normal(size=n).tolist(),
        "g": (np.arange(n) % 7).tolist(),
    }


ANALYZERS = [
    Size(),
    Completeness("a"),
    Mean("a"),
    ApproxQuantile("a", 0.5),
    Uniqueness(["g"]),
]


def _metric_values(ctx, analyzers=ANALYZERS):
    out = []
    for a in analyzers:
        value = ctx.metric(a).value
        assert value.is_success, (a, value)
        out.append((str(a), value.get()))
    return out


# mode -> (engine factory, config overrides) at a given batch size. Mesh
# batch sizes round up to a multiple of the 8 virtual devices, so mesh
# geometries below stick to multiples of 8.
def _mode_setup(mode, cpu_mesh, batch_size):
    if mode == "resident":
        return (lambda **kw: AnalysisEngine(**kw)), dict(
            device_cache_bytes=1 << 30, batch_size=batch_size
        )
    if mode == "streaming":
        return (lambda **kw: AnalysisEngine(**kw)), dict(
            device_cache_bytes=0, batch_size=batch_size
        )
    assert mode == "mesh"
    return (lambda **kw: AnalysisEngine(mesh=cpu_mesh, **kw)), dict(
        device_cache_bytes=0, batch_size=batch_size
    )


MODES = ["resident", "streaming", "mesh"]


# per-mode geometry: full batch size, the injected device's row limit,
# and the size backoff settles at (one halving; mesh aligns to the
# 8-device dp extent, so 128 -> 64 instead of 104 -> 52)
def _geometry(mode):
    if mode == "mesh":
        return dict(n=1000, full=128, over=80, settled=64)
    return dict(n=1000, full=104, over=60, settled=52)


# two-level geometry: n chosen so the settled size divides both the
# full batch and the total row count (no partial sub-slice at the tail,
# keeping the sub-batch partition identical to the native run's)
def _geometry2(mode):
    if mode == "mesh":
        return dict(n=1024, full=128, over=40, settled=32)
    return dict(n=1040, full=104, over=30, settled=26)


def _spin_until(predicate, what, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while not predicate():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.001)


def _memory_events(cap):
    return [
        e for e in cap.final["events"]
        if e.get("event") == "scan_memory_pressure"
    ]


# --------------------------------------------------------------------------
# Classification (engine/memory.py)
# --------------------------------------------------------------------------


class TestClassification:
    def test_host_memory_error_classifies(self):
        pressure = classify_memory_pressure(MemoryError("malloc"))
        assert isinstance(pressure, MemoryPressureError)
        assert pressure.origin == "host"

    def test_simulated_device_oom_classifies(self):
        pressure = classify_memory_pressure(simulated_device_oom(104, "d@3"))
        assert pressure is not None and pressure.origin == "device"

    def test_runtime_error_with_marker_classifies(self):
        # jaxlib's XlaRuntimeError subclasses RuntimeError; matched by
        # type NAME + message marker, no jaxlib import needed
        exc = RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to "
            "allocate 1073741824 bytes"
        )
        pressure = classify_memory_pressure(exc)
        assert pressure is not None and pressure.origin == "device"
        assert pressure.__cause__ is exc

    def test_value_error_mentioning_memory_does_not_classify(self):
        # conservative: only runtime-shaped exception types are
        # message-matched — a data error MENTIONING memory stays a
        # deterministic failure (quarantine path, not backoff)
        assert classify_memory_pressure(ValueError("out of memory")) is None

    def test_plain_runtime_error_does_not_classify(self):
        assert classify_memory_pressure(RuntimeError("segfault")) is None

    def test_memory_pressure_error_passes_through(self):
        original = BackoffExhausted("floor hit")
        assert classify_memory_pressure(original) is original
        assert isinstance(original, MemoryPressureError)

    def test_memory_pressure_is_not_transient(self):
        # same-size retry re-OOMs: the retry driver must never treat
        # the family as transient
        assert not is_transient(MemoryPressureError("x"))
        assert not is_transient(SimulatedResourceExhausted("x"))

    def test_simulated_message_carries_byte_count(self):
        exc = simulated_device_oom(104, "dispatch@3")
        assert f"{104 * 8} bytes" in str(exc)
        assert "dispatch@3" in str(exc)


# --------------------------------------------------------------------------
# AdaptiveBatchBackoff state machine (unit level)
# --------------------------------------------------------------------------


class TestBackoffController:
    def test_shrink_halves_to_floor_then_exhausts(self):
        b = AdaptiveBatchBackoff(1024, 100)
        sizes = []
        while b.shrink("dispatch", 0):
            sizes.append(b.effective)
        assert sizes == [512, 256, 128, 100]
        assert b.shrink("dispatch", 1) is False  # stays exhausted
        assert b.effective == 100

    def test_align_keeps_multiples(self):
        b = AdaptiveBatchBackoff(104, 8, align=8)
        sizes = []
        while b.shrink("dispatch", 0):
            sizes.append(b.effective)
        assert sizes == [48, 24, 8]
        assert all(s % 8 == 0 for s in sizes)

    def test_min_rows_clamped_to_full(self):
        b = AdaptiveBatchBackoff(100, 10_000)
        assert b.min_rows == 100
        assert b.shrink("dispatch", 0) is False  # floor == full

    def test_active_property(self):
        b = AdaptiveBatchBackoff(104, 8)
        assert not b.active
        b.shrink("dispatch", 0)
        assert b.active

    def test_heal_after_consecutive_cleans(self):
        b = AdaptiveBatchBackoff(104, 8, heal_after=2)
        b.shrink("dispatch", 0)
        b.shrink("dispatch", 0)
        assert b.effective == 26
        assert b.note_clean() is False
        assert b.note_clean() is True  # second consecutive clean heals
        assert b.effective == 52
        assert b.note_clean() is False
        assert b.note_clean() is True
        assert b.effective == 104
        assert b.note_clean() is False  # at full: nothing to heal

    def test_heal_disabled_by_default(self):
        b = AdaptiveBatchBackoff(104, 8)
        b.shrink("dispatch", 0)
        for _ in range(50):
            assert b.note_clean() is False
        assert b.effective == 52

    def test_shrink_resets_clean_streak(self):
        b = AdaptiveBatchBackoff(104, 8, heal_after=2)
        b.shrink("dispatch", 0)
        assert b.note_clean() is False  # streak 1
        b.shrink("dispatch", 1)  # OOM: streak resets
        assert b.note_clean() is False
        assert b.note_clean() is True  # needs 2 NEW consecutive cleans

    def test_make_backoff_uses_config(self):
        with config.configure(
            min_batch_rows=16, memory_heal_after_batches=5
        ):
            b = make_backoff(1024, align=4)
        assert (b.full, b.min_rows, b.heal_after, b.align) == (
            1024, 16, 5, 4
        )
        with config.configure(memory_backoff=False):
            assert make_backoff(1024) is None

    def test_inactive_controller_emits_no_telemetry(self):
        tm = get_telemetry()
        before = tm.counter("engine.batch_size_backoffs").value
        b = AdaptiveBatchBackoff(104, 8, heal_after=2)
        for _ in range(100):
            b.note_clean()  # no-op while at full size
        assert tm.counter("engine.batch_size_backoffs").value == before


# --------------------------------------------------------------------------
# Engine-level backoff: the differential oracle, all scan paths
# --------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
class TestEngineBackoff:
    def test_backoff_settles_bit_identical(self, mode, cpu_mesh):
        """A device that fits only `over` rows: the scan shrinks once
        and finishes — with metrics EXACTLY equal to a run natively
        configured at the settled batch size."""
        g = _geometry(mode)
        data = _table_data(g["n"])
        make_engine, opts = _mode_setup(mode, cpu_mesh, g["full"])
        _, ref_opts = _mode_setup(mode, cpu_mesh, g["settled"])
        with config.configure(**BACKOFF_OPTS, **ref_opts):
            ref = _metric_values(
                AnalysisRunner.do_analysis_run(
                    Dataset.from_pydict(data), ANALYZERS,
                    engine=make_engine(),
                )
            )
        tm = get_telemetry()
        oom_before = tm.counter("engine.oom_events").value
        backoffs_before = tm.counter("engine.batch_size_backoffs").value
        ds = FaultInjectingDataset(
            Dataset.from_pydict(data), oom_rows_over=g["over"]
        )
        with config.configure(**BACKOFF_OPTS, **opts):
            ctx = AnalysisRunner.do_analysis_run(
                ds, ANALYZERS, engine=make_engine()
            )
        assert _metric_values(ctx) == ref
        assert any(f[0] == "oom" for f in ds.faults_fired)
        assert tm.counter("engine.oom_events").value > oom_before
        assert (
            tm.counter("engine.batch_size_backoffs").value
            - backoffs_before
            == 1
        )
        assert ctx.degradation is None or not ctx.degradation.is_degraded

    def test_two_level_backoff_bit_identical(self, mode, cpu_mesh):
        """Two geometric halvings (full -> half -> quarter) still land
        exactly on the native quarter-size run."""
        g = _geometry2(mode)
        data = _table_data(g["n"])
        make_engine, opts = _mode_setup(mode, cpu_mesh, g["full"])
        _, ref_opts = _mode_setup(mode, cpu_mesh, g["settled"])
        with config.configure(**BACKOFF_OPTS, **ref_opts):
            ref = _metric_values(
                AnalysisRunner.do_analysis_run(
                    Dataset.from_pydict(data), ANALYZERS,
                    engine=make_engine(),
                )
            )
        tm = get_telemetry()
        backoffs_before = tm.counter("engine.batch_size_backoffs").value
        ds = FaultInjectingDataset(
            Dataset.from_pydict(data), oom_rows_over=g["over"]
        )
        with config.configure(**BACKOFF_OPTS, **opts):
            ctx = AnalysisRunner.do_analysis_run(
                ds, ANALYZERS, engine=make_engine()
            )
        assert _metric_values(ctx) == ref
        assert (
            tm.counter("engine.batch_size_backoffs").value
            - backoffs_before
            == 2
        )

    def test_exhausted_backoff_quarantines(self, mode, cpu_mesh):
        """With the floor AT the full batch size there is nothing to
        shrink: a persistent OOM at one unit quarantines that unit
        (PR 3's path) and the scan completes on the rest."""
        g = _geometry(mode)
        make_engine, opts = _mode_setup(mode, cpu_mesh, g["full"])
        tm = get_telemetry()
        q_before = tm.counter("engine.batches_quarantined").value
        ds = FaultInjectingDataset(
            Dataset.from_pydict(_table_data(g["n"])),
            oom_at_batch={2: 99},
        )
        with config.configure(
            min_batch_rows=g["full"], scan_retry=FAST_RETRY, **opts
        ):
            ctx = AnalysisRunner.do_analysis_run(
                ds, ANALYZERS, engine=make_engine()
            )
        degr = ctx.degradation
        assert degr is not None and degr.is_degraded
        assert degr.batches_quarantined == 1
        assert degr.rows_skipped == g["full"]
        assert degr.error_classes == ["BackoffExhausted"]
        assert (
            tm.counter("engine.batches_quarantined").value - q_before == 1
        )
        assert ctx.metric(Size()).value.get() == g["n"] - g["full"]

    def test_heal_restores_full_batch(self, mode, cpu_mesh):
        """One transient OOM shrinks the batch; after the configured
        number of clean units the size heals back to full — visible as
        the oom -> backoff -> heal event sequence and the gauge."""
        g = _geometry(mode)
        make_engine, opts = _mode_setup(mode, cpu_mesh, g["full"])
        tm = get_telemetry()
        ds = FaultInjectingDataset(
            Dataset.from_pydict(_table_data(g["n"])),
            oom_at_batch={0: 1},
        )
        with config.configure(
            min_batch_rows=8, memory_heal_after_batches=2, **opts
        ):
            with tm.run("heal") as cap:
                ctx = AnalysisRunner.do_analysis_run(
                    ds, ANALYZERS, engine=make_engine()
                )
        actions = [e["action"] for e in _memory_events(cap)]
        assert actions == ["oom", "backoff", "heal"]
        assert (
            tm.metrics.gauge("engine.batch_rows_effective").value
            == g["full"]
        )
        assert ctx.metric(Size()).value.get() == g["n"]
        assert ctx.degradation is None or not ctx.degradation.is_degraded


class TestBackoffDisabled:
    def test_dispatch_oom_fails_scan_when_disabled(self):
        """memory_backoff=False restores the pre-backoff contract: a
        dispatch allocation failure aborts the scan (failure metrics),
        is never counted as an OOM event, and never shrinks anything."""
        tm = get_telemetry()
        oom_before = tm.counter("engine.oom_events").value
        ds = FaultInjectingDataset(
            Dataset.from_pydict(_table_data()), oom_at_batch={1: 1}
        )
        with config.configure(
            device_cache_bytes=0,
            batch_size=104,
            memory_backoff=False,
            scan_retry=FAST_RETRY,
        ):
            ctx = AnalysisRunner.do_analysis_run(
                ds, ANALYZERS, engine=AnalysisEngine()
            )
        assert not ctx.metric(Size()).value.is_success
        assert tm.counter("engine.oom_events").value == oom_before

    def test_transfer_stage_oom_backs_off(self):
        """Streaming's host->device transfer is its own guarded stage:
        an OOM there records stage="transfer" and re-feeds the SAME
        rows through the sub-batch path — no rows lost."""
        tm = get_telemetry()
        ds = FaultInjectingDataset(
            Dataset.from_pydict(_table_data()), oom_transfer_at={1: 1}
        )
        with config.configure(
            device_cache_bytes=0, batch_size=104, **BACKOFF_OPTS
        ):
            with tm.run("transfer-oom") as cap:
                ctx = AnalysisRunner.do_analysis_run(
                    ds, ANALYZERS, engine=AnalysisEngine()
                )
        events = _memory_events(cap)
        assert [e["action"] for e in events] == ["oom", "backoff"]
        assert events[0]["stage"] == "transfer"
        assert ds.faults_fired == [("oom", "transfer", 1, 104)]
        assert ctx.metric(Size()).value.get() == 1000
        assert ctx.degradation is None or not ctx.degradation.is_degraded


# --------------------------------------------------------------------------
# Checkpoint/resume across an OOM-backoff boundary
# --------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["resident", "streaming"])
class TestCheckpointAcrossBackoff:
    def test_kill_resume_with_backoff_bit_identical(
        self, mode, cpu_mesh, tmp_path
    ):
        """Checkpoint cursors keep the NOMINAL batch size (backoff is
        internal to a dispatch), so a run killed while backed off
        resumes cleanly — and the resumed total still equals the native
        settled-size run bit-for-bit."""
        g = _geometry(mode)
        data = _table_data(g["n"])
        make_engine, opts = _mode_setup(mode, cpu_mesh, g["full"])
        _, ref_opts = _mode_setup(mode, cpu_mesh, g["settled"])
        tm = get_telemetry()
        with config.configure(
            scan_retry=FAST_RETRY, checkpoint_every_batches=3,
            **BACKOFF_OPTS, **ref_opts,
        ):
            ref = _metric_values(
                AnalysisRunner.do_analysis_run(
                    Dataset.from_pydict(data), ANALYZERS,
                    engine=make_engine(),
                )
            )
        with config.configure(
            scan_retry=FAST_RETRY, checkpoint_every_batches=3,
            **BACKOFF_OPTS, **opts,
        ):
            ckpt = ScanCheckpointer(str(tmp_path))
            engine = make_engine(checkpointer=ckpt)
            ds = FaultInjectingDataset(
                Dataset.from_pydict(data),
                oom_rows_over=g["over"],
                kill_at_batch=7,
            )
            resumes_before = tm.counter("engine.resumes").value
            with pytest.raises(ScanKilled):
                AnalysisRunner.do_analysis_run(ds, ANALYZERS, engine=engine)
            assert ckpt._storage.list_keys("scan-ckpt-")
            ctx = AnalysisRunner.do_analysis_run(ds, ANALYZERS, engine=engine)
            assert tm.counter("engine.resumes").value - resumes_before == 1
        assert _metric_values(ctx) == ref
        assert ckpt._storage.list_keys("scan-ckpt-") == []


# --------------------------------------------------------------------------
# Spill/collector downgrade chain: collector -> deferred -> host Arrow
# --------------------------------------------------------------------------


class TestSpillDowngrade:
    N = 4096

    @pytest.fixture
    def spill_data(self):
        rng = np.random.default_rng(42)
        return {
            "v": rng.normal(size=self.N).tolist(),
            "dense_g": (np.arange(self.N) % 5).tolist(),
            "id": rng.integers(0, 2**40, self.N).tolist(),
        }

    ANALYZERS = None  # set in _analyzers to keep instances fresh

    def _analyzers(self):
        return [
            Size(),
            Mean("v"),
            Uniqueness(["dense_g"]),  # forced onto the spill path
            Uniqueness(["id"]),  # high-cardinality spill plan
        ]

    def _overrides(self, **extra):
        base = dict(
            # resident cache: device_spill_eligible needs it — the
            # chunked sort path is what the downgrade chain protects
            device_cache_bytes=1 << 30,
            batch_size=512,
            scan_retry=FAST_RETRY,
            one_pass_spill=True,
            dense_grouping_budget_bytes=4 * 1024,
            **BACKOFF_OPTS,
        )
        base.update(extra)
        return base

    def _ref(self, cpu_mesh, spill_data, **extra):
        analyzers = self._analyzers()
        with config.configure(**self._overrides(**extra)):
            return _metric_values(
                AnalysisRunner.do_analysis_run(
                    Dataset.from_pydict(spill_data), analyzers,
                    engine=AnalysisEngine(mesh=cpu_mesh),
                ),
                analyzers,
            )

    def test_finalize_oom_downgrades_to_deferred(self, cpu_mesh, spill_data):
        ref = self._ref(cpu_mesh, spill_data)
        tm = get_telemetry()
        before = tm.counter("engine.spill_downgrades").value
        ds = FaultInjectingDataset(
            Dataset.from_pydict(spill_data), oom_finalize=1
        )
        analyzers = self._analyzers()
        with config.configure(**self._overrides()):
            with tm.run("finalize-oom") as cap:
                ctx = AnalysisRunner.do_analysis_run(
                    ds, analyzers, engine=AnalysisEngine(mesh=cpu_mesh)
                )
        assert _metric_values(ctx, analyzers) == ref
        assert tm.counter("engine.spill_downgrades").value - before == 1
        downgrades = [
            e for e in _memory_events(cap)
            if e["action"] == "spill-downgrade"
        ]
        assert len(downgrades) == 1
        assert downgrades[0]["stage"] == "finalize"
        assert downgrades[0]["path"] == "deferred"
        assert ("oom", "finalize", 0, 0) in ds.faults_fired

    def test_finalize_then_deferred_oom_falls_to_arrow(
        self, cpu_mesh, spill_data
    ):
        """Both rungs under pressure: collector finalize OOMs into the
        deferred re-scan, whose own device sort OOMs into Arrow's host
        group_by — results still exact."""
        ref = self._ref(cpu_mesh, spill_data)
        tm = get_telemetry()
        before = tm.counter("engine.spill_downgrades").value
        arrow_before = tm.counter("grouping.spill.host-arrow-oom").value
        ds = FaultInjectingDataset(
            Dataset.from_pydict(spill_data),
            oom_finalize=1,
            oom_deferred=1,
        )
        analyzers = self._analyzers()
        with config.configure(**self._overrides()):
            ctx = AnalysisRunner.do_analysis_run(
                ds, analyzers, engine=AnalysisEngine(mesh=cpu_mesh)
            )
        assert _metric_values(ctx, analyzers) == ref
        assert tm.counter("engine.spill_downgrades").value - before == 2
        assert (
            tm.counter("grouping.spill.host-arrow-oom").value
            - arrow_before
            == 1
        )

    def test_streamed_finalize_oom_falls_to_arrow(self, tmp_path, spill_data):
        """With the cache off, a streamed near-unique key groups in the
        collector with no cached chunks to re-scan: its finalize OOM
        goes to Arrow's host group_by at once, never to the deferred
        re-scan (whose probe would fire the armed deferred fault)."""
        import pyarrow as pa
        import pyarrow.parquet as pq

        pq.write_table(pa.table(spill_data), str(tmp_path / "d.parquet"))
        analyzers = [Size(), Mean("v"), Uniqueness(["id"])]
        options = self._overrides(device_cache_bytes=0)
        with config.configure(**options):
            ref = _metric_values(
                AnalysisRunner.do_analysis_run(
                    Dataset.from_pydict(spill_data), analyzers
                ),
                analyzers,
            )
        tm = get_telemetry()
        before = tm.counter("engine.spill_downgrades").value
        arrow_before = tm.counter("grouping.spill.host-arrow").value
        ds = FaultInjectingDataset(
            Dataset.from_parquet(str(tmp_path), read_batch_rows=512),
            oom_finalize=1,
            oom_deferred=1,
        )
        with config.configure(**options):
            with tm.run("streamed-finalize-oom") as cap:
                ctx = AnalysisRunner.do_analysis_run(ds, analyzers)
        assert _metric_values(ctx, analyzers) == ref
        assert ds.faults_fired == [("oom", "finalize", 0, 0)]
        assert tm.counter("engine.spill_downgrades").value - before == 1
        assert tm.counter("grouping.spill.host-arrow").value == (
            arrow_before + 1
        )
        (downgrade,) = [
            e for e in _memory_events(cap)
            if e["action"] == "spill-downgrade"
        ]
        assert downgrade["stage"] == "finalize"
        assert downgrade["path"] == "host-arrow"

    @pytest.mark.parametrize(
        "cached, path",
        [(True, "deferred"), (False, "host-arrow")],
        ids=["cache-on", "cache-off-parquet"],
    )
    def test_scan_init_oom_keeps_scalars(
        self, tmp_path, cpu_mesh, spill_data, cached, path
    ):
        """A shared scan that cannot allocate its states, the
        collectors' key buffers among them, runs again without the
        collectors: the scalars still come from the scan, and each
        collector takes its scan fallback (the deferred re-scan over
        the resident cache, or host Arrow for a streamed key)."""
        import pyarrow as pa
        import pyarrow.parquet as pq

        analyzers = self._analyzers()
        if cached:
            options = self._overrides()
            engine = AnalysisEngine(mesh=cpu_mesh)
            inner = Dataset.from_pydict(spill_data)
        else:
            options = self._overrides(device_cache_bytes=0)
            engine = None
            pq.write_table(
                pa.table(spill_data), str(tmp_path / "d.parquet")
            )
            inner = Dataset.from_parquet(str(tmp_path), read_batch_rows=512)
        with config.configure(**options):
            ref = _metric_values(
                AnalysisRunner.do_analysis_run(
                    Dataset.from_pydict(spill_data), analyzers, engine=engine
                ),
                analyzers,
            )
        tm = get_telemetry()
        before = tm.counter("engine.spill_downgrades").value
        ds = FaultInjectingDataset(inner, oom_scan_init=1)
        with config.configure(**options):
            with tm.run("scan-init-oom") as cap:
                ctx = AnalysisRunner.do_analysis_run(
                    ds, analyzers, engine=engine
                )
        assert _metric_values(ctx, analyzers) == ref
        assert ds.faults_fired == [("oom", "scan_init", 0, 0)]
        downgrades = [
            e for e in _memory_events(cap)
            if e["action"] == "spill-downgrade"
        ]
        assert downgrades
        assert tm.counter("engine.spill_downgrades").value - before == len(
            downgrades
        )
        assert {(e["stage"], e["path"]) for e in downgrades} == {
            ("scan", path)
        }
        assert ["id"] in [e["columns"] for e in downgrades]

    def test_deferred_path_oom_without_collectors(
        self, cpu_mesh, spill_data
    ):
        """one_pass_spill=False takes the per-plan deferred scans
        directly; a device-sort OOM there downgrades to Arrow."""
        ref = self._ref(cpu_mesh, spill_data)
        tm = get_telemetry()
        before = tm.counter("engine.spill_downgrades").value
        ds = FaultInjectingDataset(
            Dataset.from_pydict(spill_data), oom_deferred=1
        )
        analyzers = self._analyzers()
        with config.configure(**self._overrides(one_pass_spill=False)):
            ctx = AnalysisRunner.do_analysis_run(
                ds, analyzers, engine=AnalysisEngine(mesh=cpu_mesh)
            )
        assert _metric_values(ctx, analyzers) == ref
        assert tm.counter("engine.spill_downgrades").value - before == 1

    def test_spill_suite_backoff_differential(self, spill_data):
        """Batch backoff under a mixed suite (scalars + dense grouping
        + one-pass spill collectors): the collector key buffers fill
        through the sub-batch path and still match the native
        settled-size run exactly. Single-device engine: a resident
        MESH chunk is device_put once with the nominal-batch sharding,
        so a row sub-slice would inherit a different per-device
        partition (different reduction grouping, ~1 ULP) than a
        natively-sized run — value-equal, not bit-equal."""
        analyzers = self._analyzers()
        with config.configure(**self._overrides(batch_size=256)):
            ref = _metric_values(
                AnalysisRunner.do_analysis_run(
                    Dataset.from_pydict(spill_data), analyzers,
                    engine=AnalysisEngine(),
                ),
                analyzers,
            )
        ds = FaultInjectingDataset(
            Dataset.from_pydict(spill_data), oom_rows_over=300
        )
        analyzers = self._analyzers()
        with config.configure(**self._overrides(batch_size=512)):
            ctx = AnalysisRunner.do_analysis_run(
                ds, analyzers, engine=AnalysisEngine()
            )
        assert _metric_values(ctx, analyzers) == ref
        assert any(f[0] == "oom" for f in ds.faults_fired)


# --------------------------------------------------------------------------
# Watermark admission (engine/deadline.py gate + runner plumbing)
# --------------------------------------------------------------------------


class TestWatermarkAdmission:
    def test_second_run_queues_past_watermark(self):
        ctl = AdmissionController()
        ctl.acquire(0, estimated_bytes=600, watermark_bytes=1000)
        assert ctl.snapshot() == {
            "active": 1, "queued": 0, "active_bytes": 600,
        }
        admitted = []

        def worker():
            ctl.acquire(0, estimated_bytes=600, watermark_bytes=1000)
            admitted.append(True)
            ctl.release(600)

        t = threading.Thread(target=worker)
        t.start()
        _spin_until(lambda: ctl.snapshot()["queued"] == 1, "worker queued")
        assert admitted == []  # 600 + 600 > 1000: held back
        ctl.release(600)
        t.join(timeout=5)
        assert admitted == [True]
        assert ctl.snapshot() == {
            "active": 0, "queued": 0, "active_bytes": 0,
        }

    def test_oversized_single_run_admits_when_idle(self):
        # a run bigger than the watermark must not deadlock an idle
        # controller: alone, it always admits (it may still OOM and
        # back off — that is the scan layer's job)
        ctl = AdmissionController()
        ctl.acquire(0, estimated_bytes=10_000, watermark_bytes=100)
        assert ctl.snapshot()["active"] == 1
        assert ctl.snapshot()["active_bytes"] == 10_000
        ctl.release(10_000)
        assert ctl.snapshot()["active_bytes"] == 0

    def test_no_estimate_no_gate(self):
        # unsized sources contribute nothing to the watermark sum and
        # are never held back by it
        ctl = AdmissionController()
        ctl.acquire(0, estimated_bytes=0, watermark_bytes=100)
        ctl.acquire(0, estimated_bytes=0, watermark_bytes=100)
        assert ctl.snapshot() == {
            "active": 2, "queued": 0, "active_bytes": 0,
        }
        ctl.release()
        ctl.release()

    def test_estimated_run_bytes_scales_with_columns(self):
        engine = AnalysisEngine()
        one = Dataset.from_pydict({"a": [1.0] * 100})
        two = Dataset.from_pydict({"a": [1.0] * 100, "b": [2.0] * 100})
        est_one = engine.estimated_run_bytes(one)
        est_two = engine.estimated_run_bytes(two)
        assert 0 < est_one < est_two

    def test_runner_watermark_end_to_end(self):
        tm = get_telemetry()
        queued_before = tm.counter("engine.runs_queued").value
        with config.configure(
            device_cache_bytes=0,
            batch_size=104,
            memory_watermark_bytes=1 << 40,
        ):
            ctx = AnalysisRunner.do_analysis_run(
                Dataset.from_pydict(_table_data()), [Size()]
            )
        assert ctx.metric(Size()).value.get() == 1000
        # uncontended: admitted without queueing, bytes released after
        assert tm.counter("engine.runs_queued").value == queued_before
        snap = admission_controller().snapshot()
        assert snap["active"] == 0
        assert snap["active_bytes"] == 0


# --------------------------------------------------------------------------
# Degradation policy: exhausted backoff floors verification status
# --------------------------------------------------------------------------


class TestDegradationPolicy:
    def _degraded_result(self, policy):
        # the check PASSES on the partial data — status movement below
        # comes from the degradation floor alone
        check = Check(CheckLevel.ERROR, "mem").has_size(lambda s: s > 0)
        ds = FaultInjectingDataset(
            Dataset.from_pydict(_table_data()), oom_at_batch={2: 99}
        )
        with config.configure(
            device_cache_bytes=0,
            batch_size=104,
            min_batch_rows=104,  # floor == full: backoff exhausts at once
            scan_retry=FAST_RETRY,
            degradation_policy=policy,
        ):
            return VerificationSuite.do_verification_run(ds, [check])

    def test_fail_policy_floors_to_error(self):
        result = self._degraded_result("fail")
        assert result.status == CheckStatus.ERROR
        assert result.degradation.batches_quarantined == 1
        assert result.degradation.error_classes == ["BackoffExhausted"]

    def test_warn_policy_floors_to_warning(self):
        result = self._degraded_result("warn")
        assert result.status == CheckStatus.WARNING

    def test_tolerate_policy_keeps_check_status(self):
        result = self._degraded_result("tolerate")
        assert result.status == CheckStatus.SUCCESS
        assert result.degradation.rows_skipped == 104


# --------------------------------------------------------------------------
# Row-level export degrade (verification/rowlevel.py satellite)
# --------------------------------------------------------------------------


class TestRowLevelDegrade:
    def test_one_bad_one_good_predicate_export(self):
        """An unplannable predicate drops ITS row-level column only —
        the plannable constraint still exports."""
        ds = Dataset.from_pydict({"a": [1.0, -2.0, 3.0]})
        check = (
            Check(CheckLevel.ERROR, "rl")
            .satisfies("a >= 0", "a-non-negative", lambda v: v == 1.0)
            .satisfies("nosuchcol >= 0", "phantom-column", lambda v: v == 1.0)
        )
        result = VerificationSuite().on_data(ds).add_check(check).run()
        # aggregate path: the bad constraint reported a FAILURE result
        assert result.status == CheckStatus.ERROR
        rl = result.row_level_results_as_dataset().table
        good = [n for n in rl.schema.names if "a-non-negative" in n]
        assert good, rl.schema.names
        assert rl.column(good[0]).to_pylist() == [True, False, True]
        assert not [n for n in rl.schema.names if "phantom-column" in n]

    def test_bad_where_filter_drops_only_its_column(self):
        ds = Dataset.from_pydict({"a": [1.0, 2.0, 3.0]})
        check = (
            Check(CheckLevel.ERROR, "rl")
            .has_min("a", lambda v: v <= 10)
            .where("nosuchcol > 0")  # unplannable filter
            .has_completeness("a", lambda v: v == 1.0)
        )
        result = VerificationSuite().on_data(ds).add_check(check).run()
        rl = result.row_level_results_as_dataset().table
        names = rl.schema.names
        complete = [n for n in names if "Completeness" in n]
        assert complete, names
        assert rl.column(complete[0]).to_pylist() == [True, True, True]
        assert not [n for n in names if "Minimum" in n]


# --------------------------------------------------------------------------
# Observability: obs_report rendering + run captures
# --------------------------------------------------------------------------


class TestObsReport:
    def test_renders_memory_pressure_lines(self):
        from tools.obs_report import render_run

        summary = {
            "run_id": 1,
            "name": "memory",
            "wall_s": 1.0,
            "counters": {
                "engine.oom_events": 2,
                "engine.batch_size_backoffs": 1,
                "engine.spill_downgrades": 1,
            },
            "events": [
                {
                    "event": "scan_memory_pressure", "action": "oom",
                    "stage": "dispatch", "batch_index": 3, "rows": 104,
                    "origin": "device",
                },
                {
                    "event": "scan_memory_pressure", "action": "backoff",
                    "from_rows": 104, "effective_rows": 52,
                },
                {
                    "event": "scan_memory_pressure", "action": "heal",
                    "from_rows": 52, "effective_rows": 104,
                },
                {
                    "event": "scan_memory_pressure", "action": "exhausted",
                    "batch_index": 5, "effective_rows": 8,
                },
                {
                    "event": "scan_memory_pressure",
                    "action": "spill-downgrade", "stage": "finalize",
                    "columns": ["id"], "path": "deferred",
                },
            ],
        }
        text = render_run(summary)
        assert "engine.oom_events" in text
        assert "engine.batch_size_backoffs" in text
        assert "engine.spill_downgrades" in text
        assert "memory pressure (device) at dispatch batch 3" in text
        assert "batch size backoff: 104 -> 52 rows" in text
        assert "batch size heal: 52 -> 104 rows" in text
        assert "backoff exhausted at batch 5 (floor=8 rows)" in text
        assert "spill downgrade (id): finalize -> deferred" in text

    def test_capture_end_to_end(self):
        from tools.obs_report import render_run

        tm = get_telemetry()
        ds = FaultInjectingDataset(
            Dataset.from_pydict(_table_data()), oom_rows_over=60
        )
        with config.configure(
            device_cache_bytes=0, batch_size=104, **BACKOFF_OPTS
        ):
            with tm.run("memory-report") as cap:
                AnalysisRunner.do_analysis_run(
                    ds, ANALYZERS, engine=AnalysisEngine()
                )
        text = render_run(cap.final)
        assert "engine.oom_events" in text
        assert "memory pressure (device)" in text
        assert "batch size backoff: 104 -> 52 rows" in text


# --------------------------------------------------------------------------
# Zero-cost default
# --------------------------------------------------------------------------


class TestZeroCostDefault:
    def test_clean_run_emits_no_memory_telemetry(self):
        tm = get_telemetry()
        names = (
            "engine.oom_events",
            "engine.batch_size_backoffs",
            "engine.spill_downgrades",
        )
        before = [tm.counter(n).value for n in names]
        with config.configure(device_cache_bytes=0, batch_size=104):
            with tm.run("zero-cost") as cap:
                AnalysisRunner.do_analysis_run(
                    Dataset.from_pydict(_table_data()), ANALYZERS
                )
        assert _memory_events(cap) == []
        assert [tm.counter(n).value for n in names] == before

    def test_protection_off_equals_on_for_clean_data(self):
        data = _table_data()
        with config.configure(device_cache_bytes=0, batch_size=104):
            on = _metric_values(
                AnalysisRunner.do_analysis_run(
                    Dataset.from_pydict(data), ANALYZERS
                )
            )
        with config.configure(
            device_cache_bytes=0, batch_size=104, memory_backoff=False
        ):
            off = _metric_values(
                AnalysisRunner.do_analysis_run(
                    Dataset.from_pydict(data), ANALYZERS
                )
            )
        assert on == off


# --------------------------------------------------------------------------
# telemetry_lint: no ad-hoc OOM classification in the hot path
# --------------------------------------------------------------------------


class TestLintOOMRule:
    def test_repo_hot_paths_are_clean(self):
        from tools.telemetry_lint import find_violations

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert find_violations(root) == []

    def test_adhoc_oom_handling_flagged(self, tmp_path):
        from tools.telemetry_lint import find_violations

        mod = tmp_path / "deequ_tpu" / "engine"
        mod.mkdir(parents=True)
        (mod / "bad.py").write_text(
            "try:\n"
            "    pass\n"
            "except MemoryError:\n"
            "    pass\n"
            "MARKER = 'RESOURCE_EXHAUSTED: boom'\n"
        )
        tokens = {t for _rel, _line, t in find_violations(str(tmp_path))}
        assert "MemoryError" in tokens
        assert "<oom marker string>" in tokens

    def test_memory_module_is_exempt(self, tmp_path):
        from tools.telemetry_lint import find_violations

        mod = tmp_path / "deequ_tpu" / "engine"
        mod.mkdir(parents=True)
        (mod / "memory.py").write_text(
            "MARKERS = ('RESOURCE_EXHAUSTED', 'out of memory')\n"
            "def classify(exc):\n"
            "    if isinstance(exc, MemoryError):\n"
            "        return 'host'\n"
        )
        assert find_violations(str(tmp_path)) == []
