"""Unified telemetry subsystem (deequ_tpu/telemetry/): spans, counters,
run listeners, structured export, and repository-persisted operational
records. docs/OBSERVABILITY.md is the user-facing companion."""

import json
import os
import threading

import pytest

from deequ_tpu.analyzers import (
    AnalysisRunner,
    Completeness,
    Mean,
    Size,
    Uniqueness,
)
from deequ_tpu.telemetry import (
    CollectingRunListener,
    MetricsRegistry,
    Telemetry,
    get_telemetry,
    merge_summaries,
    read_jsonl,
    summarize_phases,
    summary_from_json,
    summary_to_json,
)
from deequ_tpu.telemetry.oprecords import (
    OPERATIONAL_METRICS,
    OperationalAnalyzer,
    operational_metrics,
    operational_values,
)
from fixtures import df_numeric, df_numeric_with_nulls


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------


class TestSpans:
    def test_nesting_and_attributes(self):
        tm = Telemetry(enabled=True, annotate=False)
        finished = []
        tm.add_listener(CollectingRunListener())
        with tm.run("r") as cap:
            with tm.span("outer", phase="x") as outer:
                with tm.span("inner") as inner:
                    inner.set(rows=10)
                assert outer is not inner
        finished = cap.spans
        # children finish first; the run root span closes last
        names = [s["name"] for s in finished]
        assert names == ["inner", "outer", "run:r"]
        inner_rec = finished[0]
        outer_rec = finished[1]
        root_rec = finished[2]
        assert inner_rec["parent_id"] == outer_rec["span_id"]
        assert outer_rec["parent_id"] == root_rec["span_id"]
        assert inner_rec["attributes"] == {"rows": 10}
        assert outer_rec["attributes"] == {"phase": "x"}
        assert all(s["wall_s"] >= 0 for s in finished)

    def test_exception_pops_span(self):
        tm = Telemetry(enabled=True, annotate=False)
        with pytest.raises(ValueError):
            with tm.span("boom"):
                raise ValueError("x")
        assert tm.tracer.current() is None
        # a later span parents correctly (stack not corrupted)
        with tm.run("r") as cap:
            with tm.span("after"):
                pass
        assert cap.spans[0]["name"] == "after"

    def test_thread_safety_parentage(self):
        """Spans on different threads never see each other as parents."""
        tm = Telemetry(enabled=True, annotate=False)
        records = []
        lock = threading.Lock()

        def record(sp):
            with lock:
                records.append(sp.as_record())

        def worker(i):
            with tm.tracer.span(f"outer-{i}", on_finish=record):
                with tm.tracer.span(f"inner-{i}", on_finish=record):
                    pass

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(records) == 16
        by_id = {r["span_id"]: r for r in records}
        for r in records:
            if r["name"].startswith("inner"):
                parent = by_id[r["parent_id"]]
                # the parent is the same-thread outer span
                assert parent["name"] == r["name"].replace(
                    "inner", "outer"
                )
                assert parent["thread"] == r["thread"]
            else:
                assert r["parent_id"] is None

    def test_concurrent_counter_increments(self):
        registry = MetricsRegistry()
        counter = registry.counter("c")

        def bump():
            for _ in range(1000):
                counter.inc()

        threads = [threading.Thread(target=bump) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert counter.value == 8000


# --------------------------------------------------------------------------
# disabled path
# --------------------------------------------------------------------------


class TestDisabled:
    def test_noop_identity(self):
        """Disabled spans/captures are SHARED no-op objects — nothing
        allocated, nothing recorded."""
        tm = Telemetry(enabled=False)
        cm1 = tm.span("a")
        cm2 = tm.span("b", attr=1)
        assert cm1 is cm2  # one nullcontext for every disabled span
        with tm.run("r") as cap:
            with tm.span("x"):
                tm.event("scan_phases", host_wait_s=1.0)
        assert cap.summary(tm.metrics.counters_snapshot()) is None
        assert cap.final is None
        assert cap.spans == [] and cap.events == []
        assert tm.recent() == []

    def test_counters_stay_live_when_disabled(self):
        """Counters are the always-on layer (monotonic accounting —
        bench depends on transfer.bytes deltas)."""
        tm = Telemetry(enabled=False)
        tm.counter("transfer.bytes").inc(123)
        assert tm.metrics.counters_snapshot() == {"transfer.bytes": 123}

    def test_disabled_listeners_not_called(self):
        tm = Telemetry(enabled=False)
        listener = tm.add_listener(CollectingRunListener())
        with tm.run("r"):
            tm.event("e")
        tm.analyzer_computed(object(), object())
        tm.check_evaluated(object(), object())
        assert listener.run_starts == []
        assert listener.engine_events == []
        assert listener.analyzers_computed == []
        assert listener.checks_evaluated == []

    def test_disabled_run_still_yields_run_metadata(self):
        """ctx.run_metadata keeps its classic pass timings even with
        telemetry off (the explicit-metadata fallback path)."""
        from deequ_tpu import telemetry

        telemetry.configure(enabled=False)
        try:
            ctx = AnalysisRunner.do_analysis_run(
                df_numeric(), [Size(), Mean("att1")]
            )
        finally:
            telemetry.configure(enabled=True)
        assert ctx.telemetry is None
        assert [p.name for p in ctx.run_metadata.passes] == ["scan"]
        assert ctx.run_metadata.passes[0].wall_s > 0


# --------------------------------------------------------------------------
# serde / export
# --------------------------------------------------------------------------


class TestExport:
    def _run_summary(self):
        tm = Telemetry(enabled=True, annotate=False)
        with tm.run("serde") as cap:
            tm.counter("transfer.bytes").inc(4096)
            with tm.pass_span("scan", rows=100, num_analyzers=2):
                pass
            tm.event(
                "scan_phases", host_wait_s=0.5, put_s=0.25, mode="x"
            )
        return cap.final

    def test_summary_json_round_trip(self):
        summary = self._run_summary()
        assert summary_from_json(summary_to_json(summary)) == summary

    def test_merge_summaries(self):
        a, b = self._run_summary(), self._run_summary()
        merged = merge_summaries([a, None, b])
        assert merged["wall_s"] == pytest.approx(
            a["wall_s"] + b["wall_s"]
        )
        assert len(merged["passes"]) == 2
        assert merged["counters"]["transfer.bytes"] == 8192
        assert merge_summaries([None, None]) is None
        assert merge_summaries([a]) is a

    def test_summarize_phases(self):
        summary = self._run_summary()
        phases = summarize_phases(summary["events"])
        assert phases["host_wait_s"] == pytest.approx(0.5)
        assert phases["put_s"] == pytest.approx(0.25)
        assert phases["scan_passes"] == 1

    def test_jsonl_artifact(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        tm = Telemetry(enabled=True, jsonl_path=path, annotate=False)
        with tm.run("art"):
            with tm.span("step"):
                pass
            tm.event("grouping_spill", columns=["c"], path="device-sort")
        records = read_jsonl(path)
        types = [r["type"] for r in records]
        # inner span, event, the run's own root span, then the summary
        assert types == ["span", "event", "span", "run_summary"]
        span, event, root, run = records
        assert root["name"] == "run:art"
        assert span["name"] == "step"
        assert event["event"] == "grouping_spill"
        assert run["name"] == "art"
        assert run["counters"] == {}
        # every line is plain JSON (the artifact is the CLI's input)
        with open(path) as fh:
            for line in fh:
                json.loads(line)

    def test_prometheus_exposition(self):
        registry = MetricsRegistry()
        registry.counter("transfer.bytes").inc(10)
        registry.gauge("batch.size").set(2048)
        registry.histogram("pass.wall_s").observe(0.02)
        text = registry.to_prometheus()
        assert "# TYPE deequ_tpu_transfer_bytes counter" in text
        assert "deequ_tpu_transfer_bytes 10" in text
        assert "deequ_tpu_batch_size 2048" in text
        assert 'deequ_tpu_pass_wall_s_bucket{le="+Inf"} 1' in text
        assert "deequ_tpu_pass_wall_s_count 1" in text


# --------------------------------------------------------------------------
# run integration: AnalysisRunner / profiler / verification
# --------------------------------------------------------------------------


class TestRunIntegration:
    def test_context_summary_and_wall_consistency(self):
        ctx = AnalysisRunner.do_analysis_run(
            df_numeric(),
            [Size(), Mean("att1"), Completeness("att2"),
             Uniqueness(["item"])],
        )
        summary = ctx.telemetry
        assert summary is not None
        assert [p["pass"] for p in summary["passes"]] == ["scan"]
        # per-pass walls account for (almost) the whole run wall — the
        # acceptance bound is 10%, everything outside a pass is
        # planning overhead
        pass_wall = sum(p["wall_s"] for p in summary["passes"])
        assert pass_wall <= summary["wall_s"]
        assert pass_wall >= 0.5 * summary["wall_s"]
        # run_metadata is derived FROM the summary — identical walls
        assert [p.wall_s for p in ctx.run_metadata.passes] == [
            p["wall_s"] for p in summary["passes"]
        ]
        # engine counters attributed to the run
        assert summary["counters"]["engine.scans"] >= 1
        assert any(
            e["event"] == "scan_phases" for e in summary["events"]
        )
        span_names = {s["name"] for s in summary["spans"]}
        assert "run:analysis" in span_names
        assert "pass:scan" in span_names

    def test_listener_callbacks_across_a_run(self):
        tm = get_telemetry()
        listener = tm.add_listener(CollectingRunListener())
        try:
            AnalysisRunner.do_analysis_run(
                df_numeric(), [Size(), Mean("att1")]
            )
        finally:
            tm.remove_listener(listener)
        assert len(listener.run_starts) == 1
        assert len(listener.run_ends) == 1
        run_id, name, summary = listener.run_ends[0]
        assert name == "analysis" and summary is not None
        assert listener.pass_starts == [("scan", 6, 2)]
        (pname, wall, rows, n) = listener.pass_ends[0]
        assert (pname, rows, n) == ("scan", 6, 2) and wall > 0
        computed = {a for a, _m in listener.analyzers_computed}
        assert computed == {Size(), Mean("att1")}
        assert any(
            e["event"] == "scan_phases" for e in listener.engine_events
        )

    def test_broken_listener_never_fails_the_run(self):
        class Broken(CollectingRunListener):
            def on_pass_end(self, *args):
                raise RuntimeError("dashboard down")

        tm = get_telemetry()
        before = tm.counter("telemetry.listener_errors").value
        listener = tm.add_listener(Broken())
        try:
            ctx = AnalysisRunner.do_analysis_run(
                df_numeric(), [Size()]
            )
        finally:
            tm.remove_listener(listener)
        assert ctx.metric(Size()).value.is_success
        assert tm.counter("telemetry.listener_errors").value > before

    def test_verification_result_carries_telemetry(self):
        from deequ_tpu.checks.check import Check, CheckLevel
        from deequ_tpu.verification.suite import VerificationSuite

        tm = get_telemetry()
        listener = tm.add_listener(CollectingRunListener())
        check = Check(CheckLevel.ERROR, "size").has_size(lambda n: n == 6)
        try:
            result = (
                VerificationSuite()
                .on_data(df_numeric())
                .add_check(check)
                .run()
            )
        finally:
            tm.remove_listener(listener)
        assert result.telemetry is not None
        assert result.run_metadata is not None
        assert len(listener.checks_evaluated) == 1
        assert listener.checks_evaluated[0][0] is check

    def test_profiler_merges_summaries(self):
        from deequ_tpu.profiles.profiler import ColumnProfiler

        profiles = ColumnProfiler.profile(df_numeric())
        assert profiles.telemetry is not None
        # the profiler's passes all fold into one merged summary whose
        # pass list matches the classic run_metadata view
        assert [p["pass"] for p in profiles.telemetry["passes"]] == [
            p.name for p in profiles.run_metadata.passes
        ]


# --------------------------------------------------------------------------
# operational records: the monitor monitors itself
# --------------------------------------------------------------------------


class TestOperationalRecords:
    def test_operational_values_from_summary(self):
        summary = {
            "wall_s": 2.0,
            "passes": [
                {"pass": "scan", "wall_s": 1.5, "rows": 1000,
                 "num_analyzers": 3}
            ],
            "counters": {
                "transfer.bytes": 8000,
                "engine.plan_cache.hits": 1,
                "engine.traces": 2,
                "grouping.spill.device-sort": 1,
                "grouping.spill.host-arrow": 2,
            },
        }
        values = operational_values(summary)
        assert values["rows"] == 1000
        assert values["rows_per_sec"] == pytest.approx(500.0)
        assert values["bytes_per_row"] == pytest.approx(8.0)
        assert values["spill_events"] == 3
        assert values["plan_cache_hits"] == 1
        assert operational_values(None) == {}
        for name in values:
            assert name in OPERATIONAL_METRICS

    def test_repository_round_trip_and_anomaly_series(self, tmp_path):
        """Operational records persist under the run's ResultKey
        through the FILE repository (full serde) and feed an anomaly
        strategy as an ordinary metric series."""
        from deequ_tpu.anomalydetection.base import (
            AnomalyDetector,
            DataPoint,
        )
        from deequ_tpu.anomalydetection.strategies import (
            SimpleThresholdStrategy,
        )
        from deequ_tpu.repository.base import ResultKey
        from deequ_tpu.repository.fs import FileSystemMetricsRepository

        repo = FileSystemMetricsRepository(
            str(tmp_path / "metrics.json")
        )
        for day in (1000, 2000, 3000):
            (
                AnalysisRunner.on_data(df_numeric())
                .add_analyzers([Size(), Mean("att1")])
                .use_repository(repo)
                .save_or_append_result(
                    ResultKey.of(day, {"dataset": "numeric"})
                )
                .run()
            )

        analyzer = OperationalAnalyzer("rows_per_sec")
        records = (
            repo.load()
            .for_analyzers([analyzer])
            .get_success_metrics_as_records()
        )
        assert len(records) == 3
        assert all(r["name"] == "Operational" for r in records)
        assert all(r["instance"] == "rows_per_sec" for r in records)
        assert all(r["value"] > 0 for r in records)
        assert all(r["entity"] == "Dataset" for r in records)

        # the series drives anomaly detection with zero new machinery
        series = [
            DataPoint(r["dataset_date"], r["value"]) for r in records
        ]
        detector = AnomalyDetector(SimpleThresholdStrategy(lower_bound=0.0))
        ok = detector.is_new_point_anomalous(
            series, DataPoint(4000, series[-1].metric_value)
        )
        bad = detector.is_new_point_anomalous(
            series, DataPoint(4000, -1.0)
        )
        assert not ok.is_anomalous
        assert bad.is_anomalous

    def test_returned_context_stays_clean(self, tmp_path):
        """Operational records go to the REPOSITORY only; the returned
        context (user-visible metrics) is unchanged."""
        from deequ_tpu.repository.base import (
            InMemoryMetricsRepository,
            ResultKey,
        )

        repo = InMemoryMetricsRepository()
        key = ResultKey.of(1, {})
        ctx = (
            AnalysisRunner.on_data(df_numeric())
            .add_analyzers([Size()])
            .use_repository(repo)
            .save_or_append_result(key)
            .run()
        )
        assert not any(
            isinstance(a, OperationalAnalyzer) for a in ctx.metric_map
        )
        saved = repo.load_by_key(key).analyzer_context
        assert any(
            isinstance(a, OperationalAnalyzer) for a in saved.metric_map
        )

    def test_operational_analyzer_never_computes(self):
        from deequ_tpu.analyzers.base import MetricCalculationException

        with pytest.raises(MetricCalculationException):
            OperationalAnalyzer("wall_s").compute_metric_from_state(None)
        assert operational_metrics(None) == {}


# --------------------------------------------------------------------------
# tools: obs_report + lint
# --------------------------------------------------------------------------


class TestTools:
    def test_obs_report_renders_real_artifact(self, tmp_path):
        from deequ_tpu import telemetry
        from tools.obs_report import main as report_main

        path = str(tmp_path / "runs.jsonl")
        telemetry.configure(jsonl_path=path)
        try:
            AnalysisRunner.do_analysis_run(
                df_numeric(), [Size(), Mean("att1")]
            )
        finally:
            telemetry.configure(jsonl_path=None)
        assert report_main([path]) == 0
        assert report_main([path, "--counters"]) == 0

    def test_obs_report_render_content(self, tmp_path, capsys):
        from deequ_tpu import telemetry
        from tools.obs_report import main as report_main

        path = str(tmp_path / "runs.jsonl")
        telemetry.configure(jsonl_path=path)
        try:
            AnalysisRunner.do_analysis_run(
                df_numeric(), [Size(), Uniqueness(["att1"])]
            )
        finally:
            telemetry.configure(jsonl_path=None)
        report_main([path])
        out = capsys.readouterr().out
        assert "run " in out and "(analysis)" in out
        assert "scan" in out
        assert "counters (delta over run):" in out
        assert "engine.scans" in out

    def test_obs_report_egress_line(self, tmp_path, capsys):
        """A row-level-sink run renders the egress line: rows split,
        bytes/row out, encode share (docs/EGRESS.md)."""
        from deequ_tpu import telemetry
        from deequ_tpu.checks import Check, CheckLevel
        from deequ_tpu.egress import RowLevelSink
        from deequ_tpu.verification.suite import VerificationSuite
        from tools.obs_report import main as report_main

        path = str(tmp_path / "runs.jsonl")
        telemetry.configure(jsonl_path=path)
        try:
            VerificationSuite.do_verification_run(
                df_numeric_with_nulls(),
                [Check(CheckLevel.ERROR, "c").is_complete("att1")],
                row_level_sink=RowLevelSink(str(tmp_path / "egress")),
            )
        finally:
            telemetry.configure(jsonl_path=None)
        report_main([path])
        out = capsys.readouterr().out
        assert "egress: " in out
        assert "clean /" in out and "quarantined" in out
        assert "bytes/row out" in out
        assert "encode share" in out

    def test_hot_paths_have_no_adhoc_timing(self):
        """The lint satellite: every clock/trace call outside
        deequ_tpu/telemetry/ is a violation."""
        from tools.telemetry_lint import find_violations

        root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        assert find_violations(root) == []

    def test_lint_catches_a_violation(self, tmp_path):
        from tools.telemetry_lint import find_violations

        bad = tmp_path / "deequ_tpu" / "engine"
        bad.mkdir(parents=True)
        (bad / "rogue.py").write_text(
            "import time\n"
            "# perf_counter in a comment is fine\n"
            "t0 = time.perf_counter()\n"
        )
        violations = find_violations(str(tmp_path))
        assert violations == [
            ("deequ_tpu/engine/rogue.py", 3, "perf_counter")
        ]

    def test_lint_service_bans_direct_time(self, tmp_path):
        """PR 7 rule: service modules run on injected clocks only —
        time.time / time.sleep are violations there (and only there:
        the same tokens in a non-service module stay legal)."""
        from tools.telemetry_lint import find_violations

        bad = tmp_path / "deequ_tpu" / "service"
        bad.mkdir(parents=True)
        (bad / "rogue.py").write_text(
            "import time\n"
            "# time.time in a comment is fine\n"
            "now = time.time()\n"
            "time.sleep(1)\n"
        )
        elsewhere = tmp_path / "deequ_tpu" / "repository"
        elsewhere.mkdir(parents=True)
        (elsewhere / "fine.py").write_text("import time\nt = time.time()\n")
        violations = find_violations(str(tmp_path))
        assert ("deequ_tpu/service/rogue.py", 3, "time.time") in violations
        assert ("deequ_tpu/service/rogue.py", 4, "sleep") in violations
        assert all("fine.py" not in rel for rel, _l, _t in violations)

    def test_lint_service_bans_admission_bypass(self, tmp_path):
        """PR 7 rule: the service must reach the engine through the
        runner's admission layer — a direct run_scan reference in a
        service module flags."""
        from tools.telemetry_lint import find_violations

        bad = tmp_path / "deequ_tpu" / "service"
        bad.mkdir(parents=True)
        (bad / "rogue.py").write_text(
            "def go(engine, ds, pairs):\n"
            "    return engine.run_scan(ds, pairs)\n"
        )
        violations = find_violations(str(tmp_path))
        assert ("deequ_tpu/service/rogue.py", 2, "run_scan") in violations

    def test_lint_real_service_package_is_clean(self):
        """The shipped service package obeys its own rules."""
        from tools.telemetry_lint import find_violations

        root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        service = [
            v for v in find_violations(root)
            if v[0].startswith("deequ_tpu/service/")
        ]
        assert service == []


# --------------------------------------------------------------------------
# end-to-end run tracing (docs/OBSERVABILITY.md "Tracing")
# --------------------------------------------------------------------------


def _traced_child(payload):
    """Spawn-child entry point (module level: pickled by reference)."""
    from deequ_tpu.telemetry import get_telemetry

    with get_telemetry().span("child_work"):
        return payload


def _traced_crash_child(payload):
    """Emits one span (streamed back over the pipe), then dies hard —
    the parent must still know where the child got to."""
    import signal

    from deequ_tpu.telemetry import get_telemetry
    from deequ_tpu.testing.faults import hard_crash

    with get_telemetry().span("doomed_stage"):
        pass
    hard_crash(signal.SIGSEGV)


class _SpanSink:
    """Capture every finished span record on the process telemetry."""

    def __init__(self):
        self.records = []
        self._tm = get_telemetry()

    def __enter__(self):
        self._tm.add_span_sink(self.records.append)
        return self.records

    def __exit__(self, *exc):
        self._tm.remove_span_sink(self.records.append)


def _assert_single_connected_tree(records, trace_id):
    """Every span of the trace reaches ONE root (the synthetic
    ``ticket`` root or the context's reserved root id)."""
    spans = [r for r in records if r.get("trace_id") == trace_id]
    assert spans, f"no spans for trace {trace_id}"
    ids = {r["span_id"] for r in spans}
    roots = [r for r in spans if r.get("parent_id") not in ids]
    assert len(roots) == 1, [(r["name"], r["parent_id"]) for r in roots]
    return spans, roots[0]


class TestRunTracing:
    def test_trace_context_roundtrip(self):
        from deequ_tpu.telemetry import TraceContext

        ctx = TraceContext.mint("run-7", process="host-a")
        assert ctx.trace_id.startswith("run-7-")
        back = TraceContext.decode(ctx.child(123).encode())
        assert back == TraceContext(ctx.trace_id, 123, process="host-a")
        assert TraceContext.decode("garbage") is None
        assert TraceContext.decode("t:notanint:p") is None

    def test_spawn_child_spans_reroot_connected(self):
        """A span emitted INSIDE the spawn child streams back and lands
        under the parent's launching span — one connected tree, child
        spans process-tagged for the fleet timeline."""
        from deequ_tpu.engine.subproc import IsolatedRunner
        from deequ_tpu.telemetry import TraceContext

        tm = get_telemetry()
        ctx = TraceContext.mint("iso-run")
        with _SpanSink() as records:
            with tm.trace_scope(ctx):
                with tm.span("lease_wait"):
                    out = IsolatedRunner(key="trace-ok", use_breaker=False).run(
                        _traced_child, {"x": 1}
                    )
            tm.emit_span(
                "ticket", 0.5, trace=ctx, span_id=ctx.span_id, parent_id=None
            )
        assert out == {"x": 1}
        spans, root = _assert_single_connected_tree(records, ctx.trace_id)
        assert root["name"] == "ticket"
        by_name = {r["name"]: r for r in spans}
        assert by_name["lease_wait"]["parent_id"] == root["span_id"]
        child = by_name["child_work"]
        assert child["process"] == "child"
        # the child's run span parents under the parent's lease span
        run_span = by_name["run:isolated_child"]
        assert run_span["parent_id"] == by_name["lease_wait"]["span_id"]
        assert child["parent_id"] in {r["span_id"] for r in spans}

    def test_crashed_child_streams_spans_before_death(self):
        """Satellite pin: spans that arrived before a SIGSEGV are
        replayed into the parent's tree — trace_report can show where
        the run died."""
        from deequ_tpu.engine.subproc import CrashLoopError, IsolatedRunner
        from deequ_tpu.telemetry import TraceContext

        tm = get_telemetry()
        ctx = TraceContext.mint("crash-run")
        with _SpanSink() as records:
            with tm.trace_scope(ctx):
                with tm.span("lease_wait"):
                    with pytest.raises(CrashLoopError):
                        IsolatedRunner(
                            key="trace-crash",
                            max_relaunches=1,
                            use_breaker=False,
                        ).run(_traced_crash_child, {})
            tm.emit_span(
                "ticket", 0.5, trace=ctx, span_id=ctx.span_id, parent_id=None
            )
        spans, root = _assert_single_connected_tree(records, ctx.trace_id)
        doomed = [r for r in spans if r["name"] == "doomed_stage"]
        assert len(doomed) == 1
        assert doomed[0]["process"] == "child"

    def test_member_provenance_under_coalescing(self):
        """Each coalesced member's sliced result carries telemetry
        scoped to its OWN trace_id — summary and every span record."""
        from deequ_tpu.checks.check import Check, CheckLevel
        from deequ_tpu.data import Dataset
        from deequ_tpu.service import (
            Priority,
            RunRequest,
            VerificationService,
        )

        def _suite(i):
            check = Check(CheckLevel.ERROR, f"tenant-{i}").is_complete(
                "att1"
            )
            if i % 2 == 0:
                check = check.is_complete("att2")
            return [check]

        svc = VerificationService(
            workers=1,
            coalesce=True,
            coalesce_window_s=0.0,
            trace=True,
        )
        handles = [
            svc.submit(
                RunRequest(
                    tenant=f"t{i}",
                    checks=_suite(i),
                    dataset_key="shared/trace-prov",
                    dataset_factory=df_numeric,
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
        for handle, result in zip(handles, results):
            summary = result.telemetry
            assert summary is not None
            trace_id = summary["trace_id"]
            assert trace_id.startswith(handle.run_id + "-")
            assert summary["spans"], "member summary lost its spans"
            assert all(
                sp["trace_id"] == trace_id for sp in summary["spans"]
            )
        # three members, three distinct trace identities over ONE scan
        assert len({r.telemetry["trace_id"] for r in results}) == 3


class TestTracingZeroCost:
    def test_trace_scope_is_shared_noop_when_disabled(self):
        from deequ_tpu.telemetry import TraceContext

        tm = Telemetry(enabled=False, annotate=False)
        ctx = TraceContext.mint("x")
        assert tm.trace_scope(ctx) is tm.trace_scope(None)
        assert tm.current_trace() is None

    def test_untraced_run_emits_no_trace_spans(self):
        """Without an ambient TraceContext the engine emits its live
        spans (the traversal and its ``scan:*`` stages, whose walls are
        the ``scan_phases`` buckets) and no synthetic ones — no
        phase/persist/egress spans, no trace_id tagging."""
        with _SpanSink() as records:
            ctx = AnalysisRunner.do_analysis_run(
                df_numeric(), [Size(), Mean("att1")]
            )
        assert records
        names = {r["name"] for r in records}
        assert not any(n.startswith("phase:") for n in names)
        assert "persist" not in names and "egress" not in names
        assert all(r.get("trace_id") is None for r in records)
        assert {"traverse:fused", "scan:first_step", "scan:sync"} <= names
        (phases,) = [
            e for e in ctx.run_metadata.events
            if e.get("event") == "scan_phases"
        ]
        first = [r["wall_s"] for r in records
                 if r["name"] == "scan:first_step"]
        assert phases["first_step_s"] == pytest.approx(sum(first), abs=1e-5)


class TestFirstStepNamesItsRegisterBuild:
    def test_hll_path_on_first_step_span(self):
        """The first step's span names the register build its plan
        baked in (the CPU's XLA scatter here)."""
        from deequ_tpu.analyzers import ApproxCountDistinct
        from deequ_tpu.sketches import hll

        with _SpanSink() as records:
            AnalysisRunner.do_analysis_run(
                df_numeric(), [Size(), ApproxCountDistinct("att1")]
            )
        first = [r for r in records if r["name"] == "scan:first_step"]
        assert first
        assert {r["attributes"]["hll_path"] for r in first} == {
            hll.register_build_path()
        } == {"xla"}


class TestTraceReportTool:
    def _span(self, trace, sid, parent, name, wall, start=0.0, **attrs):
        return {
            "type": "span", "trace_id": trace, "span_id": sid,
            "parent_id": parent, "name": name, "wall_s": wall,
            "started_at": start, "thread": "t", "attributes": attrs,
        }

    def _records(self):
        return [
            # slow run: queue-bound (8s of 10s in queue_wait)
            self._span("A", 1, None, "ticket", 10.0, run_id="run-a"),
            self._span("A", 2, 1, "queue_wait", 8.0),
            self._span("A", 3, 1, "execute", 2.0, start=8.0),
            # fast run: execute-bound
            self._span("B", 4, None, "ticket", 4.0, run_id="run-b"),
            self._span("B", 5, 4, "queue_wait", 1.0),
            self._span("B", 6, 4, "execute", 3.0, start=1.0),
        ]

    def test_aggregate_names_dominant_p99_stage(self):
        from tools.trace_report import (
            _Tree,
            aggregate,
            decompose,
            load_traces,
        )

        traces = load_traces(self._records())
        trees = {tid: _Tree(sp) for tid, sp in traces.items()}
        decomps = [decompose(tid, trees) for tid in traces]
        agg = aggregate(decomps)
        assert agg["runs"] == 2
        # p99 is the queue-bound run; the report must blame the queue
        assert agg["p99"]["wall_s"] == 10.0
        assert agg["p99"]["dominant_stage"] == "queue_wait"
        assert agg["p50"]["dominant_stage"] == "finalize"
        # per-run stages sum to the root wall exactly
        for d in decomps:
            assert abs(sum(d["stages"].values()) - d["wall_s"]) < 1e-9

    def test_render_waterfall_and_run_filter(self):
        from tools.trace_report import render

        out = render(self._records())
        assert "ticket" in out and "queue_wait" in out
        assert "aggregate over 2 traced run(s):" in out
        assert "dominant stage: queue_wait" in out
        only_a = render(self._records(), run="run-a")
        assert "run-b" not in only_a
        assert render([], run=None).startswith("no traced spans")

    def test_obs_report_all_and_trace_passthrough(self, tmp_path, capsys):
        from tools.obs_report import main as report_main

        path = tmp_path / "runs.jsonl"
        with path.open("w") as fh:
            for rec in self._records():
                fh.write(json.dumps(rec) + "\n")
        assert report_main([str(path), "--all"]) == 0
        out = capsys.readouterr().out
        assert "aggregate over 2 traced run(s):" in out
        assert report_main([str(path), "--trace", "run-b"]) == 0
        out = capsys.readouterr().out
        assert "run-a" not in out
