"""AnalysisRunner: THE engine entry point and pass planner.

Reference: ``src/main/scala/com/amazon/deequ/analyzers/runners/
AnalysisRunner.scala`` (SURVEY.md §2.4, §3.1): dedup analyzers, reuse
repository metrics, check preconditions (failures become failure metrics
immediately), fuse all scan-shareable analyzers into one pass, run one
frequency computation per distinct (grouping columns, filter) shared by
all grouping analyzers over it, assemble an ``AnalyzerContext``, and
optionally aggregate/persist states (the incremental path,
``runOnAggregatedStates``, SURVEY.md §3.2).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from deequ_tpu.analyzers.base import (
    Analyzer,
    GroupingAnalyzer,
    MetricCalculationException,
    ScanShareableAnalyzer,
    wrap_if_necessary,
)
from deequ_tpu.data.table import Dataset, Schema
from deequ_tpu.engine.memory import (
    classify_memory_pressure,
    oom_probe_of,
    record_spill_downgrade,
)
from deequ_tpu.engine.scan import AnalysisEngine
from deequ_tpu.metrics.metric import Metric
from deequ_tpu.telemetry import get_telemetry, merge_summaries
from deequ_tpu.utils.observe import RunMetadata, timed_pass
from deequ_tpu.utils.trylike import Try


# --------------------------------------------------------------------------
# AnalyzerContext
# --------------------------------------------------------------------------


@dataclass
class AnalyzerContext:
    """Map analyzer -> metric (reference: AnalyzerContext.scala), plus
    per-pass wall-time metadata (deequ_tpu.utils.observe — beyond the
    reference, SURVEY.md §5.1) and the raw telemetry run summary it was
    derived from (deequ_tpu.telemetry; None when telemetry is off)."""

    metric_map: Dict[Analyzer, Metric] = field(default_factory=dict)
    run_metadata: Optional["RunMetadata"] = None
    telemetry: Optional[Dict[str, Any]] = None
    # engine.resilience.ScanDegradation when the run's fused scans
    # quarantined batches (docs/RESILIENCE.md); None = clean run
    degradation: Optional[Any] = None
    # engine.deadline.ScanInterruption when the run was cancelled or
    # exhausted its deadline mid-scan — metrics cover only the batches
    # scanned before the interrupt; None = ran to completion
    interruption: Optional[Any] = None

    @staticmethod
    def empty() -> "AnalyzerContext":
        return AnalyzerContext({})

    def all_metrics(self) -> List[Metric]:
        return list(self.metric_map.values())

    def metric(self, analyzer: Analyzer) -> Optional[Metric]:
        return self.metric_map.get(analyzer)

    def subset(self, analyzers: Sequence[Analyzer]) -> "AnalyzerContext":
        """Slice this context down to ``analyzers``, matched by
        :attr:`Analyzer.identity_key` — the projection a coalesced
        superset run uses to hand each tenant exactly what a solo run
        of its suite would have produced. Metrics are matched by
        identity (not object equality) so a tenant's own analyzer
        instances key the returned map; run provenance — metadata,
        telemetry summary, degradation, interruption — is carried
        whole, because it describes the one physical scan every member
        shared."""
        wanted = {a.identity_key: a for a in _dedup(analyzers)}
        sliced = {}
        for have, metric in self.metric_map.items():
            target = wanted.get(have.identity_key)
            if target is not None:
                sliced[target] = metric
        return AnalyzerContext(
            sliced,
            run_metadata=self.run_metadata,
            telemetry=self.telemetry,
            degradation=self.degradation,
            interruption=self.interruption,
        )

    def __add__(self, other: "AnalyzerContext") -> "AnalyzerContext":
        from deequ_tpu.engine.deadline import ScanInterruption
        from deequ_tpu.engine.resilience import ScanDegradation

        merged = dict(self.metric_map)
        merged.update(other.metric_map)
        return AnalyzerContext(
            merged,
            run_metadata=RunMetadata.merge_optional(
                self.run_metadata, other.run_metadata
            ),
            telemetry=merge_summaries([self.telemetry, other.telemetry]),
            degradation=ScanDegradation.merge_optional(
                self.degradation, other.degradation
            ),
            interruption=ScanInterruption.merge_optional(
                self.interruption, other.interruption
            ),
        )

    def success_metrics_as_records(
        self, for_analyzers: Optional[Sequence[Analyzer]] = None
    ) -> List[Dict[str, Any]]:
        """Flat records (entity, instance, name, value) for successful
        metrics — the equivalent of successMetricsAsDataFrame."""
        records = []
        for analyzer, metric in self.metric_map.items():
            if for_analyzers and analyzer not in for_analyzers:
                continue
            for flat in metric.flatten():
                if flat.value.is_success:
                    records.append(
                        {
                            "entity": flat.entity.value,
                            "instance": flat.instance,
                            "name": flat.name,
                            "value": flat.value.get(),
                        }
                    )
        return records

    def success_metrics_as_json(
        self, for_analyzers: Optional[Sequence[Analyzer]] = None
    ) -> str:
        return json.dumps(
            self.success_metrics_as_records(for_analyzers), indent=2
        )

    def success_metrics_as_dataframe(self, for_analyzers=None):
        import pandas as pd

        return pd.DataFrame(
            self.success_metrics_as_records(for_analyzers),
            columns=["entity", "instance", "name", "value"],
        )


# --------------------------------------------------------------------------
# AnalysisRunner
# --------------------------------------------------------------------------


def _dedup(analyzers: Sequence[Analyzer]) -> List[Analyzer]:
    seen = set()
    out = []
    for a in analyzers:
        if a not in seen:
            seen.add(a)
            out.append(a)
    return out


class AnalysisRunner:
    """Static facade mirroring the reference's AnalysisRunner object."""

    @staticmethod
    def on_data(data: Dataset) -> "AnalysisRunBuilder":
        return AnalysisRunBuilder(data)

    @staticmethod
    def do_analysis_run(
        data: Dataset,
        analyzers: Sequence[Analyzer],
        aggregate_with=None,
        save_states_with=None,
        engine: Optional[AnalysisEngine] = None,
        metrics_repository=None,
        reuse_existing_results_for_key=None,
        fail_if_results_missing: bool = False,
        save_or_append_results_with_key=None,
        deadline=None,
        cancel=None,
        row_sink=None,
    ) -> AnalyzerContext:
        """Run the analysis. ``deadline`` (seconds, or a full
        ``RunBudget``) and ``cancel`` (a ``CancelToken``) bound the run
        (docs/RESILIENCE.md): an interrupt mid-scan still RETURNS — a
        context with partial metrics and ``context.interruption`` set —
        it never raises. Config fallbacks ``run_deadline_seconds`` /
        ``batch_stall_seconds`` apply when no explicit envelope is
        given; ``max_concurrent_runs`` queues runs FIFO, and only a run
        whose envelope closes while still QUEUED raises
        (``DeadlineExceeded``/``RunCancelled``) — it never started, so
        there is nothing partial to return."""
        analyzers = _dedup(analyzers)
        if not analyzers:
            return AnalyzerContext.empty()
        engine = engine or AnalysisEngine()

        from deequ_tpu import config
        from deequ_tpu.engine.deadline import (
            RunBudget,
            admission_controller,
            shutdown_installed,
            shutdown_token,
        )

        opts = config.options()
        # materialize the run's envelope onto the engine: explicit
        # params win, then an engine-attached budget/token (left
        # untouched — the profiler shares ONE across its passes), then
        # the config knobs; restored in finally so one engine can serve
        # bounded and unbounded runs interleaved
        prev_budget, prev_cancel = engine.budget, engine.cancel
        if deadline is not None:
            engine.budget = (
                deadline
                if isinstance(deadline, RunBudget)
                else RunBudget(
                    deadline_s=float(deadline),
                    stall_s=opts.batch_stall_seconds or None,
                )
            )
        elif engine.budget is None and (
            opts.run_deadline_seconds > 0 or opts.batch_stall_seconds > 0
        ):
            engine.budget = RunBudget(
                deadline_s=opts.run_deadline_seconds or None,
                stall_s=opts.batch_stall_seconds or None,
            )
        if cancel is not None:
            engine.cancel = cancel

        admitted = False
        limit = opts.max_concurrent_runs
        # high-watermark gate (docs/RESILIENCE.md "Memory pressure"):
        # with a watermark configured, runs also queue once the SUM of
        # their estimated device footprints would exceed it — queueing
        # instead of co-OOMing. Zero-cost default: no watermark -> no
        # estimate, and with no run limit either, no admission at all
        watermark = opts.memory_watermark_bytes
        est_bytes = 0
        if watermark > 0:
            try:
                est_bytes = engine.estimated_run_bytes(data)
            except Exception:  # noqa: BLE001 — unsized source: no gate
                est_bytes = 0
        try:
            if limit > 0 or (watermark > 0 and est_bytes > 0):
                tokens = [engine.cancel]
                if shutdown_installed():
                    tokens.append(shutdown_token())
                admission_controller().acquire(
                    limit,
                    budget=engine.budget,
                    tokens=tokens,
                    estimated_bytes=est_bytes,
                    watermark_bytes=watermark,
                )
                admitted = True
            return AnalysisRunner._do_admitted_run(
                data,
                analyzers,
                aggregate_with=aggregate_with,
                save_states_with=save_states_with,
                engine=engine,
                metrics_repository=metrics_repository,
                reuse_existing_results_for_key=reuse_existing_results_for_key,
                fail_if_results_missing=fail_if_results_missing,
                save_or_append_results_with_key=save_or_append_results_with_key,
                row_sink=row_sink,
            )
        finally:
            if admitted:
                admission_controller().release(est_bytes)
            engine.budget, engine.cancel = prev_budget, prev_cancel

    @staticmethod
    def do_coalesced_analysis_run(
        data: Dataset,
        suites: Sequence[Sequence[Analyzer]],
        engine: Optional[AnalysisEngine] = None,
        deadline=None,
        cancel=None,
    ) -> List[AnalyzerContext]:
        """One scan, many tenants: union every suite's analyzers, run
        ONE ``do_analysis_run`` over the superset, then :meth:`slice
        <AnalyzerContext.subset>` each suite's context back out.
        Analyzer states are commutative monoids and the fused pass
        already slices each vectorized member's state individually, so
        a superset scan's per-analyzer metrics equal a solo run's by
        construction (pinned differentially in tests/test_coalesce.py).
        Returns one context per input suite, in order."""
        union = _dedup([a for suite in suites for a in suite])
        superset = AnalysisRunner.do_analysis_run(
            data,
            union,
            engine=engine,
            deadline=deadline,
            cancel=cancel,
        )
        return [superset.subset(list(suite)) for suite in suites]

    @staticmethod
    def _do_admitted_run(
        data: Dataset,
        analyzers: Sequence[Analyzer],
        aggregate_with=None,
        save_states_with=None,
        engine: Optional[AnalysisEngine] = None,
        metrics_repository=None,
        reuse_existing_results_for_key=None,
        fail_if_results_missing: bool = False,
        save_or_append_results_with_key=None,
        row_sink=None,
    ) -> AnalyzerContext:
        # fresh degradation record for THIS run; every scan the run
        # issues (shared pass + deferred fallbacks) merges into it
        engine.reset_degradation()
        tm = get_telemetry()
        tm.counter("runner.runs").inc()

        # 1) reuse existing metrics from the repository (SURVEY.md §2.4 (1))
        reused = AnalyzerContext.empty()
        if metrics_repository is not None and reuse_existing_results_for_key is not None:
            existing = metrics_repository.load_by_key(
                reuse_existing_results_for_key
            )
            if existing is not None:
                reusable = {
                    a: m
                    for a, m in existing.analyzer_context.metric_map.items()
                    if a in analyzers
                }
                reused = AnalyzerContext(reusable)
            if fail_if_results_missing and len(reused.metric_map) < len(analyzers):
                missing = [a for a in analyzers if a not in reused.metric_map]
                raise RuntimeError(
                    "Could not find all necessary results in the "
                    f"MetricsRepository, missing: {missing}"
                )
        remaining = [a for a in analyzers if a not in reused.metric_map]

        # 2) preconditions against the schema -> immediate failure metrics
        passed: List[Analyzer] = []
        failures: Dict[Analyzer, Metric] = {}
        for analyzer in remaining:
            exc = _check_preconditions(analyzer, data.schema)
            if exc is not None:
                failures[analyzer] = analyzer.to_failure_metric(exc)
            else:
                passed.append(analyzer)

        # 3) partition into scan-shareable / grouping / direct
        scan_shareable = [
            a for a in passed if isinstance(a, ScanShareableAnalyzer)
        ]
        grouping = [a for a in passed if isinstance(a, GroupingAnalyzer)]
        others = [
            a
            for a in passed
            if not isinstance(a, (ScanShareableAnalyzer, GroupingAnalyzer))
        ]

        metrics: Dict[Analyzer, Metric] = dict(failures)
        # explicit metadata stays the DISABLED-telemetry fallback: with
        # telemetry on, the run capture below supersedes it
        metadata = RunMetadata()
        rows = data.num_rows

        with tm.run("analysis") as cap:
            # 4+5) ONE fused scan for every scan-shareable analyzer AND
            # every dense grouping frequency plan — a mixed verification
            # suite costs a single pass over the data (SURVEY.md §2.4);
            # device-sort/Arrow spill plans run right after, reusing the
            # chunks the shared scan just cached
            if scan_shareable or grouping or row_sink is not None:
                with timed_pass(
                    metadata, "scan", rows,
                    len(scan_shareable) + len(grouping),
                ):
                    metrics.update(
                        _run_fused_pass(
                            data, scan_shareable, grouping, engine,
                            aggregate_with, save_states_with, metadata,
                            row_sink=row_sink,
                        )
                    )

            # 6) schema-only analyzers: failure-to-metric conversion via
            # Try.recover (utils/trylike.py), the reference's idiom —
            # a raising to_failure_metric would surface as the Failure
            for analyzer in others:
                metrics[analyzer] = (
                    Try.of(
                        lambda a=analyzer: a.compute_directly(data)  # type: ignore[attr-defined]
                    )
                    .recover(analyzer.to_failure_metric)
                    .get()
                )

        summary = cap.final
        if summary is not None:
            metadata = RunMetadata.from_telemetry_summary(summary)
        n_failed = sum(
            1
            for m in metrics.values()
            if getattr(getattr(m, "value", None), "is_failure", False)
        )
        if n_failed:
            tm.counter("runner.analyzer_failures").inc(n_failed)
        for analyzer, metric in metrics.items():
            tm.analyzer_computed(analyzer, metric)

        degradation = engine.last_degradation
        if degradation is not None and not degradation.is_degraded:
            if degradation.retries == 0:
                degradation = None  # clean run: no record to carry
        context = reused + AnalyzerContext(
            metrics,
            run_metadata=metadata,
            telemetry=summary,
            degradation=degradation,
            interruption=engine.last_interruption,
        )

        # 7) optionally persist to the metrics repository — including
        # this run's OPERATIONAL records (telemetry.oprecords), so
        # anomaly strategies can trend the system's own throughput
        if metrics_repository is not None and save_or_append_results_with_key is not None:
            from deequ_tpu.repository.base import AnalysisResult
            from deequ_tpu.telemetry import clock as _wall_clock

            _tm = get_telemetry()
            _t0 = _wall_clock()
            current = metrics_repository.load_by_key(
                save_or_append_results_with_key
            )
            combined = (
                current.analyzer_context + context
                if current is not None
                else context
            )
            if summary is not None:
                from deequ_tpu.telemetry.oprecords import operational_metrics

                op = operational_metrics(summary)
                if op:
                    combined = combined + AnalyzerContext(op)
            metrics_repository.save(
                AnalysisResult(save_or_append_results_with_key, combined)
            )
            # traced runs record the repository round trip as a child
            # span — one emit per run, nothing when untraced
            if _tm.current_trace() is not None:
                _tm.emit_span(
                    "persist",
                    _wall_clock() - _t0,
                    dataset_date=getattr(
                        save_or_append_results_with_key, "dataset_date", 0
                    ),
                )

        return context

    @staticmethod
    def run_on_aggregated_states(
        schema: Schema,
        analyzers: Sequence[Analyzer],
        state_loaders: Sequence[Any],
        save_states_with=None,
    ) -> AnalyzerContext:
        """Incremental path: merge persisted states monoidally and compute
        metrics WITHOUT touching data (SURVEY.md §3.2)."""
        analyzers = _dedup(analyzers)
        metrics: Dict[Analyzer, Metric] = {}
        for analyzer in analyzers:
            exc = _check_preconditions(analyzer, schema)
            if exc is not None:
                metrics[analyzer] = analyzer.to_failure_metric(exc)
                continue
            try:
                # load inside the try: a version-mismatch or corrupt
                # state degrades to THIS analyzer's failure metric
                states = [
                    s
                    for loader in state_loaders
                    for s in [loader.load(analyzer)]
                    if s is not None
                ]
                if not states:
                    metrics[analyzer] = analyzer.compute_metric_from_state(None)
                    continue
                merge = _merge_fn_for(states[0])
                # tree fold: O(log N) depth — a left fold over N large
                # frequency states would re-touch the accumulated keys
                # N times (SURVEY.md §3.2's merge is associative, so any
                # shape is valid)
                while len(states) > 1:
                    states = [
                        merge(states[i], states[i + 1])
                        if i + 1 < len(states)
                        else states[i]
                        for i in range(0, len(states), 2)
                    ]
                merged = states[0]
                if save_states_with is not None:
                    save_states_with.persist(analyzer, merged)
                metrics[analyzer] = analyzer.compute_metric_from_state(merged)
            except Exception as exc:  # noqa: BLE001
                metrics[analyzer] = analyzer.to_failure_metric(exc)
        return AnalyzerContext(metrics)


def _merge_fn_for(state: Any):
    """States carry their own dataset-independent merge (monoid)."""
    merge = getattr(type(state), "merge", None)
    if merge is None:
        raise MetricCalculationException(
            f"state type {type(state).__name__} has no merge"
        )
    return merge


def _check_preconditions(
    analyzer: Analyzer, schema: Schema
) -> Optional[BaseException]:
    try:
        for precondition in analyzer.preconditions():
            precondition(schema)
        return None
    except Exception as exc:  # noqa: BLE001
        return wrap_if_necessary(exc)


@dataclass
class FusedPassPlan:
    """The planned (not yet executed) fused pass: vectorized scan
    units, grouping family plans, the combined ``(adapter, ops)`` scan
    pairs ready for ``engine.run_scan``, and the failure metrics
    planning already produced. First-class so a caller (the service's
    warm path, a future plan registry) can plan once, inspect the
    engine-level ``ScanPlan`` it induces, and execute later — the
    compile/execute split at the runner layer."""

    metrics: Dict[Analyzer, Metric]
    units: List[Any]
    by_plan: Dict[Any, List[Analyzer]]
    dense: List[Any]
    collectors: List[Any]
    deferred: Dict[Any, Any]
    scan_pairs: List[Tuple[Any, Any]]
    # row-level egress (deequ_tpu/egress): a RowSinkPlan whose op rides
    # LAST in scan_pairs — its per-batch bit planes host_fold straight
    # into the quarantine writer; None for ordinary runs
    row_sink: Any = None

    @property
    def empty(self) -> bool:
        return not self.scan_pairs and not self.deferred


def _plan_fused_pass(
    data: Dataset,
    analyzers: List[ScanShareableAnalyzer],
    grouping: List[GroupingAnalyzer],
    engine: AnalysisEngine,
    metadata=None,
    row_sink=None,
    keep_states: bool = False,
) -> FusedPassPlan:
    """Phase 1 of the fused pass: vectorize the scan-shareable
    analyzers, plan the grouping frequency passes, and assemble the
    scan pairs. Per-analyzer plan failures (bad predicate, unknown
    column inside an expression) become failure metrics here without
    aborting the shared pass. ``keep_states``: the run persists or
    merges states (plan_frequency_passes)."""
    from deequ_tpu.analyzers.grouping import (
        FrequencyScanAdapter,
        plan_frequency_passes,
        plans_for,
    )
    from deequ_tpu.engine.vectorize import plan_scan_units

    metrics: Dict[Analyzer, Metric] = {}
    units, plan_failures = plan_scan_units(data, analyzers)
    for analyzer, exc in plan_failures.items():
        metrics[analyzer] = analyzer.to_failure_metric(exc)

    by_plan = plans_for(grouping)
    dense, collectors, deferred = [], [], {}
    if by_plan:
        try:
            dense, collectors, deferred = plan_frequency_passes(
                data,
                list(by_plan.keys()),
                engine,
                events=None if metadata is None else metadata.events,
                keep_states=keep_states,
            )
        except Exception as exc:  # noqa: BLE001 — planning failed for
            # the whole grouping family: every grouping analyzer fails
            for group in by_plan.values():
                for analyzer in group:
                    metrics[analyzer] = analyzer.to_failure_metric(exc)
            by_plan, dense, collectors, deferred = {}, [], [], {}

    scan_pairs = (
        [(unit, unit.ops) for unit in units]
        + [
            (FrequencyScanAdapter(requests), ops)
            for (_p, _d, _s, requests, ops) in dense
        ]
        + [
            (FrequencyScanAdapter(spec.requests), spec.ops)
            for spec in collectors
        ]
    )
    if row_sink is not None:
        # the sink op rides LAST so every metric slice keeps its index
        scan_pairs = scan_pairs + [row_sink.scan_pair]
    return FusedPassPlan(
        metrics=metrics,
        units=units,
        by_plan=by_plan,
        dense=dense,
        collectors=collectors,
        deferred=deferred,
        scan_pairs=scan_pairs,
        row_sink=row_sink,
    )


def _run_fused_pass(
    data: Dataset,
    analyzers: List[ScanShareableAnalyzer],
    grouping: List[GroupingAnalyzer],
    engine: AnalysisEngine,
    aggregate_with,
    save_states_with,
    metadata=None,
    row_sink=None,
) -> Dict[Analyzer, Metric]:
    """Plan + run THE fused scan: scan-shareable analyzers (vectorized
    into stacked group ops, engine/vectorize.py), dense grouping
    frequency plans (scatter-add ScanOps, analyzers/grouping.py), AND
    high-cardinality spill plans (one-pass key collectors,
    analyzers/spill.py) all ride one engine.run_scan — one pass over
    the data, one packed state fetch, then every spill plan's sort
    finalize dispatched before any result is fetched so the per-plan
    sorts overlap. Only host-Arrow fallbacks (and collectors disabled
    via config.one_pass_spill) re-read the source. Plan failures
    degrade to failure metrics without aborting the shared pass; each
    vectorized member's ordinary state is sliced back out afterwards,
    so persistence/merge semantics are identical to the single path.
    Composes ``_plan_fused_pass`` + ``_execute_fused_pass`` — the
    runner-layer compile/execute split."""
    with get_telemetry().span("plan:prepare"):
        pass_plan = _plan_fused_pass(
            data, analyzers, grouping, engine, metadata, row_sink=row_sink,
            keep_states=(
                aggregate_with is not None or save_states_with is not None
            ),
        )
    if pass_plan.empty:
        return pass_plan.metrics
    return _execute_fused_pass(
        pass_plan, data, engine, aggregate_with, save_states_with, metadata
    )


def _execute_fused_pass(
    pass_plan: FusedPassPlan,
    data: Dataset,
    engine: AnalysisEngine,
    aggregate_with,
    save_states_with,
    metadata=None,
) -> Dict[Analyzer, Metric]:
    """Phase 2: drive a planned fused pass — the shared scan, state
    slicing/persistence, grouping finalize, deferred spill fallbacks."""
    from deequ_tpu.analyzers.grouping import (
        finalize_collector_states,
        finalize_dense_states,
        finalize_grouping_metrics,
    )

    metrics = pass_plan.metrics
    units = pass_plan.units
    by_plan = pass_plan.by_plan
    dense = pass_plan.dense
    collectors = pass_plan.collectors
    deferred = pass_plan.deferred
    scan_pairs = pass_plan.scan_pairs
    row_sink = pass_plan.row_sink

    states = None
    if scan_pairs:
        try:
            if row_sink is None:
                try:
                    states = engine.run_scan(data, scan_pairs)
                except Exception as exc:  # noqa: BLE001 — classified
                    if not collectors or classify_memory_pressure(
                        exc
                    ) is None:
                        raise
                    # the collectors' key buffers are the scan's largest
                    # states: each takes its scan fallback, and the
                    # scalars and dense plans scan again without them
                    for spec in collectors:
                        path, run = spec.scan_fallback
                        record_spill_downgrade(
                            "scan", spec.plan.columns, path
                        )
                        deferred[spec.plan] = run
                    scan_pairs = scan_pairs[: len(units) + len(dense)]
                    collectors = []
                    states = (
                        engine.run_scan(data, scan_pairs)
                        if scan_pairs else []
                    )
            else:
                # split phases so the sink learns the scan's quarantine
                # geometry (chunk rows resident / batch rows streaming)
                # BEFORE the first fold hits its writer
                scan_plan = engine.prepare_scan(data, scan_pairs)
                row_sink.bind_scan_geometry(scan_plan, data, engine)
                states = engine.execute_plan(scan_plan, data)
                row_sink.note_scan_complete(engine)
            if metadata is not None and engine.phase_times is not None:
                metadata.events.append(
                    {"event": "scan_phases", **engine.phase_times}
                )
        except Exception as exc:  # noqa: BLE001
            if row_sink is not None:
                row_sink.mark_scan_failed()
            wrapped = wrap_if_necessary(exc)
            for unit in units:
                for analyzer in unit.members:
                    metrics[analyzer] = analyzer.to_failure_metric(wrapped)
            for plan, _dicts, _sizes, _req, _ops in dense:
                for analyzer in by_plan.get(plan, []):
                    metrics[analyzer] = analyzer.to_failure_metric(wrapped)
            dense = []
            # a shared-scan failure must not take the spill plans down
            # with it (they ran independently before one-pass fusion):
            # each collector degrades to its own scan fallback (the
            # deferred re-scan, or host Arrow without a resident cache)
            for spec in collectors:
                deferred[spec.plan] = spec.scan_fallback[1]
            collectors = []

    if states is not None:
        for unit, unit_state in zip(units, states[: len(units)]):
            for member_idx, analyzer in enumerate(unit.members):
                try:
                    if unit.extract is not None:
                        state = unit.extract(unit_state, member_idx)
                        merge = _merge_fn_for(state)
                    else:
                        state = unit_state
                        merge = unit.ops.merge
                    if aggregate_with is not None:
                        prior = aggregate_with.load(analyzer)
                        if prior is not None:
                            state = merge(state, prior)
                    if save_states_with is not None:
                        save_states_with.persist(analyzer, state)
                    metrics[analyzer] = analyzer.compute_metric_from_state(
                        state
                    )
                except Exception as exc:  # noqa: BLE001
                    metrics[analyzer] = analyzer.to_failure_metric(exc)

    # grouping finalize: dense states from the shared scan + deferred
    # spill passes; exceptions stay per-plan (one plan's bad decode
    # must not discard its siblings' valid states)
    frequencies: Dict[Any, Any] = {}
    if states is not None and dense:
        for spec, state in zip(
            dense, states[len(units): len(units) + len(dense)]
        ):
            try:
                frequencies.update(
                    finalize_dense_states([spec], [state])
                )
            except Exception as exc:  # noqa: BLE001
                frequencies[spec[0]] = exc
    if states is not None and collectors:
        # dispatch every plan's sort finalize before fetching any
        # result (finalize_collector_states) so the sorts overlap;
        # isolate: one plan's failure stays its own failure metric;
        # the cancel token lets a cancelled run skip the remaining
        # per-plan device sorts instead of finishing them all
        frequencies.update(
            finalize_collector_states(
                collectors,
                # bounded slice: the row-sink op (when present) rides
                # BEHIND the collectors and must not leak into them
                states[
                    len(units) + len(dense):
                    len(units) + len(dense) + len(collectors)
                ],
                isolate=True,
                cancel=engine.cancel,
                oom_probe=oom_probe_of(data),
            )
        )
    for plan, run in deferred.items():
        # an interrupted run must not start ANOTHER pass over the
        # source — the deferred fallbacks degrade to explicit failure
        # metrics naming the interrupt instead
        if engine.last_interruption is not None:
            frequencies[plan] = MetricCalculationException(
                f"run {engine.last_interruption.kind} before the "
                "deferred frequency pass ran: "
                f"{engine.last_interruption.reason}"
            )
            continue
        try:
            frequencies[plan] = run()
        except Exception as exc:  # noqa: BLE001
            frequencies[plan] = exc
    grouped_plans = {
        plan: group
        for plan, group in by_plan.items()
        if plan in frequencies
    }
    if grouped_plans:
        metrics.update(
            finalize_grouping_metrics(
                grouped_plans, frequencies, aggregate_with,
                save_states_with,
            )
        )
    return metrics


# --------------------------------------------------------------------------
# Builder (reference: AnalysisRunBuilder.scala)
# --------------------------------------------------------------------------


class AnalysisRunBuilder:
    def __init__(self, data: Dataset):
        self._data = data
        self._analyzers: List[Analyzer] = []
        self._engine: Optional[AnalysisEngine] = None
        self._aggregate_with = None
        self._save_states_with = None
        self._repository = None
        self._reuse_key = None
        self._fail_if_results_missing = False
        self._save_key = None
        self._deadline = None
        self._cancel = None

    def add_analyzer(self, analyzer: Analyzer) -> "AnalysisRunBuilder":
        self._analyzers.append(analyzer)
        return self

    def add_analyzers(self, analyzers: Sequence[Analyzer]) -> "AnalysisRunBuilder":
        self._analyzers.extend(analyzers)
        return self

    def with_engine(self, engine: AnalysisEngine) -> "AnalysisRunBuilder":
        self._engine = engine
        return self

    def with_deadline(self, deadline) -> "AnalysisRunBuilder":
        """Bound the run: seconds (float) or a full ``RunBudget``."""
        self._deadline = deadline
        return self

    def with_cancel(self, cancel) -> "AnalysisRunBuilder":
        """Attach a ``CancelToken`` — cancelling it mid-run exits the
        scan cleanly with partial metrics + a resumable checkpoint."""
        self._cancel = cancel
        return self

    def aggregate_with(self, state_loader) -> "AnalysisRunBuilder":
        self._aggregate_with = state_loader
        return self

    def save_states_with(self, state_persister) -> "AnalysisRunBuilder":
        self._save_states_with = state_persister
        return self

    def use_repository(self, repository) -> "AnalysisRunBuilder":
        self._repository = repository
        return self

    def reuse_existing_results_for_key(
        self, key, fail_if_results_missing: bool = False
    ) -> "AnalysisRunBuilder":
        self._reuse_key = key
        self._fail_if_results_missing = fail_if_results_missing
        return self

    def save_or_append_result(self, key) -> "AnalysisRunBuilder":
        self._save_key = key
        return self

    def run(self) -> AnalyzerContext:
        return AnalysisRunner.do_analysis_run(
            self._data,
            self._analyzers,
            aggregate_with=self._aggregate_with,
            save_states_with=self._save_states_with,
            engine=self._engine,
            metrics_repository=self._repository,
            reuse_existing_results_for_key=self._reuse_key,
            fail_if_results_missing=self._fail_if_results_missing,
            save_or_append_results_with_key=self._save_key,
            deadline=self._deadline,
            cancel=self._cancel,
        )
