"""Device half of row-level egress: constraint masks inside the fused scan.

``plan_row_sink`` classifies a run's constraints into the same families
``verification/rowlevel.py`` (the differential oracle) defines:

- **scan families** (mask/predicate, pattern, traceable asserted-value):
  per-row pass booleans are ordinary traced expressions over the device
  batch — the SAME batch the metric ops already consume — so they ride
  the fused scan as one extra ``ScanOps`` whose per-batch output is the
  bit-packed ``(planes, B/8)`` uint8 matrix plus a valid-row count,
  fetched through the scan's packed epilogue and folded into the
  :class:`~deequ_tpu.egress.writer.QuarantineWriter` via ``host_fold``;
- **deferred families** (Uniqueness/UniqueValueRatio — global by
  nature — and assertions ``jax.eval_shape`` cannot trace): evaluated
  at finalize by the oracle's own ``_outcome_for``, merged with the
  spooled scan bit planes. The run then honestly reports
  ``engine.data_passes == 2`` — these families need a second look at
  the data, exactly like the one-pass-spill fallback.

The sink op sets ``cache_token=None`` (the explicit uncacheable
opt-out): its closures hold this run's writer and dataset-compiled
predicates, so the engine's cross-run plan cache must never resurrect
it — plan-cache keys for every other op are untouched, and
``merge_plans`` compatibility is moot because the service refuses to
coalesce sink runs (``CoalescePolicy``: the artifact is per-run).

Bit order is little-endian per byte to match the writer's
``np.unpackbits(..., bitorder="little")``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deequ_tpu.analyzers.base import pad_pow2
from deequ_tpu.analyzers.base import ScanOps
from deequ_tpu.analyzers.basic import (
    Completeness,
    Compliance,
    Maximum,
    MaxLength,
    Minimum,
    MinLength,
    PatternMatch,
)
from deequ_tpu.analyzers.grouping import Uniqueness, UniqueValueRatio
from deequ_tpu.constraints.constraint import (
    AnalysisBasedConstraint,
    ConstraintDecorator,
)
from deequ_tpu.data.table import ColumnRequest, ROW_MASK
from deequ_tpu.egress.writer import EgressReport, QuarantineWriter, RowLevelSink
from deequ_tpu.sql.predicate import compile_predicate
from deequ_tpu.telemetry import get_telemetry

#: a plane function: (device batch, consts) -> per-row pass booleans
PlaneFn = Callable[[Dict[str, jnp.ndarray], Optional[Dict[str, Any]]], jnp.ndarray]

_BIT_WEIGHTS = np.array([1, 2, 4, 8, 16, 32, 64, 128], dtype=np.uint8)


def _assertion_traceable(assertion, dtype) -> bool:
    """True iff the constraint's assertion vectorizes under tracing into
    a per-row boolean (shape-preserving). Assertions that branch on the
    value (``and``/``if``/chained comparisons) raise under tracing and
    fall back to the oracle's per-unique-value path at finalize."""
    try:
        out = jax.eval_shape(
            lambda v: jnp.asarray(assertion(v)),
            jax.ShapeDtypeStruct((4,), dtype),
        )
    except Exception:  # noqa: BLE001 — untraceable, not an error
        return False
    return tuple(out.shape) == (4,)


def _mask_key(column: str) -> str:
    return f"{column}::mask"


@dataclass
class _PlaneSpec:
    """One scan-evaluated outcome column."""

    name: str
    fn: PlaneFn
    requests: Tuple[ColumnRequest, ...]
    #: index into the where-exclusion planes, or None (no filter)
    excl: Optional[int] = None


@dataclass
class _Deferred:
    name: str
    analyzer: Any
    assertion: Any
    where: Optional[str]


class _SinkScanAdapter:
    """Pairs with the sink ScanOps in the runner's ``scan_pairs`` list —
    the same adapter shape ``ScanUnit``/collector adapters use."""

    def __init__(self, requests: Sequence[ColumnRequest]):
        self._requests = tuple(requests)

    def device_requests(self, dataset) -> Tuple[ColumnRequest, ...]:
        return self._requests

    def __repr__(self) -> str:  # pragma: no cover — debugging aid
        return f"RowSinkAdapter({len(self._requests)} requests)"


@dataclass
class RowSinkPlan:
    """Everything the run threads through the fused pass for one sink:
    the op that rides the scan, the writer it folds into, and the
    deferred work finalize still owes."""

    sink: RowLevelSink
    writer: QuarantineWriter
    ops: ScanOps
    adapter: _SinkScanAdapter
    scan_names: List[str]
    deferred: List[_Deferred]
    unsupported: Dict[str, str]
    batch_capacity: int  # rows one plane row can hold (B8 * 8)
    scan_failed: bool = False
    _scan_record: Any = None
    _interrupted: bool = False
    _geometry_bound: bool = field(default=False)

    @property
    def scan_pair(self) -> Tuple[_SinkScanAdapter, ScanOps]:
        return (self.adapter, self.ops)

    def bind_scan_geometry(self, scan_plan, data, engine) -> None:
        """Called between ``prepare_scan`` and ``execute_plan``: fixes
        the quarantine granularity (chunk rows resident, batch rows
        streaming) and arms the live degradation probe so failed units
        interleave into the output in source order."""
        from deequ_tpu.engine.scan import CHUNK_BATCHES

        if scan_plan.batch_size > self.batch_capacity:
            raise RuntimeError(
                f"egress planned for batch_size <= {self.batch_capacity} "
                f"but the scan resolved {scan_plan.batch_size}"
            )
        unit_rows = scan_plan.batch_size
        if scan_plan.mode == "resident":
            chunk_batches = min(
                CHUNK_BATCHES, data.num_batches(scan_plan.batch_size)
            )
            unit_rows = chunk_batches * scan_plan.batch_size
        self.writer.bind_geometry(unit_rows, scan_plan.batch_size)
        # mid-scan the live record is active_degradation; folds drained
        # in the scan's epilogue land AFTER the engine merged + cleared
        # it, so fall back to the run-scoped merged record (the runner
        # resets it per run, and this scan is the run's first)
        self.writer.set_degradation_probe(
            lambda: getattr(engine, "active_degradation", None)
            or engine.last_degradation
        )
        # arm the engine's durable-egress hooks for THIS scan only:
        # the checkpoint writer flushes the open span before saving the
        # cursor, and the resume path reconciles the writer with the
        # checkpoint before restarting (engine/scan.py)
        engine.active_egress = self.writer
        self._geometry_bound = True

    def note_scan_complete(self, engine) -> None:
        # later scans in this run (deferred-family fallbacks) must not
        # touch the sink's durable state
        engine.active_egress = None
        self._scan_record = (
            getattr(engine, "active_degradation", None)
            or engine.last_degradation
        )
        self._interrupted = engine.last_interruption is not None

    def mark_scan_failed(self) -> None:
        self.scan_failed = True


def _classify_constraints(checks, data):
    """Walk every check's constraints once, mirroring the oracle's
    family dispatch, and build plane functions for the scan families."""
    planes: List[_PlaneSpec] = []
    deferred: List[_Deferred] = []
    unsupported: Dict[str, str] = {}
    consts: Dict[str, np.ndarray] = {}
    where_planes: List[PlaneFn] = []
    where_index: Dict[str, int] = {}
    where_requests: List[ColumnRequest] = []
    seen: set = set()

    def _where_plane(where: Optional[str]) -> Optional[int]:
        if where is None:
            return None
        if where in where_index:
            return where_index[where]
        pred = compile_predicate(where, data)
        where_requests.extend(pred.requests)
        where_requests.extend(
            ColumnRequest(c, "mask") for c in pred.columns_used
        )

        def excl_fn(batch, _consts, _pred=pred):
            # True for rows EXCLUDED by the filter (oracle: _where_pass)
            return ~_pred.complies(batch)

        where_index[where] = len(where_planes)
        where_planes.append(excl_fn)
        return where_index[where]

    for check in checks:
        for constraint in getattr(check, "constraints", ()):
            inner = (
                constraint.inner
                if isinstance(constraint, ConstraintDecorator)
                else constraint
            )
            if not isinstance(inner, AnalysisBasedConstraint):
                continue
            name = str(constraint)
            if name in seen:
                continue
            analyzer = inner.analyzer
            where = getattr(analyzer, "where", None)
            try:
                spec = _plane_for(
                    analyzer, inner.assertion, where, data, consts,
                    _where_plane,
                )
            except Exception as exc:  # noqa: BLE001 — degrade this
                # constraint only (oracle: row_level_results' per-
                # constraint try/except); the aggregate path already
                # reports the same exception as a FAILURE result
                seen.add(name)
                unsupported[name] = f"{type(exc).__name__}: {exc}"
                continue
            if spec is None:
                continue  # not a row-level-capable family
            seen.add(name)
            if isinstance(spec, _Deferred):
                spec.name = name
                deferred.append(spec)
            else:
                spec.name = name
                planes.append(spec)
    return planes, list(where_index), where_planes, deferred, unsupported, consts, where_requests


def _plane_for(
    analyzer, assertion, where, data, consts, where_plane
):
    """One constraint -> a _PlaneSpec (rides the scan), a _Deferred
    (finalize phase), or None (not row-level capable). Raises to mark
    the constraint unsupported (bad predicate/pattern)."""
    if isinstance(analyzer, (Uniqueness, UniqueValueRatio)):
        # global by nature — always the oracle's two-pass path
        return _Deferred("", analyzer, assertion, where)

    if isinstance(analyzer, Completeness):
        col = analyzer.column
        excl = where_plane(where)

        def fn(batch, _consts, _k=_mask_key(col)):
            return batch[_k]

        return _PlaneSpec("", fn, (ColumnRequest(col, "mask"),), excl)

    if isinstance(analyzer, Compliance):
        pred = compile_predicate(analyzer.predicate, data)
        excl = where_plane(where)
        reqs = tuple(pred.requests) + tuple(
            ColumnRequest(c, "mask") for c in pred.columns_used
        )

        def fn(batch, _consts, _pred=pred):
            return _pred.complies(batch)

        return _PlaneSpec("", fn, reqs, excl)

    if isinstance(analyzer, PatternMatch):
        from deequ_tpu.data.strings import dictionary_matches

        col = analyzer.column
        dictionary = data.dictionary(col)
        lut = np.zeros(max(len(dictionary), 1) + 1, dtype=bool)
        lut[: len(dictionary)] = dictionary_matches(
            dictionary, analyzer.pattern
        )
        null_idx = len(lut) - 1
        key = f"__rl_lut_{len(consts)}"
        consts[key] = pad_pow2(lut)
        excl = where_plane(where)

        def fn(batch, c, _key=key, _null=null_idx, _col=col):
            lut_d = c[_key]
            # codes arrive wire-narrowed (int16 for small dicts); the
            # LUT gather needs int32 lest a >32k dictionary overflow
            codes = batch[f"{_col}::codes"].astype(jnp.int32)
            idx = jnp.where(codes < 0, _null, codes)
            idx = jnp.clip(idx, 0, lut_d.shape[0] - 1)
            return lut_d[idx] & batch[_mask_key(_col)]

        reqs = (ColumnRequest(col, "codes"), ColumnRequest(col, "mask"))
        return _PlaneSpec("", fn, reqs, excl)

    if isinstance(analyzer, (MinLength, MaxLength, Minimum, Maximum)):
        if assertion is None:
            return None
        repr_name = (
            "lengths" if isinstance(analyzer, (MinLength, MaxLength))
            else "values"
        )
        col = analyzer.column
        dtype = data.request_dtype(ColumnRequest(col, repr_name))
        if not _assertion_traceable(assertion, dtype):
            return _Deferred("", analyzer, assertion, where)
        excl = where_plane(where)

        def fn(batch, _consts, _a=assertion, _col=col, _r=repr_name):
            values = batch[f"{_col}::{_r}"]
            passes = jnp.asarray(_a(values)).astype(jnp.bool_)
            # null rows pass (NullBehavior.Ignore): placeholder lanes
            # may compute garbage, the mask overrides them
            return ~batch[_mask_key(_col)] | passes

        reqs = (ColumnRequest(col, repr_name), ColumnRequest(col, "mask"))
        return _PlaneSpec("", fn, reqs, excl)

    return None  # not a row-level family (Size, Mean, ...)


def _build_ops(
    planes: Sequence[_PlaneSpec],
    where_planes: Sequence[PlaneFn],
    consts: Dict[str, np.ndarray],
    b8: int,
    writer: QuarantineWriter,
) -> ScanOps:
    """The sink ScanOps: a fixed-shape ``(n_planes, B/8)`` uint8 state
    (fixed so OOM sub-slice re-dispatches chain through identically
    shaped jits), little-endian bit-packed on device; ``host_fold``
    hands each fold straight to the writer — the packed epilogue is the
    only device->host hop."""
    plane_fns: List[PlaneFn] = [p.fn for p in planes] + list(where_planes)
    n_planes = len(plane_fns)
    total_bits = b8 * 8
    weights = jnp.asarray(_BIT_WEIGHTS)

    def _pack(batch, c):
        if plane_fns:
            m = jnp.stack(
                [
                    jnp.asarray(f(batch, c)).astype(jnp.bool_)
                    for f in plane_fns
                ]
            )
            w = m.shape[1]
            m = jnp.pad(m, ((0, 0), (0, total_bits - w)))
            bits = jnp.sum(
                m.reshape(n_planes, b8, 8).astype(jnp.uint8) * weights,
                axis=-1,
                dtype=jnp.uint8,
            )
        else:
            bits = jnp.zeros((0, b8), dtype=jnp.uint8)
        valid = jnp.sum(batch[ROW_MASK].astype(jnp.int32), dtype=jnp.int32)
        return {"bits": bits, "valid": valid}

    def init():
        return {
            "bits": jnp.zeros((n_planes, b8), dtype=jnp.uint8),
            "valid": jnp.zeros((), dtype=jnp.int32),
        }

    if consts:
        def update(state, batch, c):
            return _pack(batch, c)
    else:
        def update(state, batch):
            return _pack(batch, None)

    def host_fold(acc, out):
        writer.consume(np.asarray(out["bits"]), int(np.asarray(out["valid"])))
        return {
            "spans": acc["spans"] + 1,
            "rows": acc["rows"] + int(np.asarray(out["valid"])),
        }

    return ScanOps(
        init=init,
        update=update,
        merge=lambda a, b: b,
        host_init=lambda: {"spans": 0, "rows": 0},
        host_fold=host_fold,
        consts=dict(consts) if consts else None,
        # explicit opt-out: closures hold this run's writer + dataset-
        # compiled predicates; never resurrect from the plan cache
        cache_token=None,
    )


def plan_row_sink(
    sink: RowLevelSink, checks, data, engine
) -> Optional[RowSinkPlan]:
    """Build the sink's scan rider for one run, or None (and a
    ``no_row_level_constraints`` report) when nothing in the suite is
    row-level capable."""
    (
        planes,
        _where_strings,
        where_planes,
        deferred,
        unsupported,
        consts,
        where_requests,
    ) = _classify_constraints(checks, data)
    if not planes and not deferred:
        sink.report = EgressReport(
            status="no_row_level_constraints",
            rows_total=int(data.num_rows),
            unsupported=unsupported,
        )
        return None
    batch_size = engine._resolve_batch_size(data.num_rows)
    b8 = (int(batch_size) + 7) // 8
    row_columns = list(sink.columns or data.schema.column_names)
    writer = QuarantineWriter(
        sink,
        data,
        scan_names=[p.name for p in planes],
        excl_of=[p.excl for p in planes],
        deferred_names=[d.name for d in deferred],
        plane_shape=(len(planes) + len(where_planes), b8),
        row_columns=row_columns,
    )
    ops = _build_ops(planes, where_planes, consts, b8, writer)
    requests: List[ColumnRequest] = []
    seen_req: set = set()
    for spec in planes:
        for r in spec.requests:
            if r.key not in seen_req:
                seen_req.add(r.key)
                requests.append(r)
    for r in where_requests:
        if r.key not in seen_req:
            seen_req.add(r.key)
            requests.append(r)
    return RowSinkPlan(
        sink=sink,
        writer=writer,
        ops=ops,
        adapter=_SinkScanAdapter(requests),
        scan_names=[p.name for p in planes],
        deferred=list(deferred),
        unsupported=unsupported,
        batch_capacity=b8 * 8,
    )


def finalize_row_sink(plan: RowSinkPlan, data, engine) -> EgressReport:
    """After the fused pass: run the deferred families through the
    oracle, replay the spool if one exists, drain trailing quarantined
    units, close the writers, and stamp ``sink.report``."""
    tm = get_telemetry()
    sink = plan.sink
    writer = plan.writer
    # defensive: the failed-scan path can reach here without
    # note_scan_complete ever running — later scans (the deferred
    # oracle, other runs on this engine) must not see a stale hook
    engine.active_egress = None
    if plan.scan_failed:
        writer.abort()
        report = EgressReport(
            status="aborted",
            rows_total=int(data.num_rows),
            rows_clean=writer.rows_clean,
            rows_quarantined=writer.rows_quarantined,
            bytes_raw=writer.bytes_raw,
            bytes_encoded=writer.bytes_encoded,
            unsupported=dict(plan.unsupported),
        )
        report.manifest_path = writer.write_manifest(report, {})
        tm.event(
            "rowlevel_egress",
            status="aborted",
            tenant=sink.tenant,
            run_id=sink.run_id,
        )
        sink.report = report
        return report

    unsupported = dict(plan.unsupported)
    deferred_outcomes: Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]] = {}
    if plan.deferred:
        from deequ_tpu.verification.rowlevel import (
            _OracleCache,
            _outcome_for,
            _where_pass,
        )

        # the deferred families re-read the source by nature
        # (uniqueness is global; untraceable assertions run per unique
        # value on the host) — the run honestly pays a second pass
        with tm.traversal(
            "egress", rows=int(data.num_rows), columns=len(plan.deferred)
        ):
            cache = _OracleCache(data)
            for d in plan.deferred:
                try:
                    excluded = _where_pass(d.where, data, cache)
                    outcome = _outcome_for(
                        d.analyzer, data, assertion=d.assertion,
                        excluded=excluded, cache=cache,
                    )
                except Exception as exc:  # noqa: BLE001 — degrades
                    unsupported[d.name] = f"{type(exc).__name__}: {exc}"
                    continue
                if outcome is None:
                    unsupported[d.name] = (
                        "assertion raised per-value; no row-level column"
                    )
                    continue
                deferred_outcomes[d.name] = (outcome, excluded)
        # columns the oracle degraded must not appear in the schema
        writer.deferred_names = [
            n for n in writer.deferred_names if n in deferred_outcomes
        ]

    record = plan._scan_record or engine.last_degradation
    if writer.spool_mode:
        writer.replay_spool(deferred_outcomes, record)
    rows_clean, rows_quarantined = writer.finish(
        record, interrupted=plan._interrupted
    )
    constraints = {n: "scan" for n in plan.scan_names}
    constraints.update({n: "deferred" for n in writer.deferred_names})
    report = EgressReport(
        status="interrupted" if plan._interrupted else "complete",
        rows_total=int(data.num_rows),
        rows_clean=rows_clean,
        rows_quarantined=rows_quarantined,
        bytes_raw=writer.bytes_raw,
        bytes_encoded=writer.bytes_encoded,
        constraints=constraints,
        unsupported=unsupported,
        clean_dir=os.path.dirname(writer._paths.get("clean", "")),
        quarantine_dir=os.path.dirname(
            writer._paths.get("quarantine", "")
        ),
    )
    failures = []
    if record is not None:
        for f in getattr(record, "failures", ()):
            failures.append(
                {
                    "batch_index": int(f.batch_index),
                    "rows": int(f.rows),
                    "error_class": str(f.error_class),
                    "attempts": int(f.attempts),
                }
            )
    report.manifest_path = writer.write_manifest(
        report, {"scan_failures": failures}
    )
    tm.event(
        "rowlevel_egress",
        status=report.status,
        rows_clean=rows_clean,
        rows_quarantined=rows_quarantined,
        bytes_raw=report.bytes_raw,
        bytes_encoded=report.bytes_encoded,
        constraints=len(constraints),
        tenant=sink.tenant,
        run_id=sink.run_id,
    )
    sink.report = report
    return report
