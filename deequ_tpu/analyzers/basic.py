"""Scan-shareable analyzers: Size, Completeness, Compliance, Mean, Sum,
Minimum, Maximum, MinLength, MaxLength, StandardDeviation, Correlation,
RatioOfSums, PatternMatch, ColumnCount.

Reference: one file per analyzer under
``src/main/scala/com/amazon/deequ/analyzers/`` (SURVEY.md §2.2). Each
analyzer here compiles to a (init, update, merge) triple over fixed-shape
states; the runner concatenates every requested analyzer's update into ONE
jitted function per batch, so N analyzers still cost one data pass — the
TPU equivalent of the reference fusing aggregation expressions into a
single ``df.agg`` job (SURVEY.md §3.1 ★#1).

Null semantics follow the reference: per-column validity masks play the
role of SQL null-skipping aggregates (``COUNT(col)`` vs ``COUNT(*)``,
SURVEY.md §7 hard part #4).
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from deequ_tpu.analyzers.base import (
    Analyzer,
    EmptyStateException,
    IllegalAnalyzerParameterException,
    Precondition,
    ScanOps,
    ScanShareableAnalyzer,
    has_column,
    is_numeric,
    is_string,
)
from deequ_tpu.analyzers import states as S
from deequ_tpu.data.table import ROW_MASK, ColumnRequest, Dataset
from deequ_tpu.metrics.metric import DoubleMetric, Entity
from deequ_tpu.sql.predicate import compile_predicate

_F64 = jnp.float64
_I64 = jnp.int64


def _acc_float():
    from deequ_tpu import config

    return config.options().accumulation_float()


def _compile_where(
    where: Optional[str], dataset: Dataset
) -> Tuple[Optional[Callable], List[ColumnRequest]]:
    """Compile an optional where-filter; returns (complies_fn, requests)."""
    if where is None:
        return None, []
    pred = compile_predicate(where, dataset)
    return pred.complies, list(pred.requests)


def _row_mask(batch, where_fn) -> jnp.ndarray:
    mask = batch[ROW_MASK]
    if where_fn is not None:
        mask = mask & where_fn(batch)
    return mask


def _col_mask(batch, column: str, where_fn) -> jnp.ndarray:
    mask = batch[f"{column}::mask"]
    if where_fn is not None:
        mask = mask & where_fn(batch)
    return mask


# TPU dtype discipline: float64 is software-emulated
# on TPU, so per-element work runs in the column's NATIVE dtype (XLA's
# tree reduction keeps f32 summation error ~ulp*log n) and only the
# per-batch *scalar* results are cast into the accumulation dtype —
# a handful of emulated scalar ops per batch instead of an emulated
# elementwise pass over millions of rows.


def _msum(x, mask):
    """Masked sum: elementwise in native dtype, scalar in accumulation
    dtype. Integral columns always widen per element to f64 (exactness
    over speed — int overflow/rounding must not depend on the float
    accumulation knob); only the scalar result follows the knob."""
    acc = _acc_float()
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.sum(jnp.where(mask, x, jnp.zeros((), x.dtype))).astype(acc)
    return jnp.sum(jnp.where(mask, x, 0).astype(_F64)).astype(acc)


def _mmin(x, mask, axis=None):
    """Masked min under Spark's ordering: NaN ranks above every value,
    so NaN values lose to any real value and win only when ALL masked
    values are NaN (SURVEY.md §2.2; pinned by tests/goldens). Scalar
    always f64 — min/max has no accumulation-error concern, and f64 is
    exact for f32 inputs and ints up to 2^53 (the reference's double
    semantics). A fixed result dtype also keeps the lax.scan carry
    stable across column types."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        # no real (non-NaN) contribution -> NaN, the nan_largest_min
        # IDENTITY (states.MinState): an empty batch must not emit
        # +inf, which would beat a later all-NaN batch's NaN in the
        # carry merge. The count guard keeps identity NaN from ever
        # surfacing for truly empty columns.
        real = mask & ~jnp.isnan(x)
        m = jnp.min(
            jnp.where(real, x, jnp.array(jnp.inf, x.dtype)), axis=axis
        ).astype(_F64)
        return jnp.where(
            jnp.any(real, axis=axis), m, jnp.array(jnp.nan, _F64)
        )
    neutral = jnp.array(jnp.iinfo(x.dtype).max, x.dtype)
    return jnp.min(jnp.where(mask, x, neutral), axis=axis).astype(_F64)


def _mmax(x, mask):
    if jnp.issubdtype(x.dtype, jnp.floating):
        neutral = jnp.array(-jnp.inf, x.dtype)
    else:
        neutral = jnp.array(jnp.iinfo(x.dtype).min, x.dtype)
    return jnp.max(jnp.where(mask, x, neutral)).astype(_F64)


def _mcount(mask) -> jnp.ndarray:
    # batch counts fit i32 (batches are <2^31 rows); the cross-batch
    # carry is an exact i64 scalar add
    return jnp.sum(mask, dtype=jnp.int32).astype(_I64)


# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Size(ScanShareableAnalyzer):
    """Row count (reference: analyzers/Size.scala; state NumMatches)."""

    where: Optional[str] = None

    @property
    def entity(self) -> Entity:
        return Entity.DATASET

    @property
    def instance(self) -> str:
        return "*"

    def device_requests(self, dataset: Dataset) -> List[ColumnRequest]:
        _, reqs = _compile_where(self.where, dataset)
        return reqs

    def make_ops(self, dataset: Dataset) -> ScanOps:
        where_fn, _ = _compile_where(self.where, dataset)

        def update(state: S.NumMatches, batch) -> S.NumMatches:
            return S.NumMatches(
                state.num_matches + _mcount(_row_mask(batch, where_fn))
            )

        return ScanOps(S.NumMatches.identity, update, S.NumMatches.merge)

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None:
            state = S.NumMatches.identity()
        return DoubleMetric.success(
            self.entity, "Size", self.instance, float(state.num_matches)
        )


@dataclass(frozen=True)
class Completeness(ScanShareableAnalyzer):
    """Fraction of non-null values (reference: analyzers/Completeness.scala;
    state NumMatchesAndCount: non-nulls over rows passing the filter)."""

    column: str
    where: Optional[str] = None

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self) -> List[Precondition]:
        return [has_column(self.column)]

    def device_requests(self, dataset: Dataset) -> List[ColumnRequest]:
        _, reqs = _compile_where(self.where, dataset)
        return [ColumnRequest(self.column, "mask")] + reqs

    def make_ops(self, dataset: Dataset) -> ScanOps:
        where_fn, _ = _compile_where(self.where, dataset)
        col = self.column

        def update(state: S.NumMatchesAndCount, batch) -> S.NumMatchesAndCount:
            rows = _row_mask(batch, where_fn)
            valid = batch[f"{col}::mask"] & rows
            return S.NumMatchesAndCount(
                state.num_matches + _mcount(valid),
                state.count + _mcount(rows),
            )

        return ScanOps(
            S.NumMatchesAndCount.identity, update, S.NumMatchesAndCount.merge
        )

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None or int(state.count) == 0:
            return self.to_failure_metric(
                EmptyStateException(
                    "Empty state for analyzer Completeness, all input values "
                    "were NULL or filtered."
                )
            )
        return DoubleMetric.success(
            self.entity,
            "Completeness",
            self.instance,
            float(state.num_matches) / float(state.count),
        )


@dataclass(frozen=True)
class Compliance(ScanShareableAnalyzer):
    """Fraction of rows satisfying a SQL predicate (reference:
    analyzers/Compliance.scala). The predicate compiles to JAX ops; string
    comparisons run on dictionary codes (deequ_tpu.sql.predicate)."""

    instance_name: str
    predicate: str
    where: Optional[str] = None

    @property
    def entity(self) -> Entity:
        return Entity.DATASET

    @property
    def instance(self) -> str:
        return self.instance_name

    def device_requests(self, dataset: Dataset) -> List[ColumnRequest]:
        pred = compile_predicate(self.predicate, dataset)
        _, where_reqs = _compile_where(self.where, dataset)
        return list(pred.requests) + where_reqs

    def make_ops(self, dataset: Dataset) -> ScanOps:
        pred = compile_predicate(self.predicate, dataset)
        where_fn, _ = _compile_where(self.where, dataset)

        def update(state: S.NumMatchesAndCount, batch) -> S.NumMatchesAndCount:
            rows = _row_mask(batch, where_fn)
            return S.NumMatchesAndCount(
                state.num_matches + _mcount(pred.complies(batch) & rows),
                state.count + _mcount(rows),
            )

        return ScanOps(
            S.NumMatchesAndCount.identity, update, S.NumMatchesAndCount.merge
        )

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None or int(state.count) == 0:
            return self.to_failure_metric(
                EmptyStateException("Empty state for analyzer Compliance.")
            )
        return DoubleMetric.success(
            self.entity,
            "Compliance",
            self.instance,
            float(state.num_matches) / float(state.count),
        )


@dataclass(frozen=True)
class PatternMatch(ScanShareableAnalyzer):
    """Fraction of rows whose value matches a regex (reference:
    analyzers/PatternMatch.scala). TPU design: the regex is evaluated
    host-side once over the column *dictionary* (small), producing a bool
    lookup table; the device pass is a gather + sum over codes — strings
    never reach the accelerator (SURVEY.md §7 hard part #3). A
    hashed-string lane column has no dictionary: each batch ships the
    regex's bool plane instead (data/strings.py)."""

    column: str
    pattern: str
    where: Optional[str] = None

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self) -> List[Precondition]:
        return [has_column(self.column), is_string(self.column)]

    def _value_repr(self, dataset: Dataset) -> str:
        """Dictionary codes, or a lane column's plane of this regex."""
        if dataset.string_lane(self.column):
            from deequ_tpu.data.strings import match_repr

            return match_repr(self.pattern)
        return "codes"

    def device_requests(self, dataset: Dataset) -> List[ColumnRequest]:
        _, reqs = _compile_where(self.where, dataset)
        return [
            ColumnRequest(self.column, self._value_repr(dataset)),
            ColumnRequest(self.column, "mask"),
        ] + reqs

    def make_ops(self, dataset: Dataset) -> ScanOps:
        from deequ_tpu.analyzers.base import pad_pow2
        from deequ_tpu.data import strings

        where_fn, _ = _compile_where(self.where, dataset)
        col = self.column
        if dataset.string_lane(col):
            # a bad pattern fails this analyzer now, not the ingest
            # worker that would build its plane mid-scan
            re.compile(self.pattern)
            plane = f"{col}::{self._value_repr(dataset)}"

            # the hashed-string lane: each row ships its own match
            def lane_update(
                state: S.NumMatchesAndCount, batch
            ) -> S.NumMatchesAndCount:
                rows = _row_mask(batch, where_fn)
                hits = batch[plane] & batch[f"{col}::mask"] & rows
                return S.NumMatchesAndCount(
                    state.num_matches + _mcount(hits),
                    state.count + _mcount(rows),
                )

            return ScanOps(
                S.NumMatchesAndCount.identity,
                lane_update,
                S.NumMatchesAndCount.merge,
            )
        dictionary = dataset.dictionary(col)
        table = np.zeros(max(len(dictionary), 1), dtype=bool)
        table[: len(dictionary)] = strings.dictionary_matches(
            dictionary, self.pattern
        )

        # LUT enters the scan as a runtime input (pow2-padded), so the
        # compiled program is shared across datasets — see ScanOps.consts
        def update(
            state: S.NumMatchesAndCount, batch, consts
        ) -> S.NumMatchesAndCount:
            lut = consts["lut"]
            rows = _row_mask(batch, where_fn)
            # codes arrive wire-narrowed (int16 for small dicts); the
            # LUT gather's clip bound must not overflow when a >32k
            # dictionary pads past the int16 range
            codes = batch[f"{col}::codes"].astype(jnp.int32)
            valid = batch[f"{col}::mask"] & rows
            hits = lut[jnp.clip(codes, 0, lut.shape[0] - 1)] & valid
            return S.NumMatchesAndCount(
                state.num_matches + _mcount(hits),
                state.count + _mcount(rows),
            )

        return ScanOps(
            S.NumMatchesAndCount.identity,
            update,
            S.NumMatchesAndCount.merge,
            consts={"lut": pad_pow2(table, False)},
        )

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None or int(state.count) == 0:
            return self.to_failure_metric(
                EmptyStateException("Empty state for analyzer PatternMatch.")
            )
        return DoubleMetric.success(
            self.entity,
            "PatternMatch",
            self.instance,
            float(state.num_matches) / float(state.count),
        )


class _NumericColumnAnalyzer(ScanShareableAnalyzer):
    """Shared plumbing for single-numeric-column analyzers."""

    column: str
    where: Optional[str]

    def preconditions(self) -> List[Precondition]:
        return [has_column(self.column), is_numeric(self.column)]

    @property
    def instance(self) -> str:
        return self.column

    def device_requests(self, dataset: Dataset) -> List[ColumnRequest]:
        _, reqs = _compile_where(self.where, dataset)
        return [
            ColumnRequest(self.column, "values"),
            ColumnRequest(self.column, "mask"),
        ] + reqs


@dataclass(frozen=True)
class Sum(_NumericColumnAnalyzer):
    """Sum of a numeric column (reference: analyzers/Sum.scala)."""

    column: str
    where: Optional[str] = None

    def make_ops(self, dataset: Dataset) -> ScanOps:
        where_fn, _ = _compile_where(self.where, dataset)
        col = self.column

        def update(state: S.SumState, batch) -> S.SumState:
            mask = _col_mask(batch, col, where_fn)
            return S.SumState(
                state.sum_value + _msum(batch[f"{col}::values"], mask),
                state.count + _mcount(mask),
            )

        return ScanOps(S.SumState.identity, update, S.SumState.merge)

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None or int(state.count) == 0:
            return self.to_failure_metric(
                EmptyStateException("Empty state for analyzer Sum.")
            )
        return DoubleMetric.success(
            self.entity, "Sum", self.instance, float(state.sum_value)
        )


@dataclass(frozen=True)
class Mean(_NumericColumnAnalyzer):
    """Arithmetic mean (reference: analyzers/Mean.scala; MeanState)."""

    column: str
    where: Optional[str] = None

    def make_ops(self, dataset: Dataset) -> ScanOps:
        where_fn, _ = _compile_where(self.where, dataset)
        col = self.column

        def update(state: S.MeanState, batch) -> S.MeanState:
            mask = _col_mask(batch, col, where_fn)
            return S.MeanState(
                state.total + _msum(batch[f"{col}::values"], mask),
                state.count + _mcount(mask),
            )

        return ScanOps(S.MeanState.identity, update, S.MeanState.merge)

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None or int(state.count) == 0:
            return self.to_failure_metric(
                EmptyStateException("Empty state for analyzer Mean.")
            )
        return DoubleMetric.success(
            self.entity,
            "Mean",
            self.instance,
            float(state.total) / float(state.count),
        )


@dataclass(frozen=True)
class Minimum(_NumericColumnAnalyzer):
    """Minimum of a numeric column (reference: analyzers/Minimum.scala)."""

    column: str
    where: Optional[str] = None

    def make_ops(self, dataset: Dataset) -> ScanOps:
        where_fn, _ = _compile_where(self.where, dataset)
        col = self.column

        def update(state: S.MinState, batch) -> S.MinState:
            mask = _col_mask(batch, col, where_fn)
            return S.MinState(
                S.nan_largest_min(
                    state.min_value, _mmin(batch[f"{col}::values"], mask)
                ),
                state.count + _mcount(mask),
            )

        return ScanOps(S.MinState.identity, update, S.MinState.merge)

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None or int(state.count) == 0:
            return self.to_failure_metric(
                EmptyStateException("Empty state for analyzer Minimum.")
            )
        # -0.0 normalizes to 0.0 (Spark's NormalizeFloatingNumbers; also
        # backend-independent — TPU min lowering loses the -0.0 sign
        # where CPU keeps it). Host-side add: XLA would fold it away.
        return DoubleMetric.success(
            self.entity, "Minimum", self.instance,
            float(state.min_value) + 0.0,
        )


@dataclass(frozen=True)
class Maximum(_NumericColumnAnalyzer):
    """Maximum of a numeric column (reference: analyzers/Maximum.scala)."""

    column: str
    where: Optional[str] = None

    def make_ops(self, dataset: Dataset) -> ScanOps:
        where_fn, _ = _compile_where(self.where, dataset)
        col = self.column

        def update(state: S.MaxState, batch) -> S.MaxState:
            mask = _col_mask(batch, col, where_fn)
            return S.MaxState(
                jnp.maximum(
                    state.max_value, _mmax(batch[f"{col}::values"], mask)
                ),
                state.count + _mcount(mask),
            )

        return ScanOps(S.MaxState.identity, update, S.MaxState.merge)

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None or int(state.count) == 0:
            return self.to_failure_metric(
                EmptyStateException("Empty state for analyzer Maximum.")
            )
        return DoubleMetric.success(
            self.entity, "Maximum", self.instance,
            float(state.max_value) + 0.0,  # -0.0 -> 0.0, see Minimum
        )


class _LengthAnalyzer(ScanShareableAnalyzer):
    column: str
    where: Optional[str]

    def preconditions(self) -> List[Precondition]:
        return [has_column(self.column), is_string(self.column)]

    @property
    def instance(self) -> str:
        return self.column

    def device_requests(self, dataset: Dataset) -> List[ColumnRequest]:
        _, reqs = _compile_where(self.where, dataset)
        return [
            ColumnRequest(self.column, "lengths"),
            ColumnRequest(self.column, "mask"),
        ] + reqs


@dataclass(frozen=True)
class MinLength(_LengthAnalyzer):
    """Minimum string length (reference: analyzers/MinLength.scala; null
    behavior = Ignore, matching the reference default)."""

    column: str
    where: Optional[str] = None

    def make_ops(self, dataset: Dataset) -> ScanOps:
        where_fn, _ = _compile_where(self.where, dataset)
        col = self.column

        def update(state: S.MinState, batch) -> S.MinState:
            mask = _col_mask(batch, col, where_fn)
            return S.MinState(
                # nan_largest_min, NOT jnp.minimum: the carry identity
                # is NaN (states.MinState), which plain minimum would
                # propagate over every real length
                S.nan_largest_min(
                    state.min_value, _mmin(batch[f"{col}::lengths"], mask)
                ),
                state.count + _mcount(mask),
            )

        return ScanOps(S.MinState.identity, update, S.MinState.merge)

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None or int(state.count) == 0:
            return self.to_failure_metric(
                EmptyStateException("Empty state for analyzer MinLength.")
            )
        return DoubleMetric.success(
            self.entity, "MinLength", self.instance, float(state.min_value)
        )


@dataclass(frozen=True)
class MaxLength(_LengthAnalyzer):
    """Maximum string length (reference: analyzers/MaxLength.scala)."""

    column: str
    where: Optional[str] = None

    def make_ops(self, dataset: Dataset) -> ScanOps:
        where_fn, _ = _compile_where(self.where, dataset)
        col = self.column

        def update(state: S.MaxState, batch) -> S.MaxState:
            mask = _col_mask(batch, col, where_fn)
            return S.MaxState(
                jnp.maximum(
                    state.max_value, _mmax(batch[f"{col}::lengths"], mask)
                ),
                state.count + _mcount(mask),
            )

        return ScanOps(S.MaxState.identity, update, S.MaxState.merge)

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None or int(state.count) == 0:
            return self.to_failure_metric(
                EmptyStateException("Empty state for analyzer MaxLength.")
            )
        return DoubleMetric.success(
            self.entity, "MaxLength", self.instance, float(state.max_value)
        )


@dataclass(frozen=True)
class StandardDeviation(_NumericColumnAnalyzer):
    """Population standard deviation via a mergeable Welford state
    (reference: analyzers/StandardDeviation.scala). The batch update
    computes (n, mean, m2) for the batch vectorized, then merges it into
    the carry with the Chan/Welford combine — numerically stable and a
    pure monoid, so the same merge is the mesh collective."""

    column: str
    where: Optional[str] = None

    def make_ops(self, dataset: Dataset) -> ScanOps:
        where_fn, _ = _compile_where(self.where, dataset)
        col = self.column

        def update(
            state: S.StandardDeviationState, batch
        ) -> S.StandardDeviationState:
            mask = _col_mask(batch, col, where_fn)
            x = batch[f"{col}::values"]
            if not jnp.issubdtype(x.dtype, jnp.floating):
                # integral columns widen to f64 regardless of the knob
                # (f32 would corrupt large ints, e.g. int64 timestamps)
                x = x.astype(_F64)
            # Welford state stays f64: n is an exact count and the
            # moments are per-batch scalars (see states.py identity)
            nb = _mcount(mask).astype(_F64)
            safe_nb = jnp.maximum(nb, 1.0)
            mean_b = _msum(x, mask).astype(_F64) / safe_nb
            # second moment: elementwise in the column dtype around the
            # batch mean; only the scalar widens to f64
            dx = jnp.where(mask, x - mean_b.astype(x.dtype), 0)
            m2_b = jnp.sum(dx * dx).astype(_F64)
            batch_state = S.StandardDeviationState(
                nb, jnp.where(nb > 0, mean_b, 0.0), jnp.where(nb > 0, m2_b, 0.0)
            )
            return S.StandardDeviationState.merge(state, batch_state)

        return ScanOps(
            S.StandardDeviationState.identity,
            update,
            S.StandardDeviationState.merge,
        )

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None or float(state.n) == 0:
            return self.to_failure_metric(
                EmptyStateException(
                    "Empty state for analyzer StandardDeviation."
                )
            )
        return DoubleMetric.success(
            self.entity,
            "StandardDeviation",
            self.instance,
            float(np.sqrt(float(state.m2) / float(state.n))),
        )


@dataclass(frozen=True)
class Correlation(ScanShareableAnalyzer):
    """Pearson correlation of two numeric columns (reference:
    analyzers/Correlation.scala; CorrelationState with Spark Corr-style
    mergeable co-moments). Rows where either value is null are skipped."""

    first_column: str
    second_column: str
    where: Optional[str] = None

    @property
    def entity(self) -> Entity:
        return Entity.MULTICOLUMN

    @property
    def instance(self) -> str:
        return f"{self.first_column},{self.second_column}"

    def preconditions(self) -> List[Precondition]:
        return [
            has_column(self.first_column),
            is_numeric(self.first_column),
            has_column(self.second_column),
            is_numeric(self.second_column),
        ]

    def device_requests(self, dataset: Dataset) -> List[ColumnRequest]:
        _, reqs = _compile_where(self.where, dataset)
        return [
            ColumnRequest(self.first_column, "values"),
            ColumnRequest(self.first_column, "mask"),
            ColumnRequest(self.second_column, "values"),
            ColumnRequest(self.second_column, "mask"),
        ] + reqs

    def make_ops(self, dataset: Dataset) -> ScanOps:
        where_fn, _ = _compile_where(self.where, dataset)
        ca, cb = self.first_column, self.second_column

        def update(state: S.CorrelationState, batch) -> S.CorrelationState:
            mask = batch[f"{ca}::mask"] & batch[f"{cb}::mask"]
            mask = mask & _row_mask(batch, where_fn)
            x = batch[f"{ca}::values"]
            y = batch[f"{cb}::values"]
            if not jnp.issubdtype(x.dtype, jnp.floating):
                x = x.astype(_F64)
            if not jnp.issubdtype(y.dtype, jnp.floating):
                y = y.astype(_F64)
            # co-moment state stays f64 like the Welford state
            nb = _mcount(mask).astype(_F64)
            safe_nb = jnp.maximum(nb, 1.0)
            x_avg = _msum(x, mask).astype(_F64) / safe_nb
            y_avg = _msum(y, mask).astype(_F64) / safe_nb
            dx = jnp.where(mask, x - x_avg.astype(x.dtype), 0)
            dy = jnp.where(mask, y - y_avg.astype(y.dtype), 0)
            batch_state = S.CorrelationState(
                nb,
                jnp.where(nb > 0, x_avg, 0.0),
                jnp.where(nb > 0, y_avg, 0.0),
                jnp.sum(dx * dy).astype(_F64),
                jnp.sum(dx * dx).astype(_F64),
                jnp.sum(dy * dy).astype(_F64),
            )
            return S.CorrelationState.merge(state, batch_state)

        return ScanOps(
            S.CorrelationState.identity, update, S.CorrelationState.merge
        )

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None or float(state.n) == 0:
            return self.to_failure_metric(
                EmptyStateException("Empty state for analyzer Correlation.")
            )
        # sqrt of the PRODUCT, like Spark's Corr (sqrt(x)*sqrt(y) is
        # not float-equivalent: exact linear dependence must yield
        # exactly 1.0); zero variance gives 0/0 = NaN as a SUCCESSFUL
        # metric value, matching Spark/deequ (r4 review + goldens).
        # The product form overflows to inf when both m_k exceed
        # ~1e154 and underflows to 0 when both sit below ~1e-162 —
        # fall back to sqrt(x)*sqrt(y) in either regime (finite
        # nonzero inputs, finite nonzero answer), keeping the product
        # form for the exact linear-dependence == 1.0 case
        # (r4 advisory + review finding).
        x_mk, y_mk = float(state.x_mk), float(state.y_mk)
        product = x_mk * y_mk
        # < tiny (not just == 0): a subnormal product carries too few
        # bits and can report |r| > 1 (review finding)
        degenerate = (not np.isfinite(product)) or (
            product < float(np.finfo(np.float64).tiny)
            and x_mk != 0.0
            and y_mk != 0.0
        )
        if degenerate and np.isfinite(x_mk) and np.isfinite(y_mk):
            denom = float(np.sqrt(x_mk) * np.sqrt(y_mk))
        else:
            denom = float(np.sqrt(product))
        with np.errstate(invalid="ignore", divide="ignore"):
            value = (
                float(np.float64(state.ck) / denom)
                if denom != 0.0
                else float("nan")
            )
        return DoubleMetric.success(
            self.entity, "Correlation", self.instance, value
        )


@dataclass(frozen=True)
class RatioOfSums(ScanShareableAnalyzer):
    """sum(numerator)/sum(denominator) (reference: analyzers/RatioOfSums.scala,
    newer upstream — SURVEY.md §2.2)."""

    numerator: str
    denominator: str
    where: Optional[str] = None

    @property
    def entity(self) -> Entity:
        return Entity.MULTICOLUMN

    @property
    def instance(self) -> str:
        return f"{self.numerator},{self.denominator}"

    def preconditions(self) -> List[Precondition]:
        return [
            has_column(self.numerator),
            is_numeric(self.numerator),
            has_column(self.denominator),
            is_numeric(self.denominator),
        ]

    def device_requests(self, dataset: Dataset) -> List[ColumnRequest]:
        _, reqs = _compile_where(self.where, dataset)
        return [
            ColumnRequest(self.numerator, "values"),
            ColumnRequest(self.numerator, "mask"),
            ColumnRequest(self.denominator, "values"),
            ColumnRequest(self.denominator, "mask"),
        ] + reqs

    def make_ops(self, dataset: Dataset) -> ScanOps:
        where_fn, _ = _compile_where(self.where, dataset)
        ca, cb = self.numerator, self.denominator

        def update(state: S.SumPairState, batch) -> S.SumPairState:
            rows = _row_mask(batch, where_fn)
            ma = batch[f"{ca}::mask"] & rows
            mb = batch[f"{cb}::mask"] & rows
            return S.SumPairState(
                state.sum_a + _msum(batch[f"{ca}::values"], ma),
                state.sum_b + _msum(batch[f"{cb}::values"], mb),
                state.count + _mcount(rows),
            )

        return ScanOps(S.SumPairState.identity, update, S.SumPairState.merge)

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None or int(state.count) == 0:
            return self.to_failure_metric(
                EmptyStateException("Empty state for analyzer RatioOfSums.")
            )
        if float(state.sum_b) == 0.0:
            return self.to_failure_metric(
                IllegalAnalyzerParameterException(
                    "Denominator sum is zero in RatioOfSums."
                )
            )
        return DoubleMetric.success(
            self.entity,
            "RatioOfSums",
            self.instance,
            float(state.sum_a) / float(state.sum_b),
        )


@dataclass(frozen=True)
class ColumnCount(Analyzer):
    """Number of columns (reference: analyzers/ColumnCount.scala) — a
    schema-only analyzer; the runner answers it without a scan."""

    @property
    def entity(self) -> Entity:
        return Entity.DATASET

    @property
    def instance(self) -> str:
        return "*"

    def compute_directly(self, dataset: Dataset) -> DoubleMetric:
        return DoubleMetric.success(
            self.entity, "ColumnCount", self.instance, float(dataset.num_columns)
        )

    def compute_metric_from_state(self, state) -> DoubleMetric:
        return self.to_failure_metric(
            EmptyStateException("ColumnCount has no scan state.")
        )
