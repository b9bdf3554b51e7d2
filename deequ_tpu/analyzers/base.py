"""Analyzer framework: states are commutative monoids, metrics are values.

Reference contract (``src/main/scala/com/amazon/deequ/analyzers/Analyzer.scala``,
SURVEY.md §2.2): an analyzer is (compute state from data, compute metric
from state, preconditions); states implement ``sum`` (a commutative monoid
merge) — the whole incremental/distributed story hangs on that.

deequ_tpu expresses each scan-shareable analyzer as a :class:`ScanOps`
triple over fixed-shape pytrees:

- ``init()``                  — monoid identity (host numpy pytree)
- ``update(state, batch)``    — traced, vectorized masked reduction over a
                                device batch; XLA fuses all analyzers'
                                updates into a single pass (the TPU
                                equivalent of the reference's one
                                ``df.agg(...)`` scan, SURVEY.md §3.1 ★#1)
- ``merge(a, b)``             — traced monoid merge; also the collective
                                used across the device mesh and across
                                persisted incremental states

Finalization (state → metric) is a tiny host-side epilogue, and failures
(missing column, empty state) become failure *metrics*, never user-facing
exceptions (SURVEY.md §5.3).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deequ_tpu.data.table import ColumnRequest, Dataset, Kind, Schema
from deequ_tpu.metrics.metric import DoubleMetric, Entity, Metric
from deequ_tpu.utils.trylike import Failure


# --------------------------------------------------------------------------
# Failure model (reference: analyzers/runners/MetricCalculationException.scala)
# --------------------------------------------------------------------------


class MetricCalculationException(Exception):
    """Base for per-analyzer failures embedded into failure metrics."""


class NoSuchColumnException(MetricCalculationException):
    pass


class WrongColumnTypeException(MetricCalculationException):
    pass


class NoColumnsSpecifiedException(MetricCalculationException):
    pass


class NumberOfSpecifiedColumnsException(MetricCalculationException):
    pass


class IllegalAnalyzerParameterException(MetricCalculationException):
    pass


class EmptyStateException(MetricCalculationException):
    pass


class MetricCalculationRuntimeException(MetricCalculationException):
    pass


def wrap_if_necessary(exc: BaseException) -> MetricCalculationException:
    if isinstance(exc, MetricCalculationException):
        return exc
    return MetricCalculationRuntimeException(repr(exc))


# --------------------------------------------------------------------------
# Preconditions (reference: analyzers/Preconditions object)
# --------------------------------------------------------------------------

Precondition = Callable[[Schema], None]


def has_column(column: str) -> Precondition:
    def check(schema: Schema) -> None:
        if not schema.has_column(column):
            raise NoSuchColumnException(
                f"Input data does not include column {column}!"
            )

    return check


def is_numeric(column: str) -> Precondition:
    def check(schema: Schema) -> None:
        if not schema.kind_of(column).is_numeric:
            raise WrongColumnTypeException(
                f"Expected type of column {column} to be numeric, but found "
                f"{schema.kind_of(column).value} instead!"
            )

    return check


def is_string(column: str) -> Precondition:
    def check(schema: Schema) -> None:
        if schema.kind_of(column) != Kind.STRING:
            raise WrongColumnTypeException(
                f"Expected type of column {column} to be String, but found "
                f"{schema.kind_of(column).value} instead!"
            )

    return check


def is_not_nested(column: str) -> Precondition:
    def check(schema: Schema) -> None:
        if schema.kind_of(column) == Kind.UNKNOWN:
            raise WrongColumnTypeException(
                f"Unsupported nested/unknown type in column {column}!"
            )

    return check


def at_least_one(columns: Sequence[str]) -> Precondition:
    def check(schema: Schema) -> None:
        if len(columns) == 0:
            raise NoColumnsSpecifiedException(
                "At least one column needs to be specified!"
            )

    return check


def exactly_n_columns(columns: Sequence[str], n: int) -> Precondition:
    def check(schema: Schema) -> None:
        if len(columns) != n:
            raise NumberOfSpecifiedColumnsException(
                f"Exactly {n} columns needed, got {len(columns)}"
            )

    return check


# --------------------------------------------------------------------------
# Scan ops
# --------------------------------------------------------------------------

StateTree = Any  # pytree of arrays (numpy host-side, jax inside jit)
Batch = Dict[str, Any]


class _CacheTokenAuto:
    def __repr__(self) -> str:  # pragma: no cover — debugging aid
        return "<CACHE_TOKEN_AUTO>"


CACHE_TOKEN_AUTO = _CacheTokenAuto()


class _DeltaPrime:
    def __repr__(self) -> str:  # pragma: no cover — debugging aid
        return "<DELTA_PRIME>"


# Sentinel passed to ScanOps.host_delta on NON-streaming paths (resident
# scan, sharded step) where no per-batch dictionary deltas flow: the op
# must load the FULL dictionaries from the dataset (a pre-pass is fine
# there — the data is already resident). Without priming, a delta-aware
# op's LUT state would silently stay empty on those paths.
DELTA_PRIME = _DeltaPrime()


@dataclass
class ScanOps:
    """The (identity, update, merge) triple for one analyzer, compiled
    against a concrete dataset (closures hold compiled predicates).

    ``consts`` — per-dataset lookup tables (dictionary LUTs for
    PatternMatch/DataType/HLL-on-strings). They enter the jitted scan as
    RUNTIME INPUTS, not closure constants: embedded constants would bake
    each dataset's dictionary into the HLO and force a full XLA
    recompile per dataset, defeating the persistent compilation cache.
    When ``consts`` is set, ``update`` takes ``(state, batch, consts)``.
    LUT shapes should be padded to powers of two (``pad_pow2``) so
    different dictionaries of similar size share one compiled program.

    Host-folded analyzers (KLL): ``update`` emits a small fixed-shape
    per-batch device output instead of a running carry, and the engine
    folds it into a host accumulator via ``host_fold`` after each batch
    — only k floats cross the boundary, the data pass stays fused."""

    init: Callable[[], StateTree]
    update: Callable[..., StateTree]
    merge: Callable[[StateTree, StateTree], StateTree]
    host_init: Optional[Callable[[], Any]] = None
    host_fold: Optional[Callable[[Any, Any], Any]] = None
    consts: Optional[Dict[str, np.ndarray]] = None
    # behavior fingerprint for the engine's cross-run plan cache: two
    # ops with EQUAL tokens must trace to identical computations (all
    # dataset-specific content rides `consts`).
    # - CACHE_TOKEN_AUTO (default): the runner derives a standard token
    #   for the built-in analyzers; the engine treats a still-AUTO op
    #   as uncacheable.
    # - None: EXPLICIT opt-out — never reuse a compiled plan containing
    #   this op (dataset-derived constants baked into the closure).
    cache_token: Optional[object] = CACHE_TOKEN_AUTO
    # collector ops (one-pass spill): the final state is a device-
    # resident key buffer consumed by a post-scan sort finalize — the
    # engine excludes it from the epilogue's packed fetch instead of
    # round-tripping megabytes of keys through the host.
    device_result: bool = False
    # one-pass dictionary deltas (docs/PERF.md "Wire diet"): ops whose
    # LUTs live in STATE instead of consts receive incremental
    # dictionary updates here. Called on the host as
    # ``host_delta(state, deltas)`` where ``deltas`` maps column ->
    # {"start": int, "values": ndarray} (new uniques appended at
    # ``start``), or with the DELTA_PRIME sentinel on non-streaming
    # paths (load full dictionaries from the dataset). Returns the
    # updated state tree; applied in batch order BEFORE that batch's
    # fused update so codes never index past the shipped LUT rows.
    host_delta: Optional[Callable[[StateTree, Any], StateTree]] = None

    def apply_update(self, state, batch, consts):
        if self.consts is None:
            return self.update(state, batch)
        return self.update(state, batch, consts)


def make_cache_token(
    analyzer: "ScanShareableAnalyzer",
    dataset: Dataset,
    predicates: Sequence[Optional[str]] = (),
) -> Optional[tuple]:
    """Standard ScanOps.cache_token: the analyzer's repr (frozen
    dataclass => deterministic, includes every parameter) plus the KINDS
    of the involved columns (update closures branch on kind at build
    time). None when any predicate bakes dictionary-derived constants
    into its closure."""
    from deequ_tpu.sql.predicate import compile_predicate

    for expression in predicates:
        if expression is None:
            continue
        if not compile_predicate(expression, dataset).dataset_independent:
            return None
    kinds = tuple(
        sorted(
            {
                (r.column, dataset.kind_token(r.column))
                for r in analyzer.device_requests(dataset)
            }
        )
    )
    return (repr(analyzer), kinds)


def pad_pow2(arr: np.ndarray, fill=0) -> np.ndarray:
    """Pad a 1-D LUT to the next power-of-two length so compiled scans
    are shared across datasets whose dictionaries have similar sizes."""
    n = len(arr)
    m = 1 << max(0, (n - 1).bit_length())
    if m <= n:
        return arr
    return np.concatenate([arr, np.full(m - n, fill, dtype=arr.dtype)])


# --------------------------------------------------------------------------
# Analyzer base classes
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Analyzer:
    """Base analyzer. Frozen dataclass => hashable, dedupable (the runner
    dedups analyzers and uses them as context-map keys, SURVEY.md §2.4)."""

    # -- identity -------------------------------------------------------

    @property
    def name(self) -> str:
        return type(self).__name__

    @property
    def entity(self) -> Entity:
        return Entity.COLUMN

    @property
    def instance(self) -> str:
        raise NotImplementedError

    @property
    def identity_key(self) -> str:
        """Stable identity for context slicing (``AnalyzerContext.subset``
        and the service-side scan coalescer). Frozen dataclass ``repr`` is
        deterministic and parameter-complete — two analyzers with equal
        keys compute the same metric on the same data, the same contract
        ``make_cache_token`` already leans on."""
        return repr(self)

    # -- contract -------------------------------------------------------

    def preconditions(self) -> List[Precondition]:
        return []

    def compute_metric_from_state(self, state: Optional[StateTree]) -> Metric:
        """Host-side finalize; ``state=None`` means no rows contributed."""
        raise NotImplementedError

    def to_failure_metric(self, exc: BaseException) -> Metric:
        return DoubleMetric(
            self.entity, self.name, self.instance, Failure(wrap_if_necessary(exc))
        )

    # -- convenience ----------------------------------------------------

    def calculate(
        self,
        data: Dataset,
        aggregate_with=None,
        save_states_with=None,
        engine=None,
    ) -> Metric:
        """Compute just this analyzer (delegates to the runner so scan
        sharing / precondition semantics are identical)."""
        from deequ_tpu.analyzers.runner import AnalysisRunner

        context = AnalysisRunner.do_analysis_run(
            data,
            [self],
            aggregate_with=aggregate_with,
            save_states_with=save_states_with,
            engine=engine,
        )
        return context.metric(self)  # type: ignore[return-value]


@dataclass(frozen=True)
class ScanShareableAnalyzer(Analyzer):
    """An analyzer whose state updates fuse into the shared single pass."""

    def device_requests(self, dataset: Dataset) -> List[ColumnRequest]:
        raise NotImplementedError

    def make_ops(self, dataset: Dataset) -> ScanOps:
        raise NotImplementedError


@dataclass(frozen=True)
class GroupingAnalyzer(Analyzer):
    """An analyzer over value frequencies; the runner computes one
    frequency table per distinct (grouping columns, filter) and shares it
    (reference: GroupingAnalyzers.scala / FrequencyBasedAnalyzer)."""

    def grouping_columns(self) -> List[str]:
        raise NotImplementedError

    @property
    def filter_condition(self) -> Optional[str]:
        return None

    def compute_metric_from_frequencies(self, frequencies) -> Metric:
        raise NotImplementedError

    def preconditions(self) -> List[Precondition]:
        cols = self.grouping_columns()
        checks: List[Precondition] = [at_least_one(cols)]
        checks.extend(has_column(c) for c in cols)
        checks.extend(is_not_nested(c) for c in cols)
        return checks


def merged_where_clause(where: Optional[str]) -> str:
    return where if where else "(no filter)"


def filter_suffix(where: Optional[str]) -> Tuple:
    """Include the filter in analyzer identity so differently-filtered
    analyzers don't collide in the context map."""
    return (where,) if where else ()
