"""Row-level verification results: per-row pass/fail per constraint.

Reference: newer-upstream row-level results (SURVEY.md §2.2
"FilteredRowOutcome", ``VerificationResult.rowLevelResultsAsDataFrame``):
row-level-capable analyzers also emit a per-row boolean outcome column.

Supported families:

- **mask/predicate**: Completeness, Compliance (and every Check method
  that compiles to it: is_contained_in, is_non_negative, satisfies,
  ...), PatternMatch (and contains_email/url/...);
- **grouping**: Uniqueness and UniqueValueRatio (a row passes iff
  its key occurs once — the reference's RowLevelGroupedConstraint
  rule for both);
- **asserted-value** (r4, reference's RowLevelAssertedConstraint):
  MinLength/MaxLength (per-row string length) and Minimum/Maximum
  (per-row numeric value) apply the CONSTRAINT'S OWN assertion to each
  row's value — e.g. ``has_min_length("s", lambda v: v >= 3)`` marks
  exactly the too-short rows. Null rows pass (the reference's default
  NullBehavior.Ignore; Completeness is the analyzer that flags nulls).

Filtered-row semantics are configurable (reference:
``AnalyzerOptions.filteredRow``): rows excluded by a ``where`` filter
count as PASSING under the default ``filtered_row_outcome="true"``, or
come back as SQL NULL under ``"null"`` (the outcome column is then a
nullable boolean, matching the reference's NULLED FilteredRowOutcome).

Outcomes are computed vectorized — device ops for predicate/mask work,
one host ``np.unique`` pass for uniqueness, assertions evaluated once
per UNIQUE value then gathered — never per-row Python.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np
import pyarrow as pa

from deequ_tpu.analyzers.base import Analyzer
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
from deequ_tpu.data.table import ColumnRequest, Dataset, Kind, ROW_MASK
from deequ_tpu.constraints.constraint import (
    AnalysisBasedConstraint,
    ConstraintDecorator,
)
from deequ_tpu.sql.predicate import compile_predicate


class _OracleCache:
    """Per-call materialization cache: one export touches only the
    columns its row-level constraints actually request, each at most
    ONCE — the row mask is built a single time, a ``where`` predicate
    shared by several constraints compiles and evaluates once, and a
    column two constraints both read is pulled from the source once
    (parquet sources re-read on every ``materialize``). Scoped to one
    ``row_level_results`` / egress-finalize call so nothing outlives
    the export."""

    def __init__(self, data: Dataset):
        self._data = data
        self._arrays: Dict[str, np.ndarray] = {}
        self._row_mask: Optional[np.ndarray] = None
        self._where: Dict[str, Optional[np.ndarray]] = {}

    def materialize(self, req: ColumnRequest) -> np.ndarray:
        if req.key not in self._arrays:
            self._arrays[req.key] = self._data.materialize(req)
        return self._arrays[req.key]

    def row_mask(self) -> np.ndarray:
        if self._row_mask is None:
            self._row_mask = np.ones(self._data.num_rows, dtype=bool)
        return self._row_mask


def _full_batch(
    data: Dataset, requests, cache: Optional[_OracleCache] = None
) -> Dict[str, np.ndarray]:
    mat = cache.materialize if cache is not None else data.materialize
    batch = {r.key: mat(r) for r in requests}
    for r in requests:
        mask_key = f"{r.column}::mask"
        if mask_key not in batch:
            batch[mask_key] = mat(ColumnRequest(r.column, "mask"))
    batch[ROW_MASK] = (
        cache.row_mask()
        if cache is not None
        else np.ones(data.num_rows, dtype=bool)
    )
    return batch


def _where_pass(
    where: Optional[str],
    data: Dataset,
    cache: Optional[_OracleCache] = None,
) -> Optional[np.ndarray]:
    """True for rows EXCLUDED by the filter (they pass by default)."""
    if where is None:
        return None
    if cache is not None and where in cache._where:
        return cache._where[where]
    pred = compile_predicate(where, data)
    batch = _full_batch(data, pred.requests, cache)
    out = ~np.asarray(jax.device_get(pred.complies(batch)), dtype=bool)
    if cache is not None:
        cache._where[where] = out
    return out


def _asserted_per_value(
    values: np.ndarray, valid: np.ndarray, assertion
) -> Optional[np.ndarray]:
    """assertion(value) per row, evaluated once per UNIQUE value and
    gathered back (the assertion is a Python scalar callable; a direct
    per-row loop would crawl on wide data). Invalid (null) rows pass —
    NullBehavior.Ignore, the reference's default — and their
    zero-fill placeholders NEVER reach the assertion (a partial
    assertion like ``1/v > 0`` must not see values outside the
    non-null domain). An assertion that still raises degrades to "no
    row-level column" (None) rather than aborting the whole export —
    the aggregate path already reported the same exception as a
    FAILURE ConstraintResult."""
    out = np.ones(len(values), dtype=bool)
    real = values[valid]
    uniques, inverse = np.unique(real, return_inverse=True)
    try:
        lut = np.fromiter(
            (bool(assertion(v)) for v in uniques.tolist()),
            dtype=bool,
            count=len(uniques),
        )
    except Exception:  # noqa: BLE001 — degrade, mirroring the
        return None  # aggregate constraint's FAILURE result
    out[valid] = lut[inverse]
    return out


def _outcome_for(
    analyzer: Analyzer,
    data: Dataset,
    assertion=None,
    excluded: Optional[np.ndarray] = None,
    cache: Optional[_OracleCache] = None,
) -> Optional[np.ndarray]:
    mat = cache.materialize if cache is not None else data.materialize

    def _asserted(repr_name: str) -> Optional[np.ndarray]:
        values = np.asarray(
            mat(ColumnRequest(analyzer.column, repr_name))
        )
        valid = np.asarray(
            mat(ColumnRequest(analyzer.column, "mask")),
            dtype=bool,
        )
        if excluded is not None:
            # where-excluded rows are outside the assertion's domain
            # exactly like nulls: a partial assertion safe on the
            # FILTERED data must not see their values (the caller
            # overrides their outcome per filtered_row_outcome)
            valid = valid & ~excluded
        return _asserted_per_value(values, valid, assertion)

    if isinstance(analyzer, (MinLength, MaxLength)):
        if assertion is None:
            return None
        out = _asserted("lengths")
    elif isinstance(analyzer, (Minimum, Maximum)):
        if assertion is None:
            return None
        out = _asserted("values")
    elif isinstance(analyzer, Completeness):
        mask = mat(ColumnRequest(analyzer.column, "mask"))
        out = np.asarray(mask, dtype=bool).copy()
    elif isinstance(analyzer, Compliance):
        pred = compile_predicate(analyzer.predicate, data)
        batch = _full_batch(data, pred.requests, cache)
        out = np.asarray(
            jax.device_get(pred.complies(batch)), dtype=bool
        ).copy()
    elif isinstance(analyzer, PatternMatch):
        from deequ_tpu.data.strings import dictionary_matches

        codes = mat(ColumnRequest(analyzer.column, "codes"))
        mask = mat(ColumnRequest(analyzer.column, "mask"))
        dictionary = data.dictionary(analyzer.column)
        lut = np.zeros(max(len(dictionary), 1) + 1, dtype=bool)
        lut[: len(dictionary)] = dictionary_matches(
            dictionary, analyzer.pattern
        )
        idx = np.where(codes < 0, len(lut) - 1, codes)
        out = lut[np.clip(idx, 0, len(lut) - 1)] & np.asarray(
            mask, dtype=bool
        )
    elif isinstance(analyzer, (Uniqueness, UniqueValueRatio)):
        columns = analyzer.grouping_columns()
        # fold columns into one exact group id via successive np.unique
        # in each column's NATIVE dtype — no float64 cast (int64 ids
        # above 2^53 must stay distinct, exactly like the HLL hashing)
        group_ids: Optional[np.ndarray] = None
        for c in columns:
            kind = data.schema.kind_of(c)
            repr_name = "codes" if kind == Kind.STRING else "values"
            values = np.asarray(mat(ColumnRequest(c, repr_name)))
            mask = np.asarray(mat(ColumnRequest(c, "mask")), dtype=bool)
            _, col_ids = np.unique(values, return_inverse=True)
            # validity joins the key so NULL is its own value,
            # distinct from the zero-fill
            col_ids = col_ids * 2 + mask.astype(np.int64)
            if group_ids is None:
                group_ids = col_ids
            else:
                pair = np.stack([group_ids, col_ids], axis=1)
                _, group_ids = np.unique(
                    pair, axis=0, return_inverse=True
                )
        _, inverse = np.unique(group_ids, return_inverse=True)
        if inverse.size == 0:
            return np.zeros(0, dtype=bool)
        if excluded is not None:
            # occurrence counts over the FILTERED data only: a key
            # unique within the filter passes even if where-excluded
            # rows share it (their own outcome is overridden by
            # filtered_row_outcome) — review finding r5
            counts = np.bincount(
                inverse[~excluded], minlength=inverse.max() + 1
            )
        else:
            counts = np.bincount(inverse)
        out = counts[inverse] == 1
    else:
        return None
    return out


def row_level_results(
    check_results,
    data: Dataset,
    filtered_row_outcome: str = "true",
) -> Dataset:
    """One boolean column per row-level-capable constraint, named by the
    constraint, over ``data`` (the dataset the suite ran on).

    ``filtered_row_outcome`` — what a row EXCLUDED by the constraint's
    ``where`` filter reports (reference: AnalyzerOptions.filteredRow):
    ``"true"`` (default) marks it passing; ``"null"`` yields SQL NULL
    in a nullable boolean column."""
    if filtered_row_outcome not in ("true", "null"):
        raise ValueError(
            "filtered_row_outcome must be 'true' or 'null', got "
            f"{filtered_row_outcome!r}"
        )
    columns: Dict[str, pa.Array] = {}
    # one shared materialization cache for the whole export: only the
    # columns the row-level constraints touch, each loaded once
    cache = _OracleCache(data)
    for check, result in check_results.items():
        for cr in result.constraint_results:
            constraint = cr.constraint
            if isinstance(constraint, ConstraintDecorator):
                inner = constraint.inner
            else:
                inner = constraint
            if not isinstance(inner, AnalysisBasedConstraint):
                continue
            try:
                excluded = _where_pass(
                    getattr(inner.analyzer, "where", None), data,
                    cache,
                )
                outcome = _outcome_for(
                    inner.analyzer,
                    data,
                    assertion=inner.assertion,
                    excluded=excluded,
                    cache=cache,
                )
            except Exception:  # noqa: BLE001 — degrade: an unplannable
                # predicate (compile_predicate in _where_pass or the
                # Compliance branch) drops THIS constraint's column
                # only, mirroring _asserted_per_value's discipline; the
                # aggregate path already reported the same exception as
                # a FAILURE ConstraintResult
                continue
            if outcome is None:
                continue
            if excluded is None:
                columns[str(constraint)] = pa.array(outcome)
            elif filtered_row_outcome == "true":
                columns[str(constraint)] = pa.array(outcome | excluded)
            else:  # "null": excluded rows are SQL NULL
                columns[str(constraint)] = pa.array(
                    outcome, mask=excluded
                )
    if not columns:
        return Dataset(pa.table({"__no_row_level_constraints__": pa.array([], pa.bool_())}))
    return Dataset(pa.table(columns))
