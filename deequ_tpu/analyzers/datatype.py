"""DataType inference analyzer.

Reference: ``analyzers/DataType.scala`` + the ``StatefulDataType``
Catalyst aggregate (SURVEY.md §2.2, §2.3): per-value classification into
{Unknown(null), Fractional, Integral, Boolean, String} buckets, counts
packed into a vector whose merge is elementwise sum.

TPU design (SURVEY.md §2.3 table): the regex classification runs
host-side ONCE over the column *dictionary* (vectorized, small), giving a
code -> bucket lookup table; the device pass is a gather + one-hot
count — a 5-counter psum across the mesh. Numeric/boolean columns
classify from the schema directly (every non-null value already has the
column's type).
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deequ_tpu.analyzers.base import (
    Precondition,
    ScanOps,
    ScanShareableAnalyzer,
    has_column,
)
from deequ_tpu.analyzers.basic import _compile_where, _row_mask
from deequ_tpu.analyzers.states import DataTypeHistogram
from deequ_tpu.data.table import LANE_DTYPE, ColumnRequest, Dataset, Kind
from deequ_tpu.metrics.distribution import (
    Distribution,
    DistributionValue,
    HistogramMetric,
)
from deequ_tpu.metrics.metric import Entity, Metric
from deequ_tpu.utils.trylike import Success

# Classification regexes (reference: StatefulDataType's patterns)
_INTEGRAL_RE = re.compile(r"^[-+]?\d+$")
_FRACTIONAL_RE = re.compile(r"^[-+]?(\d+\.\d*|\.\d+|\d+)([eE][-+]?\d+)?$")
_BOOLEAN_RE = re.compile(r"^(true|false)$", re.IGNORECASE)

_BUCKET_NAMES = ("Unknown", "Fractional", "Integral", "Boolean", "String")


def classify_string(value: str) -> int:
    if _BOOLEAN_RE.match(value):
        return DataTypeHistogram.BOOLEAN
    if _INTEGRAL_RE.match(value):
        return DataTypeHistogram.INTEGRAL
    if _FRACTIONAL_RE.match(value):
        return DataTypeHistogram.FRACTIONAL
    return DataTypeHistogram.STRING


def counts_from_code_presence(
    codes: "jnp.ndarray",  # (C, B) int codes, -1 = null
    valid: "jnp.ndarray",  # (C, B) validity (row mask pre-ANDed)
    rows: "jnp.ndarray",  # (B,) kept-row mask
    table: "jnp.ndarray",  # (C, D) class LUT per dictionary entry
) -> "jnp.ndarray":
    """(C, 6) type counts for dict-encoded columns WITHOUT per-row
    gathers: per-code counts via a (C, D, B)->(C, D) compare-reduce
    (VPU rate), then a class einsum over the LUT — vs per-row LUT
    gather + scatter-add, both serialized-scatter-class on TPU
    (~5-9x slower measured, docs/PERF.md). Null slot = kept rows
    minus typed rows (a valid row always has a code; invalid/null
    rows match no dictionary slot). The single-analyzer and stacked
    group paths BOTH call this — their states max-merge, so the math
    must stay single-sourced."""
    from deequ_tpu.sketches.hll import tiled_code_presence

    D = table.shape[1]
    cnt = tiled_code_presence(codes, valid, D, count=True)  # (C, D)
    onehot = jax.nn.one_hot(table, 6, dtype=jnp.int32)
    counts = jnp.einsum("cd,cdk->ck", cnt, onehot)
    kept = rows.sum(dtype=jnp.int32)
    nulls = kept - cnt.sum(axis=1, dtype=jnp.int32)
    return counts.at[:, DataTypeHistogram.NULL].add(nulls)


def dictionary_buckets(dictionary) -> np.ndarray:
    """i32 bucket per dictionary entry (at least one slot), classified
    in vectorized form by the rule the lane's ``dtype`` plane uses
    (data/strings.classify), so both paths bucket a string alike."""
    from deequ_tpu.data import strings

    lut = np.zeros(max(len(dictionary), 1), dtype=np.int32)
    if len(dictionary):
        lut[: len(dictionary)] = strings.classify(
            strings.dictionary_values(dictionary)
        )
    return lut


def _bucket_counts(bucket, valid, rows) -> "jnp.ndarray":
    """(6,) i64 counts of a batch's per-row buckets: invalid rows count
    as null, padding rows not at all. i32 scatter, i64 result: int64
    scatters are ~30x slower on TPU (emulated); batch counts fit i32."""
    bucket = jnp.where(valid, bucket, DataTypeHistogram.NULL)
    bucket = jnp.where(rows, bucket, 5)  # padding -> reserved
    counts = jnp.zeros(7, dtype=jnp.int32).at[
        bucket.astype(jnp.int32)
    ].add(1)[:6]
    return counts.astype(jnp.int64).at[5].set(0)


@dataclass(frozen=True)
class DataType(ScanShareableAnalyzer):
    """Inferred-type histogram of a column (reference: DataType.scala)."""

    column: str
    where: Optional[str] = None

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self) -> List[Precondition]:
        return [has_column(self.column)]

    def device_requests(self, dataset: Dataset) -> List[ColumnRequest]:
        _, reqs = _compile_where(self.where, dataset)
        kind = dataset.schema.kind_of(self.column)
        if kind != Kind.STRING:
            value_repr = "mask"
        elif dataset.string_lane(self.column):
            value_repr = LANE_DTYPE
        else:
            value_repr = "codes"
        col_req = ColumnRequest(self.column, value_repr)
        return [col_req, ColumnRequest(self.column, "mask")] + reqs

    def make_ops(self, dataset: Dataset) -> ScanOps:
        where_fn, _ = _compile_where(self.where, dataset)
        col = self.column
        kind = dataset.schema.kind_of(col)
        # the presence fast path shares ONE implementation with the
        # stacked group builder (counts_from_code_presence below):
        # the two produce merge-compatible states, so the math must
        # stay single-sourced

        if kind == Kind.STRING and dataset.string_lane(col):
            plane = f"{col}::{LANE_DTYPE}"

            # the hashed-string lane: each row ships its own bucket
            def lane_update(
                state: DataTypeHistogram, batch
            ) -> DataTypeHistogram:
                rows = _row_mask(batch, where_fn)
                valid = batch[f"{col}::mask"] & rows
                return DataTypeHistogram(
                    state.counts + _bucket_counts(batch[plane], valid, rows)
                )

            return ScanOps(
                DataTypeHistogram.identity,
                lane_update,
                DataTypeHistogram.merge,
            )
        if kind == Kind.STRING:
            from deequ_tpu.analyzers.base import pad_pow2

            lut = dictionary_buckets(dataset.dictionary(col))

            # LUT as runtime input (pow2-padded): shared compiled scan
            # across datasets — see ScanOps.consts
            def update(
                state: DataTypeHistogram, batch, consts
            ) -> DataTypeHistogram:
                from deequ_tpu.sketches.hll import PRESENCE_DICT_CAP

                table = consts["lut"]
                rows = _row_mask(batch, where_fn)
                valid = batch[f"{col}::mask"] & rows
                codes = batch[f"{col}::codes"]
                if table.shape[0] <= PRESENCE_DICT_CAP:
                    counts = counts_from_code_presence(
                        codes[None, :],
                        valid[None, :],
                        rows,
                        table[None, :],
                    )[0]
                    return DataTypeHistogram(
                        state.counts + counts.astype(jnp.int64)
                    )
                bucket = table[jnp.clip(codes, 0, table.shape[0] - 1)]
                return DataTypeHistogram(
                    state.counts + _bucket_counts(bucket, valid, rows)
                )

            return ScanOps(
                DataTypeHistogram.identity,
                update,
                DataTypeHistogram.merge,
                consts={"lut": pad_pow2(lut, DataTypeHistogram.STRING)},
            )
        else:
            static_bucket = {
                Kind.INTEGRAL: DataTypeHistogram.INTEGRAL,
                Kind.FRACTIONAL: DataTypeHistogram.FRACTIONAL,
                Kind.BOOLEAN: DataTypeHistogram.BOOLEAN,
            }.get(kind, DataTypeHistogram.STRING)

            def update(state: DataTypeHistogram, batch) -> DataTypeHistogram:
                rows = _row_mask(batch, where_fn)
                valid = batch[f"{col}::mask"] & rows
                n_valid = jnp.sum(valid, dtype=jnp.int64)
                n_null = jnp.sum(rows & ~valid, dtype=jnp.int64)
                counts = state.counts
                counts = counts.at[static_bucket].add(n_valid)
                counts = counts.at[DataTypeHistogram.NULL].add(n_null)
                return DataTypeHistogram(counts)

        return ScanOps(
            DataTypeHistogram.identity, update, DataTypeHistogram.merge
        )

    def compute_metric_from_state(self, state) -> Metric:
        if state is None:
            state = DataTypeHistogram.identity()
        counts = np.asarray(state.counts)[:5]
        total = int(counts.sum())
        values = {
            name: DistributionValue(
                int(c), (int(c) / total) if total else 0.0
            )
            for name, c in zip(_BUCKET_NAMES, counts)
        }
        dist = Distribution(values, number_of_bins=5)
        return HistogramMetric(
            Entity.COLUMN, "DataType", self.instance, Success(dist)
        )


def inferred_kind(metric: HistogramMetric) -> Kind:
    """Decide a concrete type from the histogram, the way the reference's
    profiler promotes string columns (SURVEY.md §3.3 pass 1->2): any
    String => String; any Fractional => Fractional (integrals embed);
    else Integral / Boolean / Unknown."""
    dist = metric.value.get()
    non_null = {
        k: v.absolute for k, v in dist.values.items() if k != "Unknown"
    }
    total = sum(non_null.values())
    if total == 0:
        return Kind.UNKNOWN
    if non_null.get("String", 0) > 0:
        return Kind.STRING
    if non_null.get("Fractional", 0) > 0:
        if non_null.get("Boolean", 0) > 0:
            return Kind.STRING
        return Kind.FRACTIONAL
    if non_null.get("Boolean", 0) > 0:
        if non_null.get("Integral", 0) > 0:
            return Kind.STRING
        return Kind.BOOLEAN
    return Kind.INTEGRAL
