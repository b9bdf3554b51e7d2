"""ApproxCountDistinct: HLL cardinality estimate.

Reference: ``analyzers/ApproxCountDistinct.scala`` + the
``StatefulHyperloglogPlus`` Catalyst aggregate (SURVEY.md §2.2/§2.3).
State = int8[2^14] registers; update = hash+clz+scatter-max inside the
shared fused scan; merge = elementwise max (mesh all-reduce / persisted
state merge). Nulls are ignored, matching the reference.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from deequ_tpu.analyzers.base import (
    Precondition,
    ScanOps,
    ScanShareableAnalyzer,
    has_column,
)
from deequ_tpu.analyzers.basic import _compile_where, _row_mask
from deequ_tpu.analyzers.states import ApproxCountDistinctState
from deequ_tpu.data.table import LANE_HASH, ColumnRequest, Dataset, Kind
from deequ_tpu.metrics.metric import DoubleMetric
from deequ_tpu.sketches import hll


def hll_value_repr(dataset: Dataset, column: str) -> str:
    """What an HLL over ``column`` reads: numeric values, dictionary
    codes, or a lane column's row hashes."""
    if dataset.schema.kind_of(column) != Kind.STRING:
        return "values"
    return LANE_HASH if dataset.string_lane(column) else "codes"


@dataclass(frozen=True)
class ApproxCountDistinct(ScanShareableAnalyzer):
    column: str
    where: Optional[str] = None

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self) -> List[Precondition]:
        return [has_column(self.column)]

    def device_requests(self, dataset: Dataset) -> List[ColumnRequest]:
        _, reqs = _compile_where(self.where, dataset)
        return [
            ColumnRequest(self.column, hll_value_repr(dataset, self.column)),
            ColumnRequest(self.column, "mask"),
        ] + reqs

    def make_ops(self, dataset: Dataset) -> ScanOps:
        from deequ_tpu.analyzers.base import pad_pow2

        where_fn, _ = _compile_where(self.where, dataset)
        col = self.column
        kind = dataset.schema.kind_of(col)

        def init() -> ApproxCountDistinctState:
            return ApproxCountDistinctState(
                np.zeros(hll.M, dtype=np.int8)
            )

        if kind == Kind.STRING and dataset.string_lane(col):
            consts = None

            def registers_of(batch, c, mask):
                # the hashed-string lane: each row ships its own hash
                h1, h2 = hll.lane_hash_pair(batch[f"{col}::{LANE_HASH}"])
                return hll.registers_from_hash_pair(h1, h2, mask)

        elif kind == Kind.STRING:
            # hash LUTs as runtime inputs (pow2-padded): the compiled
            # scan is shared across datasets — see ScanOps.consts
            lut1_host, lut2_host = hll.dictionary_hash_pairs(
                dataset.dictionary(col)
            )
            consts = {"h1": pad_pow2(lut1_host), "h2": pad_pow2(lut2_host)}

            def registers_of(batch, c, mask):
                lut1, lut2 = c["h1"], c["h2"]
                if lut1.shape[0] <= hll.PRESENCE_DICT_CAP:
                    # small dictionary: presence compare-reduce beats
                    # the per-row gather+scatter (sketches/hll.py)
                    return hll.registers_from_code_presence(
                        batch[f"{col}::codes"][None, :],
                        mask[None, :],
                        lut1[None, :],
                        lut2[None, :],
                    )[0]
                codes = jnp.clip(
                    batch[f"{col}::codes"], 0, lut1.shape[0] - 1
                )
                return hll.registers_from_hash_pair(
                    lut1[codes], lut2[codes], mask
                )

        else:
            consts = None

            def registers_of(batch, c, mask):
                h1, h2 = hll.hash_pair_numeric(batch[f"{col}::values"])
                return hll.registers_from_hash_pair(h1, h2, mask)

        def update(state: ApproxCountDistinctState, batch, consts_in=None):
            mask = batch[f"{col}::mask"] & _row_mask(batch, where_fn)
            regs = registers_of(batch, consts_in, mask)
            return ApproxCountDistinctState(
                jnp.maximum(state.registers, regs)
            )

        return ScanOps(
            init, update, ApproxCountDistinctState.merge, consts=consts
        )

    def compute_metric_from_state(self, state) -> DoubleMetric:
        if state is None:
            return DoubleMetric.success(
                self.entity, "ApproxCountDistinct", self.instance, 0.0
            )
        return DoubleMetric.success(
            self.entity,
            "ApproxCountDistinct",
            self.instance,
            hll.estimate(np.asarray(state.registers)),
        )
