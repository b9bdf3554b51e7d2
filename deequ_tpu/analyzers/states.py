"""Analyzer state types: fixed-shape pytrees forming commutative monoids.

Reference: the ``State[S]`` family in
``src/main/scala/com/amazon/deequ/analyzers/*.scala`` (SURVEY.md §2.2) —
``NumMatches``, ``NumMatchesAndCount``, ``MeanState``, ``MinState``,
``MaxState``, ``SumState``, ``StandardDeviationState`` (Welford),
``CorrelationState``. Each state here is a NamedTuple of scalars/arrays
(hence automatically a JAX pytree), with a dataset-independent ``merge``
so persisted states can be combined without touching data
(``runOnAggregatedStates``, SURVEY.md §3.2).

All merges are commutative and associative; identities are provided by
the analyzers' ``ScanOps.init``.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Type

import jax.numpy as jnp
import numpy as np


def _facc(value: float = 0.0):
    """Scalar in the configured accumulation float dtype (see
    deequ_tpu.config: f64 default; f32 avoids emulated f64 scalar ops on
    TPU at the cost of cross-batch rounding)."""
    from deequ_tpu import config

    return config.options().accumulation_float()(value)


def _iacc(value: int = 0):
    """Count scalar — always int64: counts are exact row-count semantics
    regardless of the float accumulation knob (i64 scalar adds are a few
    emulated ops per batch, never per element)."""
    return np.int64(value)


def nan_largest_min(a, b):
    """Min under Spark's ordering, where NaN ranks ABOVE every value
    including +inf (SURVEY.md §2.2 numeric semantics): NaN loses to any
    non-NaN operand; min(NaN, NaN) = NaN. A plain ``jnp.minimum``
    propagates NaN, which would let one all-NaN shard poison a merged
    Minimum. The MAX side needs no counterpart — NaN-propagating
    ``jnp.maximum`` IS Spark's max (NaN is the largest value)."""
    return jnp.where(
        jnp.isnan(a), b, jnp.where(jnp.isnan(b), a, jnp.minimum(a, b))
    )


class NumMatches(NamedTuple):
    num_matches: jnp.ndarray  # int64 scalar

    @staticmethod
    def identity() -> "NumMatches":
        return NumMatches(_iacc(0))

    @staticmethod
    def merge(a: "NumMatches", b: "NumMatches") -> "NumMatches":
        return NumMatches(a.num_matches + b.num_matches)


class NumMatchesAndCount(NamedTuple):
    num_matches: jnp.ndarray
    count: jnp.ndarray

    @staticmethod
    def identity() -> "NumMatchesAndCount":
        return NumMatchesAndCount(_iacc(0), _iacc(0))

    @staticmethod
    def merge(
        a: "NumMatchesAndCount", b: "NumMatchesAndCount"
    ) -> "NumMatchesAndCount":
        return NumMatchesAndCount(
            a.num_matches + b.num_matches, a.count + b.count
        )

    @property
    def metric_value(self):
        return self.num_matches / self.count


class SumState(NamedTuple):
    sum_value: jnp.ndarray  # float64
    count: jnp.ndarray  # int64; tracks emptiness

    @staticmethod
    def identity() -> "SumState":
        return SumState(_facc(0.0), _iacc(0))

    @staticmethod
    def merge(a: "SumState", b: "SumState") -> "SumState":
        return SumState(a.sum_value + b.sum_value, a.count + b.count)


class MeanState(NamedTuple):
    total: jnp.ndarray  # float64
    count: jnp.ndarray  # int64

    @staticmethod
    def identity() -> "MeanState":
        return MeanState(_facc(0.0), _iacc(0))

    @staticmethod
    def merge(a: "MeanState", b: "MeanState") -> "MeanState":
        return MeanState(a.total + b.total, a.count + b.count)


class MinState(NamedTuple):
    min_value: jnp.ndarray  # float64
    count: jnp.ndarray

    @staticmethod
    def identity() -> "MinState":
        # always f64: min/max carries no accumulation error (see
        # basic._mmin) and must not round large ints. NaN, not +inf:
        # under the Spark ordering NaN is nan_largest_min's identity —
        # +inf would beat an all-NaN column's NaN and surface as a
        # bogus min of inf. count==0 guards the truly-empty case.
        return MinState(np.float64(np.nan), _iacc(0))

    @staticmethod
    def merge(a: "MinState", b: "MinState") -> "MinState":
        return MinState(
            nan_largest_min(a.min_value, b.min_value), a.count + b.count
        )


class MaxState(NamedTuple):
    max_value: jnp.ndarray
    count: jnp.ndarray

    @staticmethod
    def identity() -> "MaxState":
        return MaxState(np.float64(-np.inf), _iacc(0))

    @staticmethod
    def merge(a: "MaxState", b: "MaxState") -> "MaxState":
        return MaxState(jnp.maximum(a.max_value, b.max_value), a.count + b.count)


class StandardDeviationState(NamedTuple):
    """Welford-style mergeable variance accumulator (n, avg, m2)."""

    n: jnp.ndarray  # float64
    avg: jnp.ndarray
    m2: jnp.ndarray

    @staticmethod
    def identity() -> "StandardDeviationState":
        # always f64: n carries an exact count (config.py promises the
        # accumulation knob never rounds counts) and the moments are
        # per-batch scalars — f64 here costs a few emulated ops per
        # batch, never per element
        z = np.float64(0.0)
        return StandardDeviationState(z, z, z)

    @staticmethod
    def merge(
        a: "StandardDeviationState", b: "StandardDeviationState"
    ) -> "StandardDeviationState":
        n = a.n + b.n
        safe_n = jnp.maximum(n, 1.0)
        delta = b.avg - a.avg
        avg = jnp.where(n > 0, a.avg + delta * b.n / safe_n, 0.0)
        m2 = a.m2 + b.m2 + delta * delta * a.n * b.n / safe_n
        return StandardDeviationState(n, avg, m2)


class CorrelationState(NamedTuple):
    """Mergeable Pearson correlation accumulator (Spark Corr-style)."""

    n: jnp.ndarray
    x_avg: jnp.ndarray
    y_avg: jnp.ndarray
    ck: jnp.ndarray  # co-moment
    x_mk: jnp.ndarray
    y_mk: jnp.ndarray

    @staticmethod
    def identity() -> "CorrelationState":
        z = np.float64(0.0)  # see StandardDeviationState.identity
        return CorrelationState(z, z, z, z, z, z)

    @staticmethod
    def merge(a: "CorrelationState", b: "CorrelationState") -> "CorrelationState":
        n = a.n + b.n
        safe_n = jnp.maximum(n, 1.0)
        dx = b.x_avg - a.x_avg
        dy = b.y_avg - a.y_avg
        frac = a.n * b.n / safe_n
        x_avg = jnp.where(n > 0, a.x_avg + dx * b.n / safe_n, 0.0)
        y_avg = jnp.where(n > 0, a.y_avg + dy * b.n / safe_n, 0.0)
        ck = a.ck + b.ck + dx * dy * frac
        x_mk = a.x_mk + b.x_mk + dx * dx * frac
        y_mk = a.y_mk + b.y_mk + dy * dy * frac
        return CorrelationState(n, x_avg, y_avg, ck, x_mk, y_mk)


class SumPairState(NamedTuple):
    """For RatioOfSums: two sums plus a row count."""

    sum_a: jnp.ndarray
    sum_b: jnp.ndarray
    count: jnp.ndarray

    @staticmethod
    def identity() -> "SumPairState":
        return SumPairState(_facc(0.0), _facc(0.0), _iacc(0))

    @staticmethod
    def merge(a: "SumPairState", b: "SumPairState") -> "SumPairState":
        return SumPairState(
            a.sum_a + b.sum_a, a.sum_b + b.sum_b, a.count + b.count
        )


class DataTypeHistogram(NamedTuple):
    """Counts per inferred type bucket, packed as one int64[6] vector:
    [null, fractional, integral, boolean, string, (reserved)].
    Merge = elementwise sum (a psum across the mesh)."""

    counts: jnp.ndarray  # int64[6]

    NULL = 0
    FRACTIONAL = 1
    INTEGRAL = 2
    BOOLEAN = 3
    STRING = 4

    @staticmethod
    def identity() -> "DataTypeHistogram":
        return DataTypeHistogram(np.zeros(6, dtype=np.int64))

    @staticmethod
    def merge(a: "DataTypeHistogram", b: "DataTypeHistogram") -> "DataTypeHistogram":
        return DataTypeHistogram(a.counts + b.counts)


class ApproxCountDistinctState(NamedTuple):
    """HLL registers (int8[m]; rho <= 33 — narrow dtype quarters the
    wire bytes when states cross to the host); merge = elementwise max
    (SURVEY.md §2.3: the reference's StatefulHyperloglogPlus merges
    register words by word-wise max — here the registers are a device
    vector and the merge is a ``lax.max`` all-reduce). States persisted
    as int32 by older builds promote cleanly on merge."""

    registers: jnp.ndarray  # int8[m]

    @staticmethod
    def merge(
        a: "ApproxCountDistinctState", b: "ApproxCountDistinctState"
    ) -> "ApproxCountDistinctState":
        return ApproxCountDistinctState(jnp.maximum(a.registers, b.registers))


# (The KLL sketch state is host-side — deequ_tpu.sketches.kll.KLLSketchState —
# because its compaction is data-dependent; its device-side per-batch
# pre-compaction output is transient and never persisted.)

# Persisted-state format versions: bump when a state's INTERPRETATION
# changes (not just its shape), so stale states are rejected instead of
# silently merged wrong. v2 of ApproxCountDistinctState: integral
# columns hash the raw int64 payload (v1 float-canonicalized, colliding
# above 2^53) — v1 registers place the same values in different
# registers, so a v1+v2 max-merge would double-count.
STATE_FORMAT_VERSIONS: Dict[str, int] = {
    "ApproxCountDistinctState": 2,
}


# Registry used by state serde (deequ_tpu.io.state_provider).
STATE_TYPES: Dict[str, Type] = {
    cls.__name__: cls
    for cls in (
        NumMatches,
        NumMatchesAndCount,
        SumState,
        MeanState,
        MinState,
        MaxState,
        StandardDeviationState,
        CorrelationState,
        SumPairState,
        DataTypeHistogram,
        ApproxCountDistinctState,
    )
}
