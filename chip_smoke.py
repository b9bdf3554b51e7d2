#!/usr/bin/env python3
"""deequ_tpu's main path, once, on a real TPU chip.

Drives the entry points a user calls (``VerificationSuite``,
``ColumnProfilerRunner``, ``Dataset.from_parquet``) over a TPC-DS
``store_sales``-faithful table made from ``--seed`` (default 8M rows x
50 columns, resident on the device), and checks every metric against a
plain numpy/pyarrow reference computed from the same arrays:

- ``verify``: one mixed-constraint check, run as one fused scan;
- ``pallas``: HLL through the Pallas scatter kernel, equal to XLA's;
- ``profile``: the column profiler over all 50 columns;
- ``stream``: a 2M-row slice written to parquet shards and streamed
  back with the device cache off; equal to the resident run.

``--chips 4`` runs only the ``verify`` phase, on a 4-device ``dp`` mesh
and on one device, both against the reference.

Earlier lines report each phase's wall time, compile count and time,
and peak device bytes. The last line is the JSON result; any mismatch,
exception or missing chip exits non-zero and prints no result. The
tests rehearse the phase functions on the CPU (tests/test_chip_smoke.py);
``main()`` itself refuses to run anywhere but a TPU.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

ROWS = 8_000_000
COLS = 50
STREAM_ROWS = 2_000_000
STREAM_SHARDS = 4
# the run's output directory (gitignored; the parquet shards written
# under it are removed when the stream phase ends)
OUT_DIR = os.path.join("chiprun_out", "chip_smoke")
# float sums, means and standard deviations agree to this relative error
REL_TOL = 1e-6
# HLL: 4x the standard error 1.04/sqrt(m) at the engine's m = 2^14
HLL_TOL = 4 * 1.04 / math.sqrt(1 << 14)
# KLL: docs/KLL_ERROR.md §4 bounds the rank error by 3/k at k = 2048
KLL_RANK_TOL = 3 / 2048
CONTAINED = [f"cat_{j:03d}" for j in range(32)]


class Mismatch(AssertionError):
    """A metric disagreed with the plain reference."""


def log(msg: str) -> None:
    print(msg, flush=True)


# -- data and the plain reference -------------------------------------------


def build_table(rows: int, cols: int, seed: int):
    from deequ_tpu.testing.tpcds import store_sales_faithful

    return store_sales_faithful(rows, cols, seed)


class Reference:
    """Plain numpy/pyarrow answers over an Arrow table, computed on
    demand and memoized per column; independent of deequ_tpu."""

    def __init__(self, table):
        self.table = table
        self.n = table.num_rows
        self._memo: dict = {}

    def _col(self, name: str):
        import pyarrow as pa

        col = self.table.column(name).combine_chunks()
        if pa.types.is_dictionary(col.type):
            col = col.cast(col.type.value_type)
        return col

    def _memoized(self, key, fn):
        if key not in self._memo:
            self._memo[key] = fn()
        return self._memo[key]

    def valid(self, name: str) -> np.ndarray:
        """Non-null values, as float64 (exact for every column here)."""

        def compute():
            col = self._col(name)
            return col.drop_null().to_numpy(zero_copy_only=False).astype(
                np.float64
            )

        return self._memoized(("valid", name), compute)

    def completeness(self, name: str) -> float:
        return (self.n - self.table.column(name).null_count) / self.n

    def minimum(self, name: str) -> float:
        return float(self.valid(name).min())

    def maximum(self, name: str) -> float:
        return float(self.valid(name).max())

    def sum(self, name: str) -> float:
        return float(self.valid(name).sum())

    def mean(self, name: str) -> float:
        return float(self.valid(name).mean())

    def stddev(self, name: str) -> float:
        return float(self.valid(name).std())  # population, as deequ

    def distinct(self, name: str) -> int:
        import pyarrow.compute as pc

        return self._memoized(
            ("distinct", name),
            lambda: int(pc.count_distinct(self._col(name)).as_py()),
        )

    def uniqueness(self, name: str) -> float:
        import pyarrow.compute as pc

        def compute():
            counts = pc.value_counts(self._col(name).drop_null())
            ones = int(np.count_nonzero(
                counts.field("counts").to_numpy() == 1
            ))
            return ones / self.n

        return self._memoized(("unique", name), compute)

    def compliance(self, mask: np.ndarray) -> float:
        return int(np.count_nonzero(mask)) / self.n

    def rank_interval(self, name: str, value: float):
        """Normalised rank interval [lo, hi] that ``value`` occupies in
        the sorted non-null values."""
        ordered = self._memoized(
            ("sorted", name), lambda: np.sort(self.valid(name))
        )
        n = len(ordered)
        lo = np.searchsorted(ordered, value, side="left") / n
        hi = np.searchsorted(ordered, value, side="right") / n
        return lo, hi


def _exact(got, want) -> bool:
    return got == want


def _rel(got, want) -> bool:
    return abs(got - want) <= REL_TOL * max(abs(want), 1e-300)


def _hll(got, want) -> bool:
    return abs(got - want) <= HLL_TOL * max(want, 1)


def peer_rule(rule):
    """How two runs of the system must agree on a metric: as closely as
    each agrees with the reference, except that HLL registers merge by
    max, so every execution path yields the same estimate."""
    return _exact if rule is _hll else rule


def compare(label: str, got, want, rule) -> None:
    if not rule(got, want):
        raise Mismatch(
            f"{label}: got {got!r}, reference {want!r} ({rule.__name__})"
        )


def compare_quantile(label: str, got: float, q: float, ref: Reference,
                     column: str) -> None:
    lo, hi = ref.rank_interval(column, got)
    if not (lo - KLL_RANK_TOL <= q <= hi + KLL_RANK_TOL):
        raise Mismatch(
            f"{label}: estimate {got!r} sits at ranks [{lo}, {hi}], "
            f"outside {q} +- {KLL_RANK_TOL}"
        )


# -- instrumentation ---------------------------------------------------------


class CompileMeter:
    """Counts backend compiles (cache hits included) and their seconds
    through ``jax.monitoring``; read a phase as a delta."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return (self.compiles, self.compile_s, self.cache_hits)


def device_summary() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def timed_phase(name: str, meter: CompileMeter, fn, *args, **kwargs):
    """Run one phase and print its wall, compile and memory line."""
    before = meter.snapshot()
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    after = meter.snapshot()
    log(
        f"[{name}] ok wall_s={wall} compiles={after[0] - before[0]} "
        f"compile_s={after[1] - before[1]} "
        f"compile_cache_hits={after[2] - before[2]} "
        f"device={device_summary()['kind']} peak_bytes={peak_bytes()}"
    )
    return out


# -- phase: verify -----------------------------------------------------------

NUMERIC = ("price0", "ext0", "qty0", "m0")


def verification_check(ref: Reference):
    """One check mixing constraint kinds; every assertion holds on the
    reference, so the suite must report SUCCESS."""
    from deequ_tpu import Check, CheckLevel

    check = (
        Check(CheckLevel.ERROR, "chip smoke")
        .has_size(lambda n: n == ref.n)
        .is_complete("m0")
        .has_completeness("price0", lambda c: c > 0.9)
    )
    for col in NUMERIC:
        check = (
            check.has_min(col, math.isfinite)
            .has_max(col, math.isfinite)
            .has_mean(col, lambda v: v > 0)
            .has_sum(col, lambda v: v > 0)
            .has_standard_deviation(col, lambda v: v > 0)
        )
    return (
        check.satisfies("price1 >= 50", "price1 at least 50",
                        lambda f: 0 < f < 1)
        .is_contained_in("c0", CONTAINED, lambda f: 0 < f < 1)
        .has_uniqueness("k0", lambda u: 0 < u <= 1)
        .has_number_of_distinct_values("k0", lambda d: d > 0)
        .has_uniqueness("ext0", lambda u: 0 <= u <= 1)
        .has_number_of_distinct_values("ext0", lambda d: d > 0)
        .has_approx_count_distinct("k1", lambda d: d > 0)
        .has_approx_count_distinct("ext1", lambda d: d > 0)
        .has_approx_count_distinct("c1", lambda d: d > 0)
        .has_approx_quantile("m0", 0.5, lambda v: v > 0)
        .has_approx_quantile("ext0", 0.9, lambda v: v > 0)
    )


def expected_metrics(ref: Reference):
    """(analyzer, reference value, rule) for every metric the check
    computes; rule "kll" carries (quantile, column)."""
    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        ApproxQuantile,
        Completeness,
        Compliance,
        CountDistinct,
        Maximum,
        Mean,
        Minimum,
        Size,
        StandardDeviation,
        Sum,
        Uniqueness,
    )

    price1 = ref.table.column("price1").to_numpy()
    c0 = ref._col("c0").to_numpy(zero_copy_only=False)
    out = [
        (Size(), float(ref.n), _exact),
        (Completeness("m0"), ref.completeness("m0"), _exact),
        (Completeness("price0"), ref.completeness("price0"), _exact),
        (Compliance("price1 at least 50", "price1 >= 50"),
         ref.compliance(price1 >= 50), _exact),
        (Compliance(
            "c0 contained in " + ",".join(CONTAINED),
            "c0 IS NULL OR c0 IN ("
            + ", ".join(f"'{v}'" for v in CONTAINED) + ")",
        ), ref.compliance(np.isin(c0, CONTAINED)), _exact),
    ]
    for col in NUMERIC:
        out += [
            (Minimum(col), ref.minimum(col), _exact),
            (Maximum(col), ref.maximum(col), _exact),
            (Mean(col), ref.mean(col), _rel),
            (Sum(col), ref.sum(col), _rel),
            (StandardDeviation(col), ref.stddev(col), _rel),
        ]
    for col in ("k0", "ext0"):
        out += [
            (Uniqueness(col), ref.uniqueness(col), _exact),
            (CountDistinct(col), float(ref.distinct(col)), _exact),
        ]
    for col in ("k1", "ext1", "c1"):
        out.append(
            (ApproxCountDistinct(col), float(ref.distinct(col)), _hll)
        )
    for col, q in (("m0", 0.5), ("ext0", 0.9)):
        out.append((ApproxQuantile(col, q), (q, col), "kll"))
    return out


def metric_values(result, expected) -> dict:
    values = {}
    for analyzer, _, _ in expected:
        metric = result.metrics.get(analyzer)
        if metric is None:
            raise Mismatch(f"{analyzer}: no metric computed")
        if not metric.value.is_success:
            raise Mismatch(f"{analyzer}: {metric.value}")
        values[analyzer] = float(metric.value.get())
    return values


def check_against_reference(label: str, values: dict, expected,
                            ref: Reference) -> None:
    for analyzer, want, rule in expected:
        name = f"{label} {analyzer}"
        if rule == "kll":
            q, column = want
            compare_quantile(name, values[analyzer], q, ref, column)
        else:
            compare(name, values[analyzer], want, rule)


def run_verification(ds, ref: Reference, engine=None):
    """One suite run; returns (result, metric values, data passes,
    seconds in the suite's ``run()``)."""
    from deequ_tpu import VerificationSuite
    from deequ_tpu.telemetry import get_telemetry

    passes = get_telemetry().counter("engine.data_passes")
    before = passes.value
    builder = VerificationSuite().on_data(ds).add_check(
        verification_check(ref)
    )
    if engine is not None:
        builder = builder.with_engine(engine)
    t0 = time.perf_counter()
    result = builder.run()
    run_s = time.perf_counter() - t0
    values = metric_values(result, expected_metrics(ref))
    return result, values, passes.value - before, run_s


def phase_verify(ds, ref: Reference, label: str = "verify",
                 engine=None, one_scan: bool = True) -> dict:
    """The suite against the reference; with ``one_scan``, also as one
    fused scan (streamed sources may add dictionary pre-passes)."""
    from deequ_tpu import CheckStatus
    from deequ_tpu.engine import AnalysisEngine

    engine = engine or AnalysisEngine()
    result, values, passes, run_s = run_verification(ds, ref, engine)
    if result.status != CheckStatus.SUCCESS:
        raise Mismatch(
            f"{label}: status {result.status}: "
            f"{result.check_results_as_records()}"
        )
    # one trace for a fresh plan, none for a cached one: a second means
    # a step's signature changed mid-scan (a retrace and a recompile)
    if one_scan and (passes != 1 or engine.trace_count > 1):
        raise Mismatch(
            f"{label}: not one fused scan (data passes {passes}, traces "
            f"{engine.trace_count}, plan cache hit {engine.plan_cache_hit})"
        )
    check_against_reference(label, values, expected_metrics(ref), ref)
    log(f"[{label}] {len(values)} metrics match the reference; "
        f"run_s={run_s} data_passes={passes} traces={engine.trace_count}")
    return values


# -- phase: profile ----------------------------------------------------------


def phase_profile(ds, ref: Reference) -> None:
    import pyarrow as pa

    from deequ_tpu.profiles import ColumnProfilerRunner

    t0 = time.perf_counter()
    profiles = ColumnProfilerRunner().on_data(ds).run()
    run_s = time.perf_counter() - t0
    if profiles.num_records != ref.n:
        raise Mismatch(f"profile: {profiles.num_records} records")
    for name in ref.table.column_names:
        profile = profiles.profiles[name]
        label = f"profile {name}"
        compare(f"{label} completeness", profile.completeness,
                ref.completeness(name), _exact)
        compare(f"{label} approx distinct",
                profile.approximate_num_distinct_values,
                ref.distinct(name), _hll)
        typ = ref.table.schema.field(name).type
        if pa.types.is_dictionary(typ):
            continue
        for field, want, rule in (
            ("minimum", ref.minimum(name), _exact),
            ("maximum", ref.maximum(name), _exact),
            ("mean", ref.mean(name), _rel),
            ("sum", ref.sum(name), _rel),
            ("std_dev", ref.stddev(name), _rel),
        ):
            got = getattr(profile, field, None)
            if got is None:
                raise Mismatch(f"{label}: no {field}")
            compare(f"{label} {field}", got, want, rule)
    log(f"[profile] {len(ref.table.column_names)} column profiles match "
        f"the reference; run_s={run_s}")


# -- phase: stream -----------------------------------------------------------


def phase_stream(table, rows: int, out_dir: str, shards: int = STREAM_SHARDS):
    """Write ``rows`` rows as parquet shards, stream them back with the
    device cache off, and require the resident run's metrics."""
    import pyarrow.parquet as pq

    from deequ_tpu import config
    from deequ_tpu.data import Dataset

    piece = table.slice(0, rows)
    ref = Reference(piece)
    os.makedirs(out_dir, exist_ok=True)
    shard_dir = tempfile.mkdtemp(prefix="stream-", dir=out_dir)
    try:
        step = -(-rows // shards)
        for i in range(shards):
            pq.write_table(
                piece.slice(i * step, step),
                os.path.join(shard_dir, f"part-{i:03d}.parquet"),
            )
        resident = phase_verify(Dataset.from_arrow(piece), ref,
                                "stream/resident")
        streamed_ds = Dataset.from_parquet(shard_dir)
        with config.configure(device_cache_bytes=0):
            streamed = phase_verify(streamed_ds, ref, "stream/parquet",
                                    one_scan=False)
        if streamed_ds._materialized:
            raise Mismatch(
                "stream: host materialized "
                f"{sorted(streamed_ds._materialized)}"
            )
    finally:
        shutil.rmtree(shard_dir, ignore_errors=True)
    for analyzer, want, rule in expected_metrics(ref):
        if rule == "kll":  # both already within the rank bound
            continue
        compare(f"stream vs resident {analyzer}", streamed[analyzer],
                resident[analyzer], peer_rule(rule))
    log(f"[stream] {rows} rows from {shards} parquet shards equal the "
        "resident run")


# -- phase: pallas ----------------------------------------------------------


def phase_pallas(ds, ref: Reference) -> None:
    """HLL registers through the Pallas scatter kernel equal the XLA
    scatter's (``config.pallas_scatter``): on a TPU the kernel runs or
    the run fails, never a silent fallback."""
    from deequ_tpu import config
    from deequ_tpu.analyzers import AnalysisRunner, ApproxCountDistinct
    from deequ_tpu.sketches import pallas_scatter

    analyzers = [ApproxCountDistinct(c) for c in ("k1", "ext1", "c1", "m1")]
    runs = {}
    for flag in (False, True):
        with config.configure(pallas_scatter=flag):
            runs[flag] = AnalysisRunner.do_analysis_run(ds, analyzers)
            token = pallas_scatter.impl_token()
        if token != ("pallas" if flag else "xla"):
            raise Mismatch(f"pallas: pallas_scatter={flag} ran {token}")
    for a in analyzers:
        got = runs[True].metric(a).value.get()
        compare(f"pallas {a}", got, runs[False].metric(a).value.get(),
                _exact)
        compare(f"pallas {a}", got, ref.distinct(a.column), _hll)
    log(f"[pallas] kernel registers equal the XLA scatter's on "
        f"{len(analyzers)} columns")


# -- phase: mesh (--chips 4) -------------------------------------------------


def phase_mesh(ds, ref: Reference, devices, batch_size=None) -> None:
    """``verify`` on a ``dp`` mesh over ``devices`` and on one device:
    both equal the reference, and each other. ``batch_size`` (default:
    the engine's) lets a small rehearsal take several steps."""
    from jax.sharding import Mesh

    from deequ_tpu.engine import AnalysisEngine

    single = phase_verify(ds, ref, "mesh/single",
                          AnalysisEngine(batch_size=batch_size))
    mesh = Mesh(np.array(devices), ("dp",))
    meshed = phase_verify(ds, ref, f"mesh/dp{len(devices)}",
                          AnalysisEngine(mesh=mesh, batch_size=batch_size))
    for analyzer, _, rule in expected_metrics(ref):
        if rule == "kll":
            continue
        compare(f"mesh vs single {analyzer}", meshed[analyzer],
                single[analyzer], peer_rule(rule))
    log(f"[mesh] dp{len(devices)} mesh equals one device equals the "
        "reference")


# -- main --------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax

    device = device_summary()
    if device["platform"] != "tpu" or device["count"] < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s), JAX found "
              f"{device}", file=sys.stderr)
        return 2

    from deequ_tpu import config
    from deequ_tpu.data import Dataset

    meter = CompileMeter()
    log(f"[setup] device {device} jax {jax.__version__} compile cache "
        f"{config.options().compilation_cache_dir or 'off'}")
    t0 = time.perf_counter()
    table = build_table(ROWS, COLS, args.seed)
    ref = Reference(table)
    ds = Dataset.from_arrow(table)
    log(f"[setup] {ROWS}x{COLS} table from seed {args.seed} in "
        f"{time.perf_counter() - t0} s")

    if args.chips == 4:
        timed_phase("mesh", meter, phase_mesh, ds, ref, jax.devices()[:4])
    else:
        timed_phase("verify", meter, phase_verify, ds, ref)
        timed_phase("pallas", meter, phase_pallas, ds, ref)
        timed_phase("profile", meter, phase_profile, ds, ref)
        timed_phase("stream", meter, phase_stream, table, STREAM_ROWS,
                    OUT_DIR)
    log(f"[done] peak_bytes_in_use={peak_bytes()} "
        f"compile_s_total={meter.compile_s} compiles={meter.compiles} "
        f"cache_hits={meter.cache_hits}")
    # the chips this run used, which a host with more may exceed
    print(json.dumps({"ok": True, "device": {**device, "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
