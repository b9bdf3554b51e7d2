"""Benchmark harness: measures the BASELINE.json configs on one chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The headline metric is rows/sec/chip for the full ColumnProfiler
(BASELINE.json: 1B rows x 50 cols TPC-DS in <60s on v5e-8 => a per-chip
baseline of 1e9 rows / 60 s / 8 chips ~= 2.083e6 rows/sec/chip).
The workload here is scaled to one chip's memory: the profiler runs once
to populate compile caches (a 1B-row run amortizes compilation across
~250 batches; a scaled run must not be charged full compile cost), then
the measured run profiles FRESH data of identical shape, so transfers
and device execution are fully re-measured.

Secondary configs (fused numeric bundle, grouping, sketches) are timed
the same way and reported in the detail dict on stderr.

The run is BUDGETED (--budget seconds, default
$DEEQU_TPU_BENCH_BUDGET_S or 600): secondary configs are skipped —
with a note in the detail dict — once the remaining budget can't cover
their estimated cost, and the headline JSON line is ALWAYS printed.
``--quick`` runs the headline config only, at reduced scale.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


NORTH_STAR_ROWS_PER_SEC_PER_CHIP = 1e9 / 60.0 / 8.0  # BASELINE.json


def _timed(fn):
    """(wall_s, bytes_shipped, link MB/s, result) for one run — the
    transfer counter lets a slow round be decomposed into link vs
    compute straight from the bench artifact (VERDICT r2 weak #6)."""
    from deequ_tpu.data.table import transfer_bytes

    b0 = transfer_bytes()
    t0 = time.time()
    result = fn()
    wall = time.time() - t0
    shipped = transfer_bytes() - b0
    return wall, shipped, (shipped / wall / 1e6 if wall > 0 else 0.0), result


def _phases(run_metadata):
    """Sum the engine's per-pass wall decomposition events into one
    dict (VERDICT r3 next #2): host_wait_s = source read/convert;
    put_s = transfer dispatch incl. link backpressure; dispatch_s =
    jitted step dispatch; first_step_s = the first step alone (carries
    any trace/compile cost, so cold runs don't read as dispatch
    overhead); sync_s = blocked on the device queue (remaining
    transfers + compute). wall ≈ sum of the five; under a saturated
    link, attribution BETWEEN buckets is indicative only (GIL/
    backpressure smear — see deequ_tpu.telemetry.phases.PhaseClock)."""
    from deequ_tpu.telemetry import summarize_phases

    return summarize_phases(
        run_metadata.events if run_metadata else []
    )


# --------------------------------------------------------------------------
# Crash-proof harness: host probe, row auto-sizing, subprocess-per-config
# --------------------------------------------------------------------------


def probe_host() -> dict:
    """What this host can actually sustain: cores and available memory
    — recorded in the artifact so a round's numbers are interpretable,
    and fed to :func:`autosize` (ROADMAP item 1: the 1-core CI
    container segfaults ≥1M-row streamed runs that a real host shrugs
    off). It never starts a JAX backend: the parent of spawned configs
    must stay off the chip, or every child fails or hangs reaching it;
    each config reports its own device (:func:`_bench_child`)."""
    probe = {"cpu_count": os.cpu_count() or 1, "mem_available_mb": None}
    try:
        with open("/proc/meminfo", encoding="ascii") as fh:
            for line in fh:
                if line.startswith("MemAvailable:"):
                    probe["mem_available_mb"] = int(line.split()[1]) // 1024
                    break
    except OSError:
        pass
    return probe


def autosize(probe: dict) -> dict:
    """Row sizing for this host. ``$DEEQU_TPU_BENCH_SCALE`` overrides
    everything; otherwise small (≤2-core) hosts run at 1/4 scale — 1/8
    under real memory pressure — and streamed configs are additionally
    capped below the documented ≥1M-row crash threshold, so the bench
    measures the engine rather than the container's limits."""
    env = os.environ.get("DEEQU_TPU_BENCH_SCALE", "")
    cores = probe.get("cpu_count") or 1
    mem_mb = probe.get("mem_available_mb")
    if env:
        scale = max(0.001, float(env))
    else:
        scale = 0.25 if cores <= 2 else 1.0
        if mem_mb is not None and mem_mb < 6_000:
            scale = min(scale, 0.125 if mem_mb < 3_000 else 0.25)
    streaming_cap = 800_000 if (cores <= 2 and not env) else None
    return {"row_scale": scale, "streaming_row_cap": streaming_cap}


def _sized(base_rows: int, sizing: dict, streamed: bool = False) -> int:
    rows = max(100_000, int(base_rows * sizing["row_scale"]))
    cap = sizing.get("streaming_row_cap") if streamed else None
    return min(rows, cap) if cap else rows


#: config name -> thunk over the sized-args dict. Looked up CHILD-SIDE
#: by :func:`_bench_child`, so only ``(name, args)`` cross the spawn
#: pipe — the lambdas themselves are never pickled.
CONFIG_REGISTRY = {
    "profiler": lambda a: bench_profiler(a["rows"], a["cols"]),
    "profiler_50col": lambda a: bench_profiler_wide(a["rows"], 50),
    "profiler_50col_8m": lambda a: bench_profiler_wide(a["rows"], 50),
    "fused_bundle_10col": lambda a: bench_fused_bundle(a["rows"]),
    "grouping_5cat": lambda a: bench_grouping(a["rows"]),
    "one_pass_spill_grouping": lambda a: bench_one_pass_grouping(a["rows"]),
    "sketches_hll_kll": lambda a: bench_sketches(a["rows"]),
    "resilience_overhead": lambda a: bench_resilience_overhead(a["rows"]),
    "memory_backoff_overhead": (
        lambda a: bench_memory_backoff_overhead(a["rows"])
    ),
    "watchdog_overhead": lambda a: bench_watchdog_overhead(a["rows"]),
    "service_concurrent_suites": (
        lambda a: bench_service_concurrent_suites(a["rows"], a["clients"])
    ),
    "service_coalesced_suites": (
        lambda a: bench_service_coalesced_suites(a["rows"], a["clients"])
    ),
    "service_elastic_placement": (
        lambda a: bench_service_elastic_placement(a["rows"], a["clients"])
    ),
    "service_preemption": (
        lambda a: bench_service_preemption(a["rows"], a["clients"])
    ),
    "spill_grouping_12M_distinct": lambda a: bench_spill_grouping(a["rows"]),
    "joint_grouping_mi_1Mcard_pair": lambda a: bench_joint_grouping(a["rows"]),
    "streaming_parquet": (
        lambda a: bench_streaming_parquet(a["rows"], a["cols"])
    ),
    "streaming_wire_diet": lambda a: bench_streaming_wire_diet(a["rows"]),
    "streaming_ingest_parallel": (
        lambda a: bench_streaming_ingest_parallel(a["rows"], a["cols"])
    ),
    "streaming_bundle_100m": lambda a: bench_streaming_bundle_100m(a["rows"]),
    "rowlevel_egress": lambda a: bench_rowlevel_egress(a["rows"]),
    "egress_resume": lambda a: bench_egress_resume(a["rows"]),
    "fleet_failover": lambda a: bench_fleet_failover(a["rows"]),
}


#: extra environment a config's spawned child needs, applied by
#: ``run_one`` around the spawn and restored after (the parent's
#: already-initialized jax backend is unaffected — only the child's
#: fresh import reads it). ``service_elastic_placement`` measures
#: sub-slice placement, which needs a multi-device pool; on a CPU
#: host that means forcing virtual host devices.
CONFIG_CHILD_ENV = {
    "service_elastic_placement": {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    },
    # BENCH_r12 bisection (docs/PERF.md "Streaming crash family"): the
    # r11 SIGSEGV/SIGABRT pair did NOT reproduce on this host — both
    # configs run clean at 800k rows with a warm persistent XLA cache
    # present. The cache remains the one shared mutable input these two
    # children have that the healthy configs don't exercise as hard, so
    # it stays disabled here as a cheap containment (cost: one extra
    # in-child compile, ~2s) until a reproducing host pins the cause.
    "streaming_wire_diet": {"DEEQU_TPU_COMPILE_CACHE": ""},
    "streaming_ingest_parallel": {"DEEQU_TPU_COMPILE_CACHE": ""},
}


def _apply_child_env(name: str):
    """Set a config's CONFIG_CHILD_ENV vars, returning a restore
    thunk. XLA_FLAGS composes: an existing device-count flag wins
    (the caller already chose a pool size), anything else is appended
    to rather than clobbered."""
    saved = {}
    for key, value in CONFIG_CHILD_ENV.get(name, {}).items():
        prior = os.environ.get(key)
        saved[key] = prior
        if key == "XLA_FLAGS" and prior:
            if "xla_force_host_platform_device_count" in prior:
                continue
            value = f"{prior} {value}"
        os.environ[key] = value

    def restore():
        for key, prior in saved.items():
            if prior is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = prior

    return restore


def _bench_child(payload: dict):
    """``IsolatedRunner`` child entry: run ONE config and ship its
    detail dict back over the pipe. Each config is self-warming, so a
    fresh process per config pays only the import+compile it already
    paid — and a SIGSEGV in one config can no longer take out the
    artifact: its status lands in the JSON and the next config runs in
    a clean process."""
    import jax

    result = CONFIG_REGISTRY[payload["name"]](payload["args"])
    devices = jax.devices()
    result["device"] = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    return result


def _tpcds_like(num_rows: int, num_cols: int, seed: int):
    """A store_sales-shaped synthetic table: ~60% numeric measures,
    ~20% integral keys, ~20% low-cardinality categorical strings."""
    import pyarrow as pa

    from deequ_tpu.data import Dataset

    rng = np.random.default_rng(seed)
    cols = {}
    n_num = max(1, int(num_cols * 0.6))
    n_key = max(1, int(num_cols * 0.2))
    n_cat = max(1, num_cols - n_num - n_key)
    for i in range(n_num):
        vals = rng.normal(100.0, 25.0, num_rows).astype(np.float32)
        if i % 3 == 0:  # some nulls so masks are real
            idx = rng.integers(0, num_rows, num_rows // 50)
            vals[idx] = np.nan
            arr = pa.array(vals, pa.float32(), mask=np.isnan(vals))
        else:
            arr = pa.array(vals, pa.float32())
        cols[f"m{i}"] = arr
    for i in range(n_key):
        cols[f"k{i}"] = pa.array(
            rng.integers(0, 10_000_000, num_rows, dtype=np.int64)
        )
    cats = np.array([f"cat_{j:03d}" for j in range(64)])
    for i in range(n_cat):
        cols[f"c{i}"] = pa.array(
            cats[rng.integers(0, len(cats), num_rows)]
        ).dictionary_encode()
    return Dataset.from_arrow(pa.table(cols))


def bench_profiler(num_rows: int, num_cols: int):
    """Config 5 / north star: full ColumnProfiler."""
    from deequ_tpu.profiles.profiler import ColumnProfiler

    warm = _tpcds_like(num_rows, num_cols, seed=1)
    warm_s, _, _, _ = _timed(lambda: ColumnProfiler.profile(warm))

    fresh = _tpcds_like(num_rows, num_cols, seed=2)
    wall, shipped, mbps, profiles = _timed(
        lambda: ColumnProfiler.profile(fresh)
    )
    out = {
        "wall_s": wall,
        "cold_s": warm_s,
        "rows_per_sec": num_rows / wall,
        "bytes_shipped": shipped,
        "link_mb_per_sec": mbps,
        "phases": _phases(profiles.run_metadata),
    }
    if profiles.run_metadata is not None:
        out["passes"] = profiles.run_metadata.as_records()
    # steady state: re-profile the SAME dataset (columns device-resident)
    # — separates compute/plan capability from the host->device link
    resident_wall, resident_shipped, _, _ = _timed(
        lambda: ColumnProfiler.profile(fresh)
    )
    out["resident_rerun_s"] = resident_wall
    out["resident_rows_per_sec"] = num_rows / resident_wall
    out["resident_bytes_shipped"] = resident_shipped
    return out


def bench_fused_bundle(num_rows: int):
    """Config 2: Mean/StdDev/Min/Max/Compliance over 10 numeric cols."""
    import pyarrow as pa

    from deequ_tpu.analyzers import (
        AnalysisRunner,
        Compliance,
        Maximum,
        Mean,
        Minimum,
        StandardDeviation,
    )
    from deequ_tpu.data import Dataset

    def make(seed):
        rng = np.random.default_rng(seed)
        return Dataset.from_arrow(
            pa.table(
                {
                    f"n{i}": rng.normal(0, 1, num_rows).astype(np.float32)
                    for i in range(10)
                }
            )
        )

    analyzers = []
    for i in range(10):
        analyzers += [
            Mean(f"n{i}"),
            StandardDeviation(f"n{i}"),
            Minimum(f"n{i}"),
            Maximum(f"n{i}"),
        ]
    analyzers.append(Compliance("n0 pos", "n0 > 0"))

    AnalysisRunner.do_analysis_run(make(1), analyzers)  # warm compile
    fresh = make(2)
    wall, shipped, mbps, ctx = _timed(
        lambda: AnalysisRunner.do_analysis_run(fresh, analyzers)
    )
    return {
        "wall_s": wall,
        "rows_per_sec": num_rows / wall,
        "bytes_shipped": shipped,
        "link_mb_per_sec": mbps,
        "phases": _phases(ctx.run_metadata),
    }


def bench_grouping(num_rows: int):
    """Config 3: Distinctness + Uniqueness + Histogram on categoricals."""
    import pyarrow as pa

    from deequ_tpu.analyzers import (
        AnalysisRunner,
        Distinctness,
        Histogram,
        Uniqueness,
    )
    from deequ_tpu.data import Dataset

    def make(seed):
        rng = np.random.default_rng(seed)
        cats = np.array([f"v{j}" for j in range(1000)])
        return Dataset.from_arrow(
            pa.table(
                {
                    f"c{i}": pa.array(
                        cats[rng.integers(0, len(cats), num_rows)]
                    ).dictionary_encode()
                    for i in range(5)
                }
            )
        )

    analyzers = []
    for i in range(5):
        analyzers += [
            Distinctness([f"c{i}"]),
            Uniqueness([f"c{i}"]),
            Histogram(f"c{i}"),
        ]

    AnalysisRunner.do_analysis_run(make(1), analyzers)
    fresh = make(2)
    wall, shipped, mbps, ctx = _timed(
        lambda: AnalysisRunner.do_analysis_run(fresh, analyzers)
    )
    return {
        "wall_s": wall,
        "rows_per_sec": num_rows / wall,
        "bytes_shipped": shipped,
        "link_mb_per_sec": mbps,
        "phases": _phases(ctx.run_metadata),
    }


def bench_sketches(num_rows: int):
    """Config 4: HLL ApproxCountDistinct + KLL ApproxQuantile, high-card."""
    import pyarrow as pa

    from deequ_tpu.analyzers import (
        AnalysisRunner,
        ApproxCountDistinct,
        ApproxQuantile,
    )
    from deequ_tpu.data import Dataset

    def make(seed):
        rng = np.random.default_rng(seed)
        return Dataset.from_arrow(
            pa.table(
                {
                    "id": rng.integers(0, 1 << 40, num_rows, dtype=np.int64),
                    "x": rng.normal(0, 1, num_rows).astype(np.float32),
                }
            )
        )

    analyzers = [ApproxCountDistinct("id"), ApproxQuantile("x", 0.5)]
    AnalysisRunner.do_analysis_run(make(1), analyzers)
    fresh = make(2)
    wall, shipped, mbps, ctx = _timed(
        lambda: AnalysisRunner.do_analysis_run(fresh, analyzers)
    )
    return {
        "wall_s": wall,
        "rows_per_sec": num_rows / wall,
        "bytes_shipped": shipped,
        "link_mb_per_sec": mbps,
        "phases": _phases(ctx.run_metadata),
    }


def _tpcds_faithful(num_rows: int, num_cols: int, seed: int):
    """The store_sales-faithful wide table (deequ_tpu/testing/tpcds.py,
    shared with chip_smoke.py). Its data changed at PR 21: extended
    amounts are float64 (was float32) and null price slots hold their
    cent value (was NaN), so the 50-col configs' numbers before and
    after PR 21 are not compared. The 20-col headline keeps `_tpcds_like`'s
    all-continuous measures for round-over-round comparability."""
    from deequ_tpu.data import Dataset
    from deequ_tpu.testing.tpcds import store_sales_faithful

    return Dataset.from_arrow(store_sales_faithful(num_rows, num_cols, seed))


def bench_profiler_wide(num_rows: int, num_cols: int):
    """The NORTH-STAR-shaped config (VERDICT r4 next #2): a first-class
    resident measurement at 50 columns on the store_sales-faithful
    mix, so the 1B x 50 cell-rate claim is measured, not extrapolated.
    cold_s also tracks compile scaling (~300 analyzers)."""
    from deequ_tpu.profiles.profiler import ColumnProfiler

    fresh = _tpcds_faithful(num_rows, num_cols, seed=4)
    # cold_s = compile + transfer together (one dataset keeps this
    # config affordable); a warm-compile link rate would need a second
    # full transfer, and the 20-col headline already measures the link
    # properly — so no link_mb_per_sec here (it would be understated
    # by the ~300-analyzer compile share)
    cold_s, shipped, _, _ = _timed(lambda: ColumnProfiler.profile(fresh))
    # resident reruns: min of two — run 2 has warm registers, so the
    # adaptive mid-cardinality dedup path (sketches/hll.py) is active
    # exactly as it would be on every batch but the first of a 1B run
    r1, _, _, _ = _timed(lambda: ColumnProfiler.profile(fresh))
    r2, _, _, _ = _timed(lambda: ColumnProfiler.profile(fresh))
    resident_wall = min(r1, r2)
    rate = num_rows / resident_wall
    return {
        "cold_compile_plus_transfer_s": cold_s,
        "bytes_shipped": shipped,
        "resident_wall_s": resident_wall,
        "resident_rows_per_sec": rate,
        "ns_per_cell": 1e9 / (rate * num_cols),
        # the link-independent projection: what the 1B x 50 north star
        # costs at THIS chip's measured resident rate on 8 chips
        "projected_1b_x50_resident_8chip_s": 1e9 / (rate * 8),
    }


def bench_spill_grouping(num_rows: int):
    """High-cardinality exact grouping (~num_rows distinct int64 keys):
    the device sort+segment path vs the host Arrow group_by, fresh and
    device-resident."""
    from deequ_tpu import config
    from deequ_tpu.analyzers import (
        AnalysisRunner,
        CountDistinct,
        Distinctness,
        Uniqueness,
    )
    from deequ_tpu.data import Dataset

    def make(seed):
        import pyarrow as pa

        rng = np.random.default_rng(seed)
        return Dataset.from_arrow(
            pa.table(
                {"id": rng.integers(0, 1 << 40, num_rows, dtype=np.int64)}
            )
        )

    analyzers = [CountDistinct("id"), Uniqueness("id"), Distinctness("id")]
    AnalysisRunner.do_analysis_run(make(5), analyzers)  # warm compile
    fresh = make(6)
    wall, shipped, mbps, ctx = _timed(
        lambda: AnalysisRunner.do_analysis_run(fresh, analyzers)
    )
    resident_wall, _, _, _ = _timed(
        lambda: AnalysisRunner.do_analysis_run(fresh, analyzers)
    )
    with config.configure(device_spill_grouping=False):
        host_ds = make(6)
        arrow_wall, _, _, _ = _timed(
            lambda: AnalysisRunner.do_analysis_run(host_ds, analyzers)
        )
    spilled = [
        e for e in (ctx.run_metadata.events if ctx.run_metadata else [])
        if e.get("event") == "grouping_spill"
    ]
    return {
        "wall_s": wall,
        "rows_per_sec": num_rows / wall,
        "bytes_shipped": shipped,
        "link_mb_per_sec": mbps,
        "resident_wall_s": resident_wall,
        "resident_rows_per_sec": num_rows / resident_wall,
        "host_arrow_wall_s": arrow_wall,
        "device_vs_arrow_resident": arrow_wall / resident_wall,
        "spill_events": spilled,
    }


def bench_one_pass_grouping(num_rows: int):
    """The one-pass-spill config: a grouping-heavy mixed suite — two
    high-cardinality int id columns and an f64 column under
    Uniqueness / Distinctness / CountDistinct, plus scalar analyzers —
    run with ``config.one_pass_spill`` on (spill key extraction rides
    the shared fused scan, sorts overlap) vs off (one deferred re-scan
    per spill plan). Reports wall AND passes over the source
    (``engine.data_passes``) for each form: the tentpole claim is the
    mixed suite costing exactly ONE traversal."""
    import pyarrow as pa

    from deequ_tpu import config
    from deequ_tpu.analyzers import (
        AnalysisRunner,
        Completeness,
        CountDistinct,
        Distinctness,
        Mean,
        Uniqueness,
    )
    from deequ_tpu.data import Dataset
    from deequ_tpu.telemetry import get_telemetry

    def make(seed):
        rng = np.random.default_rng(seed)
        return Dataset.from_arrow(
            pa.table(
                {
                    "id_a": rng.integers(
                        0, 1 << 40, num_rows, dtype=np.int64
                    ),
                    "id_b": rng.integers(
                        0, 1 << 40, num_rows, dtype=np.int64
                    ),
                    "price": rng.normal(0, 1, num_rows),
                    "x": rng.normal(0, 1, num_rows),
                }
            )
        )

    analyzers = [
        Mean("x"),
        Completeness("price"),
        Uniqueness("id_a"),
        Distinctness("id_b"),
        CountDistinct("price"),
    ]

    def passes() -> int:
        snapshot = get_telemetry().metrics.snapshot()
        return snapshot["counters"].get("engine.data_passes", 0)

    out = {}
    for label, one_pass in (("one_pass", True), ("per_plan", False)):
        with config.configure(one_pass_spill=one_pass):
            AnalysisRunner.do_analysis_run(make(31), analyzers)  # warm
            fresh = make(32)
            before = passes()
            wall, shipped, mbps, _ = _timed(
                lambda: AnalysisRunner.do_analysis_run(fresh, analyzers)
            )
            out[label] = {
                "wall_s": wall,
                "rows_per_sec": num_rows / wall,
                "passes_over_source": passes() - before,
                "bytes_shipped": shipped,
                "link_mb_per_sec": mbps,
            }
    out["speedup_one_pass"] = (
        out["per_plan"]["wall_s"] / out["one_pass"]["wall_s"]
    )
    return out


def bench_joint_grouping(num_rows: int):
    """r4 config (VERDICT r3 next #7): MutualInformation + Uniqueness
    over a PAIR of ~1M-cardinality int columns (joint key space far
    past the dense budget -> the packed-joint-code device sort), plus
    an f64 high-cardinality column (host-packed u64 keys on TPU, where
    the X64 rewriter lacks the f64 bitcast). Host Arrow comparison
    included."""
    import pyarrow as pa

    from deequ_tpu import config
    from deequ_tpu.analyzers import (
        AnalysisRunner,
        CountDistinct,
        MutualInformation,
        Uniqueness,
    )
    from deequ_tpu.data import Dataset

    def make(seed):
        rng = np.random.default_rng(seed)
        a = rng.integers(0, 1 << 20, num_rows, dtype=np.int64)
        b = np.where(
            rng.random(num_rows) < 0.5,
            a,
            rng.integers(0, 1 << 20, num_rows),
        )
        return Dataset.from_arrow(
            pa.table(
                {
                    "a": pa.array(a),
                    "b": pa.array(b),
                    "f": pa.array(rng.normal(0, 1, num_rows)),
                }
            )
        )

    analyzers = [
        MutualInformation(["a", "b"]),
        Uniqueness(["a", "b"]),
        CountDistinct("f"),
    ]
    AnalysisRunner.do_analysis_run(make(21), analyzers)  # warm compile
    fresh = make(22)
    wall, shipped, mbps, ctx = _timed(
        lambda: AnalysisRunner.do_analysis_run(fresh, analyzers)
    )
    with config.configure(device_spill_grouping=False):
        arrow_wall, _, _, _ = _timed(
            lambda: AnalysisRunner.do_analysis_run(make(22), analyzers)
        )
    events = [
        e
        for e in (ctx.run_metadata.events if ctx.run_metadata else [])
        if e.get("event") == "grouping_spill"
    ]
    return {
        "wall_s": wall,
        "rows_per_sec": num_rows / wall,
        "bytes_shipped": shipped,
        "link_mb_per_sec": mbps,
        "host_arrow_wall_s": arrow_wall,
        "device_vs_arrow": arrow_wall / wall,
        "spill_events": events,
    }


def bench_streaming_parquet(num_rows: int, num_cols: int):
    """Streaming ingest config: profile a multi-file parquet table with
    the device cache disabled — memory stays O(batch), every byte
    re-streams from storage through the packed-mask wire diet."""
    import shutil
    import tempfile

    import pyarrow.parquet as pq

    from deequ_tpu import config
    from deequ_tpu.data import Dataset
    from deequ_tpu.profiles.profiler import ColumnProfiler

    workdir = tempfile.mkdtemp(prefix="deequ_tpu_bench_pq_")
    try:
        ds = _tpcds_like(num_rows, num_cols, seed=7)
        shard_rows = num_rows // 4
        for i in range(4):
            # the last shard takes the remainder so every row lands
            length = None if i == 3 else shard_rows
            pq.write_table(
                ds.table.slice(i * shard_rows, length),
                f"{workdir}/part{i}.parquet",
            )
        with config.configure(device_cache_bytes=0, batch_size=1 << 19):
            ColumnProfiler.profile(Dataset.from_parquet(workdir))  # warm
            wall, shipped, mbps, profiles = _timed(
                lambda: ColumnProfiler.profile(Dataset.from_parquet(workdir))
            )
        return {
            "wall_s": wall,
            "rows_per_sec": num_rows / wall,
            "bytes_shipped": shipped,
            "link_mb_per_sec": mbps,
            "phases": _phases(profiles.run_metadata),
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def bench_streaming_wire_diet(num_rows: int = 4_000_000):
    """Wire-diet config (docs/PERF.md): the SAME multi-file parquet
    table streamed twice — per-column codecs + one-pass dictionary
    deltas ON vs OFF — so the bytes/row reduction and the put/compute
    overlap of the depth-2 pipeline are measured differentially on
    identical data. The table is codec-friendly on purpose: int64 keys
    whose stats admit i16/i32, f64 measures that are f32-exact, and
    dictionary strings (codes + deltas instead of a value pre-pass)."""
    import shutil
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    from deequ_tpu import config
    from deequ_tpu.analyzers import (
        AnalysisRunner,
        ApproxCountDistinct,
        DataType,
        Maximum,
        Mean,
        Minimum,
    )
    from deequ_tpu.data import Dataset
    from deequ_tpu.telemetry import get_telemetry

    rng = np.random.default_rng(17)
    workdir = tempfile.mkdtemp(prefix="deequ_tpu_bench_wire_")
    # the string suite pairs ACD + DataType on BOTH columns so the
    # codes ride one pooled unit: deltas on = ONE traversal of the
    # source; deltas off re-reads each column once for its value_set
    # (data_passes 1 vs 3 in the artifact)
    analyzers = [
        Mean("f0"), Minimum("f0"), Maximum("f0"), Mean("f1"),
        Minimum("k0"), Maximum("k0"), ApproxCountDistinct("k1"),
        ApproxCountDistinct("k2"),
        ApproxCountDistinct("s0"), ApproxCountDistinct("s1"),
        DataType("s0"), DataType("s1"),
    ]
    try:
        shard_rows = num_rows // 4
        cats = np.array([f"cat_{j:04d}" for j in range(512)])
        for i in range(4):
            rows = num_rows - 3 * shard_rows if i == 3 else shard_rows
            # f32-exact doubles: generate as f32, store as f64
            f = rng.normal(100.0, 25.0, rows).astype(np.float32)
            pq.write_table(
                pa.table(
                    {
                        "f0": pa.array(f.astype(np.float64)),
                        "f1": pa.array(
                            np.abs(f).astype(np.float64)
                        ),
                        "k0": pa.array(
                            rng.integers(0, 30_000, rows, dtype=np.int64)
                        ),
                        "k1": pa.array(
                            rng.integers(0, 100, rows, dtype=np.int64)
                        ),
                        "k2": pa.array(
                            rng.integers(0, 2, rows, dtype=np.int64)
                        ),
                        "s0": pa.array(
                            cats[rng.integers(0, len(cats), rows)]
                        ),
                        "s1": pa.array(
                            cats[rng.integers(0, 64, rows)]
                        ),
                    }
                ),
                f"{workdir}/part{i}.parquet",
            )

        tm = get_telemetry()

        def run(codecs_on: bool):
            with config.configure(
                device_cache_bytes=0,
                batch_size=1 << 19,
                wire_codecs=codecs_on,
                dict_deltas=codecs_on,
            ):
                AnalysisRunner.do_analysis_run(  # warm the plan
                    Dataset.from_parquet(workdir), analyzers
                )
                raw0 = tm.counter("engine.wire_bytes_raw").value
                enc0 = tm.counter("engine.wire_bytes_encoded").value
                passes0 = tm.counter("engine.data_passes").value
                wall, shipped, mbps, ctx = _timed(
                    lambda: AnalysisRunner.do_analysis_run(
                        Dataset.from_parquet(workdir), analyzers
                    )
                )
                return {
                    "wall_s": wall,
                    "rows_per_sec": num_rows / wall,
                    "bytes_shipped": shipped,
                    "link_mb_per_sec": mbps,
                    "raw_bytes_per_row": (
                        tm.counter("engine.wire_bytes_raw").value - raw0
                    ) / num_rows,
                    "encoded_bytes_per_row": (
                        tm.counter("engine.wire_bytes_encoded").value
                        - enc0
                    ) / num_rows,
                    "data_passes": (
                        tm.counter("engine.data_passes").value - passes0
                    ),
                    "phases": _phases(ctx.run_metadata),
                }

        on = run(True)
        off = run(False)
        return {
            "codecs_on": on,
            "codecs_off": off,
            "bytes_per_row_reduction": (
                off["encoded_bytes_per_row"] / on["encoded_bytes_per_row"]
                if on["encoded_bytes_per_row"] > 0
                else 0.0
            ),
            "wall_speedup": off["wall_s"] / on["wall_s"],
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def bench_streaming_ingest_parallel(
    num_rows: int = 4_000_000, num_cols: int = 10
):
    """Parallel-ingest config (docs/PERF.md r10): the SAME multi-file
    parquet table streamed at ingest_workers ∈ {1, 2, 4} — workers=1
    is the legacy single-prefetcher oracle, workers>1 the ordered
    decode/encode pool — so the wall delta is attributable to host
    decode overlap alone. The analyzer suite is one-pass on purpose
    (scalars + codes-borne ACD/DataType; no dictionary materializer)
    and the artifact pins data_passes == 1 per run plus bit-identical
    metrics across worker counts. NOTE the host matters: the pool
    overlaps HOST decode across cores, so on a 1-core container the
    w4/w1 speedup reads ~1.0x by construction — host_cpu_count is in
    the artifact so the verdict can tell a regression from a small
    host."""
    import shutil
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    from deequ_tpu import config
    from deequ_tpu.analyzers import (
        AnalysisRunner,
        ApproxCountDistinct,
        Completeness,
        DataType,
        Maximum,
        Mean,
        Minimum,
    )
    from deequ_tpu.data import Dataset
    from deequ_tpu.telemetry import get_telemetry

    rng = np.random.default_rng(23)
    workdir = tempfile.mkdtemp(prefix="deequ_tpu_bench_ingest_")
    analyzers = [
        Mean("f0"), Minimum("f0"), Maximum("f0"),
        Mean("f1"), Completeness("f2"),
        Minimum("k0"), Maximum("k1"), ApproxCountDistinct("k2"),
        # ACD + DataType PAIRED per string column: the pair rides one
        # pooled codes unit inside the single pass; a lone string
        # analyzer would trigger the dictionary pre-pass and break the
        # data_passes == 1 pin this config asserts
        ApproxCountDistinct("s0"), DataType("s0"),
        ApproxCountDistinct("s1"), DataType("s1"),
    ]
    try:
        shard_rows = num_rows // 4
        cats = np.array([f"cat_{j:04d}" for j in range(512)])
        for i in range(4):
            rows = num_rows - 3 * shard_rows if i == 3 else shard_rows
            f = rng.normal(100.0, 25.0, rows).astype(np.float32)
            f2 = f.astype(np.float64)
            f2[rng.integers(0, rows, rows // 50)] = np.nan
            pq.write_table(
                pa.table(
                    {
                        "f0": pa.array(f.astype(np.float64)),
                        "f1": pa.array(np.abs(f).astype(np.float64)),
                        "f2": pa.array(f2, mask=np.isnan(f2)),
                        "k0": pa.array(
                            rng.integers(0, 30_000, rows, dtype=np.int64)
                        ),
                        "k1": pa.array(
                            rng.integers(0, 100, rows, dtype=np.int64)
                        ),
                        "k2": pa.array(
                            rng.integers(0, 1 << 20, rows, dtype=np.int64)
                        ),
                        "s0": pa.array(
                            cats[rng.integers(0, len(cats), rows)]
                        ),
                        "s1": pa.array(cats[rng.integers(0, 64, rows)]),
                    }
                ),
                f"{workdir}/part{i}.parquet",
            )

        tm = get_telemetry()

        def run(workers: int):
            with config.configure(
                device_cache_bytes=0,
                batch_size=1 << 19,
                wire_codecs=True,
                dict_deltas=True,
                ingest_workers=workers,
            ):
                AnalysisRunner.do_analysis_run(  # warm the plan
                    Dataset.from_parquet(workdir), analyzers
                )
                passes0 = tm.counter("engine.data_passes").value
                wall, shipped, mbps, ctx = _timed(
                    lambda: AnalysisRunner.do_analysis_run(
                        Dataset.from_parquet(workdir), analyzers
                    )
                )
                events = (
                    ctx.run_metadata.events if ctx.run_metadata else []
                )
                pool = {}
                for e in events:
                    if e.get("event") == "ingest_pool":
                        for k in (
                            "workers", "released", "decode_s",
                            "encode_s", "idle_s", "stall_s", "wall_s",
                            "peak_in_flight", "peak_in_flight_bytes",
                        ):
                            pool[k] = pool.get(k, 0) + e.get(k, 0)
                phases = _phases(ctx.run_metadata)
                out = {
                    "wall_s": wall,
                    "rows_per_sec": num_rows / wall,
                    "link_mb_per_sec": mbps,
                    "data_passes": (
                        tm.counter("engine.data_passes").value - passes0
                    ),
                    # decode wall vs run wall: >1x aggregate decode_s
                    # per wall second means the pool really overlapped
                    "host_wait_s": phases.get("host_wait_s", 0.0),
                    "phases": phases,
                }
                if pool:
                    out["pool"] = pool
                    out["decode_overlap_x"] = (
                        (pool["decode_s"] + pool["encode_s"]) / wall
                        if wall > 0 else 0.0
                    )
                metrics = {
                    (m.instance, m.name): m.value
                    for m in ctx.all_metrics()
                }
                return out, metrics

        results = {}
        baselines = None
        identical = True
        for w in (1, 2, 4):
            results[f"workers_{w}"], metrics = run(w)
            if baselines is None:
                baselines = metrics
            elif metrics != baselines:
                identical = False
        w1 = results["workers_1"]["wall_s"]
        return {
            **results,
            "metrics_identical_across_workers": identical,
            "speedup_w2": w1 / results["workers_2"]["wall_s"],
            "speedup_w4": w1 / results["workers_4"]["wall_s"],
            "host_cpu_count": os.cpu_count(),
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def bench_resilience_overhead(num_rows: int = 4_000_000):
    """Resilience tax on a CLEAN scan (docs/RESILIENCE.md): the same
    streaming fused-bundle run with retry + periodic checkpointing ON
    (ScanCheckpointer to local disk, every 2 batches) vs OFF
    (max_attempts=1, no checkpointer). No faults fire — this prices the
    bookkeeping alone: per-batch try dispatch, device_get of carried
    states at each checkpoint, and the pickle+fsync. Reported as pct
    overhead over the unprotected wall."""
    import shutil
    import tempfile

    import pyarrow as pa

    from deequ_tpu import config
    from deequ_tpu.analyzers import (
        AnalysisRunner,
        Compliance,
        Maximum,
        Mean,
        Minimum,
        StandardDeviation,
    )
    from deequ_tpu.data import Dataset
    from deequ_tpu.engine.resilience import RetryPolicy
    from deequ_tpu.engine.scan import AnalysisEngine
    from deequ_tpu.io.state_provider import ScanCheckpointer
    from deequ_tpu.telemetry import get_telemetry

    def make(seed):
        rng = np.random.default_rng(seed)
        return Dataset.from_arrow(
            pa.table(
                {
                    f"n{i}": rng.normal(0, 1, num_rows).astype(np.float32)
                    for i in range(10)
                }
            )
        )

    analyzers = []
    for i in range(10):
        analyzers += [
            Mean(f"n{i}"),
            StandardDeviation(f"n{i}"),
            Minimum(f"n{i}"),
            Maximum(f"n{i}"),
        ]
    analyzers.append(Compliance("n0 pos", "n0 > 0"))

    workdir = tempfile.mkdtemp(prefix="deequ_tpu_bench_ckpt_")
    try:
        with config.configure(device_cache_bytes=0, batch_size=1 << 19):
            AnalysisRunner.do_analysis_run(make(41), analyzers)  # warm
            fresh = make(42)
            with config.configure(
                scan_retry=RetryPolicy(max_attempts=1)
            ):
                off_wall, _, _, _ = _timed(
                    lambda: AnalysisRunner.do_analysis_run(
                        fresh, analyzers
                    )
                )
            tm = get_telemetry()
            ckpts_before = tm.counter("engine.checkpoints_written").value
            with config.configure(checkpoint_every_batches=2):
                engine = AnalysisEngine(
                    checkpointer=ScanCheckpointer(workdir)
                )
                on_wall, _, _, _ = _timed(
                    lambda: AnalysisRunner.do_analysis_run(
                        fresh, analyzers, engine=engine
                    )
                )
            ckpts = tm.counter("engine.checkpoints_written").value
        return {
            "unprotected_wall_s": off_wall,
            "protected_wall_s": on_wall,
            "checkpoints_written": ckpts - ckpts_before,
            "overhead_pct": round(
                100.0 * (on_wall - off_wall) / off_wall, 2
            ) if off_wall > 0 else 0.0,
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def bench_memory_backoff_overhead(num_rows: int = 4_000_000):
    """Memory-protection tax on a CLEAN scan (docs/RESILIENCE.md
    "Memory pressure"): the same streaming fused-bundle run with the
    adaptive batch backoff armed (config.memory_backoff, the default)
    vs disabled. No allocation failure fires — this prices the
    machinery alone: the per-dispatch try frame, the backoff controller
    checks, and the effective-batch gauge. Acceptance bar is <2%
    overhead (a clean run must not pay for protection it never uses)."""
    import pyarrow as pa

    from deequ_tpu import config
    from deequ_tpu.analyzers import (
        AnalysisRunner,
        Compliance,
        Maximum,
        Mean,
        Minimum,
        StandardDeviation,
    )
    from deequ_tpu.data import Dataset

    def make(seed):
        rng = np.random.default_rng(seed)
        return Dataset.from_arrow(
            pa.table(
                {
                    f"n{i}": rng.normal(0, 1, num_rows).astype(np.float32)
                    for i in range(10)
                }
            )
        )

    analyzers = []
    for i in range(10):
        analyzers += [
            Mean(f"n{i}"),
            StandardDeviation(f"n{i}"),
            Minimum(f"n{i}"),
            Maximum(f"n{i}"),
        ]
    analyzers.append(Compliance("n0 pos", "n0 > 0"))

    with config.configure(device_cache_bytes=0, batch_size=1 << 19):
        AnalysisRunner.do_analysis_run(make(41), analyzers)  # warm
        fresh = make(42)
        with config.configure(memory_backoff=False):
            off_wall, _, _, _ = _timed(
                lambda: AnalysisRunner.do_analysis_run(fresh, analyzers)
            )
        with config.configure(memory_backoff=True):
            on_wall, _, _, _ = _timed(
                lambda: AnalysisRunner.do_analysis_run(fresh, analyzers)
            )
    return {
        "unprotected_wall_s": off_wall,
        "protected_wall_s": on_wall,
        "overhead_pct": round(
            100.0 * (on_wall - off_wall) / off_wall, 2
        ) if off_wall > 0 else 0.0,
    }


def bench_watchdog_overhead(num_rows: int = 4_000_000):
    """Supervision tax on a CLEAN scan (docs/RESILIENCE.md): the same
    streaming fused-bundle run with a run budget armed (watchdog thread
    polling, per-batch deadline/stall checks, supervised prefetch queue
    polls) vs fully unsupervised. No stall or deadline fires — this
    prices the monitoring alone; the acceptance bar is <2% overhead."""
    import pyarrow as pa

    from deequ_tpu import config
    from deequ_tpu.analyzers import (
        AnalysisRunner,
        Compliance,
        Maximum,
        Mean,
        Minimum,
        StandardDeviation,
    )
    from deequ_tpu.data import Dataset
    from deequ_tpu.engine.deadline import RunBudget
    from deequ_tpu.engine.scan import AnalysisEngine

    def make(seed):
        rng = np.random.default_rng(seed)
        return Dataset.from_arrow(
            pa.table(
                {
                    f"n{i}": rng.normal(0, 1, num_rows).astype(np.float32)
                    for i in range(10)
                }
            )
        )

    analyzers = []
    for i in range(10):
        analyzers += [
            Mean(f"n{i}"),
            StandardDeviation(f"n{i}"),
            Minimum(f"n{i}"),
            Maximum(f"n{i}"),
        ]
    analyzers.append(Compliance("n0 pos", "n0 > 0"))

    with config.configure(device_cache_bytes=0, batch_size=1 << 19):
        AnalysisRunner.do_analysis_run(make(41), analyzers)  # warm
        fresh = make(42)
        off_wall, _, _, _ = _timed(
            lambda: AnalysisRunner.do_analysis_run(fresh, analyzers)
        )
        # generous limits: the watchdog is armed and polling but never
        # fires, so the delta is pure supervision machinery
        engine = AnalysisEngine(
            budget=RunBudget(deadline_s=3600.0, stall_s=600.0)
        )
        on_wall, _, _, _ = _timed(
            lambda: AnalysisRunner.do_analysis_run(
                fresh, analyzers, engine=engine
            )
        )
    return {
        "unsupervised_wall_s": off_wall,
        "supervised_wall_s": on_wall,
        "overhead_pct": round(
            100.0 * (on_wall - off_wall) / off_wall, 2
        ) if off_wall > 0 else 0.0,
    }


def _probe_link_mb_per_sec() -> float:
    """The host->device bandwidth: the MIN of two 32 MB
    transfers (forced by fetches of a device reduction) — a single
    sample on a link that swings minute-to-minute over-sizes the run
    too easily; the min is the conservative sizing input."""
    import jax

    rng = np.random.default_rng(0)
    payloads = [rng.random(4_000_000) for _ in range(3)]  # 32 MB each
    jitted = jax.jit(lambda x: x.sum())
    float(jitted(jax.device_put(payloads[0])))  # warm the compile
    worst = float("inf")
    for payload in payloads[1:]:
        t0 = time.time()
        float(jitted(jax.device_put(payload)))
        worst = min(
            worst, payload.nbytes / max(time.time() - t0, 1e-9) / 1e6
        )
    return worst


def bench_service_concurrent_suites(
    num_rows: int = 2_000_000, clients: int = 8
):
    """Multi-tenant service throughput (PR 7, docs/SERVICE.md): N
    clients across two tenants with mixed priorities verify ONE shared
    dataset key through a warm ``VerificationService``. Prices the
    whole service path — queue, scheduler, shared dataset cache, plan
    reuse — against the same suite run back-to-back directly. Reports
    recompiles-after-warmup (must be 0), dataset placements (must be
    1), and queue-wait p50/p99."""
    import threading

    import pyarrow as pa

    from deequ_tpu import Check, CheckLevel
    from deequ_tpu.data import Dataset
    from deequ_tpu.service import (
        Priority,
        RunRequest,
        VerificationService,
    )
    from deequ_tpu.telemetry import get_telemetry

    schema = {
        "k1": "int64",
        "k2": "int64",
        "v1": "float32",
        "v2": "float32",
    }

    def make():
        rng = np.random.default_rng(5)
        return Dataset.from_arrow(
            pa.table(
                {
                    "k1": rng.integers(
                        0, 1 << 40, num_rows, dtype=np.int64
                    ),
                    "k2": rng.integers(
                        0, 1 << 40, num_rows, dtype=np.int64
                    ),
                    "v1": rng.normal(0, 1, num_rows).astype(np.float32),
                    "v2": rng.normal(0, 1, num_rows).astype(np.float32),
                }
            )
        )

    def checks():
        return [
            Check(CheckLevel.ERROR, "bench-suite")
            .is_complete("k1")
            .is_complete("v1")
            .is_non_negative("k2")
        ]

    tm = get_telemetry()
    svc = VerificationService(workers=2, interactive_reserve=1).start()
    try:
        warm_wall = time.time()
        svc.warmup(
            schema,
            checks=checks(),
            profile=False,
            nullable=(False,),
            wide_ints=(True,),
            batch_size=min(num_rows, 1 << 21),
            engine_variants=[{}],
        )
        warm_wall = time.time() - warm_wall
        compiles_before = tm.counter("engine.plan_cache.misses").value
        placements_before = tm.counter(
            "service.dataset_cache.misses"
        ).value

        handles = []
        t0 = time.time()
        for i in range(clients):
            handles.append(
                svc.submit(
                    RunRequest(
                        tenant="analytics" if i % 2 else "risk",
                        checks=checks(),
                        dataset_key="bench/shared",
                        dataset_factory=make,
                        priority=(
                            Priority.BATCH
                            if i % 2
                            else Priority.INTERACTIVE
                        ),
                    )
                )
            )
        threads = [
            threading.Thread(target=h.wait, args=(600,))
            for h in handles
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.time() - t0

        waits = sorted(
            h.started_at - h.submitted_at for h in handles
        )
        compiles = (
            tm.counter("engine.plan_cache.misses").value
            - compiles_before
        )
        placements = (
            tm.counter("service.dataset_cache.misses").value
            - placements_before
        )
        return {
            "clients": clients,
            "rows": num_rows,
            "warmup_wall_s": round(warm_wall, 3),
            "wall_s": round(wall, 3),
            "runs_per_sec": round(clients / wall, 3) if wall else 0.0,
            "recompiles_after_warmup": compiles,
            "dataset_placements": placements,
            "queue_wait_p50_s": round(waits[len(waits) // 2], 4),
            "queue_wait_p99_s": round(waits[-1], 4),
        }
    finally:
        svc.stop(drain=False, timeout=30)


def bench_service_coalesced_suites(
    num_rows: int = 2_000_000, clients: int = 4
):
    """Scan coalescing (docs/SERVICE.md "Scan coalescing"): K
    overlapping BATCH suites against ONE shared dataset key, run twice
    through otherwise-identical services — coalescing OFF then ON.
    The ON phase must show ``engine.data_passes`` collapse from ~K to
    ~1 while per-run results stay identical; two INTERACTIVE gate runs
    ride along in each phase so the queue-wait split by priority class
    shows coalescing never taxes the interactive path (the ISSUE's
    acceptance criterion). Suites are submitted BEFORE the workers
    start (window 0): the first pop atomically absorbs every queued
    compatible ticket, so grouping is deterministic, not racy."""
    import threading

    import pyarrow as pa

    from deequ_tpu import Check, CheckLevel
    from deequ_tpu.data import Dataset
    from deequ_tpu.service import (
        Priority,
        RunRequest,
        VerificationService,
    )
    from deequ_tpu.telemetry import get_telemetry

    def make():
        rng = np.random.default_rng(5)
        return Dataset.from_arrow(
            pa.table(
                {
                    "k1": rng.integers(
                        0, 1 << 40, num_rows, dtype=np.int64
                    ),
                    "k2": rng.integers(
                        0, 1 << 40, num_rows, dtype=np.int64
                    ),
                    "v1": rng.normal(0, 1, num_rows).astype(np.float32),
                    "v2": rng.normal(0, 1, num_rows).astype(np.float32),
                }
            )
        )

    def suite(i):
        # K overlapping tenant suites: everyone wants completeness on
        # k1; the rest differs per tenant, so the superset is a real
        # union, not K copies of one suite
        check = Check(CheckLevel.ERROR, f"tenant-suite-{i}").is_complete(
            "k1"
        )
        if i % 2 == 0:
            check = check.is_complete("v1").is_non_negative("k2")
        else:
            check = check.is_complete("v2")
        return [check]

    def gate():
        return [
            Check(CheckLevel.ERROR, "gate").is_complete("v1")
        ]

    tm = get_telemetry()

    def phase(coalesce_on: bool):
        svc = VerificationService(
            workers=2,
            interactive_reserve=1,
            coalesce=coalesce_on,
            coalesce_window_s=0.0,
        )
        batch = [
            svc.submit(
                RunRequest(
                    tenant=f"tenant-{i}",
                    checks=suite(i),
                    dataset_key="bench/coalesce",
                    dataset_factory=make,
                    priority=Priority.BATCH,
                )
            )
            for i in range(clients)
        ]
        inter = [
            svc.submit(
                RunRequest(
                    tenant="risk",
                    checks=gate(),
                    dataset_key="bench/coalesce",
                    dataset_factory=make,
                    priority=Priority.INTERACTIVE,
                )
            )
            for _ in range(2)
        ]
        passes_before = tm.counter("engine.data_passes").value
        t0 = time.time()
        svc.start()
        try:
            threads = [
                threading.Thread(target=h.wait, args=(600,))
                for h in batch + inter
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            wall = time.time() - t0
        finally:
            svc.stop(drain=False, timeout=30)
        passes = tm.counter("engine.data_passes").value - passes_before

        def waits(handles):
            return sorted(
                max(0.0, h.started_at - h.submitted_at) for h in handles
            )
        batch_waits = waits(batch)
        inter_waits = waits(inter)
        total = len(batch) + len(inter)
        return {
            "wall_s": round(wall, 3),
            "runs_per_sec": round(total / wall, 3) if wall else 0.0,
            "data_passes": int(passes),
            "batch_wait_p50_s": round(
                batch_waits[len(batch_waits) // 2], 4
            ),
            "batch_wait_p99_s": round(batch_waits[-1], 4),
            "interactive_wait_p50_s": round(
                inter_waits[len(inter_waits) // 2], 4
            ),
            "interactive_wait_p99_s": round(inter_waits[-1], 4),
        }

    saved_before = tm.counter("service.scan_passes_saved").value
    off = phase(False)
    on = phase(True)
    saved = tm.counter("service.scan_passes_saved").value - saved_before
    return {
        "rows": num_rows,
        "clients": clients,
        "off": off,
        "on": on,
        "scan_passes_saved": int(saved),
        "data_passes_off": off["data_passes"],
        "data_passes_on": on["data_passes"],
        "speedup": (
            round(off["wall_s"] / on["wall_s"], 3)
            if on["wall_s"]
            else 0.0
        ),
    }


def bench_service_elastic_placement(
    num_rows: int = 1_000_000, clients: int = 4
):
    """Elastic device placement (docs/SERVICE.md "Elastic placement"):
    K concurrent small suites — each on its OWN dataset key, so they
    never coalesce — run twice through otherwise-identical services.
    The ELASTIC arm uses the default policy (small footprints lease
    1-device sub-slices, so runs overlap on disjoint devices); the
    WHOLE-MESH arm pins every lease to the full pool, so runs
    serialize on the lease. Both arms replay plans warmed beforehand
    (the process-global shape-keyed plan cache), so the measured
    recompiles-after-warmup must be 0; every run's metrics must be
    bit-equal to the solo whole-mesh reference. The config needs a
    multi-device pool — the parent injects
    ``--xla_force_host_platform_device_count=8`` into the child's
    environment (CONFIG_CHILD_ENV); a 1-device pool still returns
    rc=0 with the degenerate numbers reported."""
    import threading

    import jax
    import pyarrow as pa

    from deequ_tpu import Check, CheckLevel
    from deequ_tpu.data import Dataset
    from deequ_tpu.service import (
        ElasticPlacer,
        PlacementPolicy,
        Priority,
        RunRequest,
        VerificationService,
    )
    from deequ_tpu.telemetry import get_telemetry

    pool_total = jax.device_count()

    def make():
        # one seed for every tenant: identical data, so every run's
        # metrics — elastic slice, whole-mesh slice, solo reference —
        # must be BIT-equal, whatever the placement chose
        rng = np.random.default_rng(11)
        return Dataset.from_arrow(
            pa.table(
                {
                    "k1": rng.integers(
                        0, 1 << 40, num_rows, dtype=np.int64
                    ),
                    "v1": rng.normal(0, 1, num_rows).astype(np.float32),
                    "v2": rng.normal(0, 1, num_rows).astype(np.float32),
                }
            )
        )

    def suite():
        return [
            Check(CheckLevel.ERROR, "elastic-suite")
            .is_complete("k1")
            .is_non_negative("k1")
            .is_complete("v1")
        ]

    def fingerprint(result):
        # exact metric values (repr keeps every float bit) keyed by
        # analyzer — the bit-equality pin across placements
        return tuple(
            sorted(
                (str(analyzer), repr(getattr(metric, "value", metric)))
                for analyzer, metric in dict(result.metrics).items()
            )
        )

    whole_mesh_placer = lambda: ElasticPlacer(  # noqa: E731
        policy=PlacementPolicy(
            bytes_per_device=1, default_devices=pool_total
        )
    )

    def run_phase(svc, label):
        handles = [
            svc.submit(
                RunRequest(
                    tenant=f"tenant-{i}",
                    checks=suite(),
                    dataset_key=f"bench/elastic/{label}/{i}",
                    dataset_factory=make,
                    priority=Priority.BATCH,
                )
            )
            for i in range(clients)
        ]
        t0 = time.time()
        svc.start()
        try:
            threads = [
                threading.Thread(target=h.wait, args=(600,))
                for h in handles
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            wall = time.time() - t0
        finally:
            svc.stop(drain=False, timeout=30)
        waits = sorted(
            max(0.0, (h.started_at or 0.0) - h.submitted_at)
            for h in handles
        )
        spans = [
            (
                h.started_at or 0.0,
                h.finished_at or 0.0,
                (h.placement or {}).get("ndev") or pool_total,
                tuple((h.placement or {}).get("device_ids") or ()),
            )
            for h in handles
        ]
        # peak placement concurrency: at each run start, how many runs
        # were live at once — the leases guarantee their device sets
        # are pairwise disjoint, which the artifact double-checks
        max_live, disjoint = 0, True
        for s0, _f0, _n0, _d0 in spans:
            live = [
                d
                for s, f, _n, d in spans
                if s <= s0 < f
            ]
            if len(live) > max_live:
                max_live = len(live)
                seen: set = set()
                for dev_ids in live:
                    if seen.intersection(dev_ids):
                        disjoint = False
                    seen.update(dev_ids)
        busy = sum((f - s) * n for s, f, n, _d in spans)
        return {
            "wall_s": round(wall, 3),
            "wait_p50_s": round(waits[len(waits) // 2], 4),
            "wait_p99_s": round(waits[-1], 4),
            "max_concurrent": max_live,
            "slices_disjoint": disjoint,
            "device_busy_fraction": round(
                busy / (wall * pool_total), 4
            )
            if wall
            else 0.0,
            "placements": [
                {"ndev": n, "device_ids": list(d)}
                for _s, _f, n, d in spans
            ],
        }, [h.result(timeout=0) for h in handles]

    tm = get_telemetry()

    # solo whole-mesh reference: one run on the full pool — the
    # bit-equality baseline; it also compiles the whole-mesh shape
    solo_svc = VerificationService(
        workers=1, isolated=False, coalesce=False,
        placer=whole_mesh_placer(),
    )
    _stats, solo_results = run_phase(solo_svc, "solo")
    solo_print = fingerprint(solo_results[0])

    # warm the elastic shapes (untimed): same K submissions through an
    # identical elastic service populate the process-global shape-keyed
    # plan cache, so the measured arms below replay, never compile
    warm_svc = VerificationService(
        workers=clients, isolated=False, coalesce=False,
        elastic_placement=True,
    )
    run_phase(warm_svc, "warm")

    misses_before = tm.counter("engine.plan_cache.misses").value
    elastic_svc = VerificationService(
        workers=clients, isolated=False, coalesce=False,
        elastic_placement=True,
    )
    elastic, elastic_results = run_phase(elastic_svc, "elastic")
    whole_svc = VerificationService(
        workers=clients, isolated=False, coalesce=False,
        placer=whole_mesh_placer(),
    )
    whole, whole_results = run_phase(whole_svc, "whole")
    recompiles = tm.counter("engine.plan_cache.misses").value - misses_before

    bit_equal = all(
        fingerprint(r) == solo_print
        for r in elastic_results + whole_results
    )
    return {
        "rows": num_rows,
        "clients": clients,
        "pool_devices": pool_total,
        "elastic": elastic,
        "whole_mesh": whole,
        "recompiles_after_warmup": int(recompiles),
        "metrics_bit_equal": bool(bit_equal),
        "speedup": (
            round(whole["wall_s"] / elastic["wall_s"], 3)
            if elastic["wall_s"]
            else 0.0
        ),
    }


def bench_service_preemption(num_rows: int = 1_000_000, clients: int = 4):
    """Checkpoint-conserving preemption (docs/SERVICE.md "Preemption
    and autoscaling"): K INTERACTIVE suites arrive while long BATCH
    runs saturate a 1-worker pool. With ``preemption=True`` the
    running BATCH victim is cancelled at its next batch boundary
    (final checkpoint persisted), requeued with its cursor, and
    resumed after the interactive burst — so the measured interactive
    p99 queue wait must match the idle-pool p99 (same K interactive
    submissions, no BATCH load) within 10%, work must be conserved
    (extra ``engine.data_passes`` == preemptions: one resumed
    traversal each, which recomputes at most the one in-flight batch),
    and every preempted-then-resumed BATCH result must be bit-equal to
    the uninterrupted solo reference."""
    import tempfile
    import threading
    import time as _time

    import pyarrow as pa

    from deequ_tpu import Check, CheckLevel, config
    from deequ_tpu.data import Dataset
    from deequ_tpu.service import (
        Priority,
        RunRequest,
        VerificationService,
    )
    from deequ_tpu.telemetry import get_telemetry

    def make():
        rng = np.random.default_rng(17)
        return Dataset.from_arrow(
            pa.table(
                {
                    "k1": rng.integers(
                        0, 1 << 40, num_rows, dtype=np.int64
                    ),
                    "v1": rng.normal(0, 1, num_rows).astype(np.float32),
                    "v2": rng.normal(0, 1, num_rows).astype(np.float32),
                }
            )
        )

    def batch_suite():
        return [
            Check(CheckLevel.ERROR, "preempt-batch")
            .is_complete("k1")
            .is_non_negative("k1")
            .is_complete("v1")
            .is_complete("v2")
        ]

    def interactive_suite():
        return [Check(CheckLevel.ERROR, "preempt-inter").is_complete("k1")]

    def fingerprint(result):
        return tuple(
            sorted(
                (str(analyzer), repr(getattr(metric, "value", metric)))
                for analyzer, metric in dict(result.metrics).items()
            )
        )

    def submit(svc, label, i, priority, checks):
        return svc.submit(
            RunRequest(
                tenant=f"tenant-{i}",
                checks=checks,
                dataset_key=f"bench/preempt/{label}/{priority}/{i}",
                dataset_factory=make,
                priority=priority,
            )
        )

    def wait_all(handles, timeout=600):
        threads = [
            threading.Thread(target=h.wait, args=(timeout,))
            for h in handles
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=timeout)

    def waits_of(handles):
        return sorted(
            max(0.0, (h.started_at or 0.0) - h.submitted_at)
            for h in handles
        )

    tm = get_telemetry()
    root = tempfile.mkdtemp(prefix="deequ_tpu_bench_preempt_")
    nbatch = 2
    # many small batches => preemption lands quickly at a boundary and
    # the conserved-work claim (cursor skips completed batches) is
    # about real work, not one giant batch
    overrides = dict(
        batch_size=max(4096, num_rows // 16), checkpoint_every_batches=1
    )
    try:
        with config.configure(**overrides):
            # solo uninterrupted BATCH reference: the bit-equality pin
            # (also warms the plan cache for every later arm)
            solo_svc = VerificationService(
                workers=1, isolated=False, coalesce=False,
                preemption=True, journal_dir=f"{root}/solo",
            )
            solo_svc.start()
            try:
                solo = submit(
                    solo_svc, "solo", 0, Priority.BATCH, batch_suite()
                )
                solo.wait(600)
                submit(
                    solo_svc, "solo", 0, Priority.INTERACTIVE,
                    interactive_suite(),
                ).wait(600)  # warm the interactive plan too
            finally:
                solo_svc.stop(drain=False, timeout=30)
            solo_print = fingerprint(solo.result(timeout=0))

            # idle-pool reference: the SAME K interactive submissions
            # on an identical (preemption-enabled) service with no
            # BATCH load — the p99 the saturated arm must match
            idle_svc = VerificationService(
                workers=1, isolated=False, coalesce=False,
                preemption=True, journal_dir=f"{root}/idle",
            )
            idle_svc.start()
            try:
                idle_handles = [
                    submit(
                        idle_svc, "idle", i, Priority.INTERACTIVE,
                        interactive_suite(),
                    )
                    for i in range(clients)
                ]
                wait_all(idle_handles)
            finally:
                idle_svc.stop(drain=False, timeout=30)
            idle_waits = waits_of(idle_handles)

            # saturated arm: BATCH runs own the single worker, THEN the
            # interactive burst arrives and must preempt through
            preempts0 = tm.counter("service.preemptions").value
            resumes0 = tm.counter("service.preempt_resumes").value
            conserved0 = tm.counter(
                "service.preempted_batches_conserved"
            ).value
            passes0 = tm.counter("engine.data_passes").value
            sat_svc = VerificationService(
                workers=1, isolated=False, coalesce=False,
                preemption=True, journal_dir=f"{root}/sat",
            )
            sat_svc.start()
            try:
                batch_handles = [
                    submit(
                        sat_svc, "sat", i, Priority.BATCH, batch_suite()
                    )
                    for i in range(nbatch)
                ]
                deadline = _time.time() + 60
                while (
                    not any(h.started_at for h in batch_handles)
                    and _time.time() < deadline
                ):
                    _time.sleep(0.01)
                inter_handles = [
                    submit(
                        sat_svc, "sat", i, Priority.INTERACTIVE,
                        interactive_suite(),
                    )
                    for i in range(clients)
                ]
                wait_all(inter_handles)
                wait_all(batch_handles)
            finally:
                sat_svc.stop(drain=False, timeout=30)
            sat_waits = waits_of(inter_handles)
            preemptions = int(
                tm.counter("service.preemptions").value - preempts0
            )
            resumes = int(
                tm.counter("service.preempt_resumes").value - resumes0
            )
            conserved = int(
                tm.counter("service.preempted_batches_conserved").value
                - conserved0
            )
            data_passes = int(
                tm.counter("engine.data_passes").value - passes0
            )
    finally:
        import shutil

        shutil.rmtree(root, ignore_errors=True)

    idle_p99 = idle_waits[-1]
    sat_p99 = sat_waits[-1]
    batch_results = [h.result(timeout=0) for h in batch_handles]
    bit_equal = all(
        r is not None and fingerprint(r) == solo_print
        for r in batch_results
    )
    # every preemption costs exactly one extra traversal entry (the
    # resumed pass), whose cursor skips all completed batches
    extra_passes = data_passes - (nbatch + clients)
    return {
        "rows": num_rows,
        "clients": clients,
        "idle_wait_p99_s": round(idle_p99, 4),
        "saturated_wait_p99_s": round(sat_p99, 4),
        # 10% relative plus a small absolute floor: at millisecond
        # scale a single scheduler-thread wakeup would otherwise flip
        # the verdict on noise
        "interactive_p99_within_10pct": bool(
            sat_p99 <= idle_p99 * 1.10 + 0.25
        ),
        "preemptions": preemptions,
        "preempt_resumes": resumes,
        "batches_conserved": conserved,
        "data_passes": data_passes,
        "extra_passes": extra_passes,
        "work_conserved": bool(
            0 <= extra_passes <= max(preemptions, 0)
        ),
        "preempted_results_bit_equal": bool(bit_equal),
    }


def bench_streaming_bundle_100m(num_rows: int = 100_000_000):
    """BASELINE.json config 2 at its SPECIFIED scale, streamed:
    Mean/StdDev/Min/Max/Compliance over 10 numeric f32 columns,
    100M rows read from multi-file parquet with the device cache off —
    nothing above 32M rows had ever executed before r4 (VERDICT r3
    next #2). Generated shard-by-shard so host memory stays bounded;
    the measured run re-streams every byte storage->host->device.

    The run is LINK-BOUND by construction (~40 B/row) — on a slow link
    the full 100M rows is a long stall. The config therefore probes the link first
    and sizes the row count to a ~240 s wall (capped at 100M), with
    the probe and chosen size disclosed in the output; per-row and
    projection numbers are scale-independent."""
    import shutil
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    from deequ_tpu import config
    from deequ_tpu.analyzers import (
        AnalysisRunner,
        Compliance,
        Maximum,
        Mean,
        Minimum,
        StandardDeviation,
    )
    from deequ_tpu.data import Dataset

    batch = 1 << 21
    probe_mbps = _probe_link_mb_per_sec()
    bytes_per_row = 40.3  # measured (values + packed masks)
    target_wall_s = 240.0
    affordable = int(probe_mbps * 1e6 * target_wall_s / bytes_per_row)
    if affordable < num_rows:  # probe-sized runs keep an 8M floor; an
        # explicit smaller argument is honored as-is
        num_rows = max(8_000_000, affordable)
    # whole 2^21-row batches (= the configured batch size, so no
    # padded tail inflates bytes_per_row and the projection)
    num_rows = max(batch, (num_rows // batch) * batch)

    rng = np.random.default_rng(11)
    workdir = tempfile.mkdtemp(prefix="deequ_tpu_bench_100m_")

    def shard_table(rows: int) -> "pa.Table":
        return pa.table(
            {
                f"n{j}": rng.normal(0.0, 1.0, rows).astype(np.float32)
                for j in range(10)
            }
        )

    try:
        shard_rows = 12_500_000
        gen_t0 = time.time()
        done = 0
        i = 0
        while done < num_rows:
            rows = min(shard_rows, num_rows - done)
            pq.write_table(
                shard_table(rows), f"{workdir}/part{i:02d}.parquet"
            )
            done += rows
            i += 1
        gen_s = time.time() - gen_t0

        analyzers = []
        for j in range(10):
            analyzers += [
                Mean(f"n{j}"),
                StandardDeviation(f"n{j}"),
                Minimum(f"n{j}"),
                Maximum(f"n{j}"),
            ]
        analyzers.append(Compliance("n0 pos", "n0 > 0"))

        with config.configure(device_cache_bytes=0, batch_size=batch):
            # warm the compiles on a tiny same-schema parquet (identical
            # batch shape: the tail batch pads to the same 2M width)
            warmdir = tempfile.mkdtemp(prefix="deequ_tpu_bench_100m_w_")
            try:
                pq.write_table(
                    shard_table(1 << 21), f"{warmdir}/part.parquet"
                )
                AnalysisRunner.do_analysis_run(
                    Dataset.from_parquet(warmdir), analyzers
                )
            finally:
                shutil.rmtree(warmdir, ignore_errors=True)

            wall, shipped, mbps, ctx = _timed(
                lambda: AnalysisRunner.do_analysis_run(
                    Dataset.from_parquet(workdir), analyzers
                )
            )
        bytes_per_row = shipped / num_rows if num_rows else 0.0
        out = {
            "rows": num_rows,
            "link_probe_mb_per_sec": round(probe_mbps, 2),
            "wall_s": wall,
            "rows_per_sec": num_rows / wall,
            "bytes_shipped": shipped,
            "bytes_per_row": round(bytes_per_row, 2),
            "link_mb_per_sec": mbps,
            "gen_parquet_s": gen_s,
            "phases": _phases(ctx.run_metadata),
        }
        # extrapolation to the 1B x 50-col north star, stated as math
        # on THIS config's measurements (VERDICT r3 next #2): 1B rows
        # at 5x the columns ships 5x the bytes/row; v5e-8 divides the
        # stream over 8 chips each with its own host link
        if mbps > 0:
            out["projected_1b_x50_wall_s_link_bound_8chip"] = round(
                1e9 * bytes_per_row * 5 / (mbps * 1e6) / 8, 1
            )
            out["projection_math"] = (
                f"1e9 rows * {bytes_per_row:.1f} B/row * 5 (50/10 cols)"
                f" / {mbps:.1f} MB/s / 8 chips"
            )
        return out
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def bench_rowlevel_egress(num_rows: int = 4_000_000):
    """Row-level egress config (docs/EGRESS.md): the SAME mask/predicate
    suite streamed twice — once with a RowLevelSink splitting every row
    into clean/quarantine parquet, once metrics-only — so the price of
    bytes OUT is measured differentially on identical data: wall
    overhead, outbound bytes/row (raw -> encoded), and the pass
    accounting (both arms must read the source exactly once; the split
    rides the same fused scan the metrics do)."""
    import shutil
    import tempfile

    import pyarrow as pa

    from deequ_tpu import config
    from deequ_tpu.checks import Check, CheckLevel
    from deequ_tpu.data import Dataset
    from deequ_tpu.egress import RowLevelSink
    from deequ_tpu.telemetry import get_telemetry
    from deequ_tpu.verification.suite import VerificationSuite

    rng = np.random.default_rng(23)
    amount = rng.gamma(2.0, 40.0, num_rows)
    amount[rng.random(num_rows) < 0.01] *= -1.0
    user = rng.integers(0, max(1, num_rows // 50), num_rows)
    domain = np.where(rng.random(num_rows) < 0.05, "bad addr", "ex.com")
    email = np.char.add(
        np.char.add("u", user.astype("U12")), np.char.add("@", domain)
    ).astype(object)
    email[rng.random(num_rows) < 0.02] = None
    data = Dataset.from_arrow(
        pa.table(
            {
                "event_id": pa.array(np.arange(num_rows, dtype=np.int64)),
                "amount": pa.array(amount),
                "email": pa.array(email, type=pa.string()),
            }
        )
    )
    checks = [
        Check(CheckLevel.ERROR, "hygiene")
        .is_complete("email")
        .has_pattern("email", r"@ex\.com$")
        .satisfies("amount >= 0", "amount_non_negative")
    ]
    tm = get_telemetry()
    workdirs = []

    def run(egress_on: bool):
        def once():
            sink = None
            if egress_on:
                out_dir = tempfile.mkdtemp(prefix="deequ_tpu_bench_eg_")
                workdirs.append(out_dir)
                sink = RowLevelSink(out_dir)
            return VerificationSuite.do_verification_run(
                data, checks, row_level_sink=sink
            )

        with config.configure(device_cache_bytes=0):
            once()  # warm the plan; priced runs below are steady-state
            raw0 = tm.counter("engine.egress_bytes_raw").value
            enc0 = tm.counter("engine.egress_bytes_encoded").value
            passes0 = tm.counter("engine.data_passes").value
            wall, _shipped, _mbps, result = _timed(once)
        out = {
            "wall_s": wall,
            "rows_per_sec": num_rows / wall,
            "data_passes": (
                tm.counter("engine.data_passes").value - passes0
            ),
            "egress_raw_bytes_per_row": (
                tm.counter("engine.egress_bytes_raw").value - raw0
            ) / num_rows,
            "egress_encoded_bytes_per_row": (
                tm.counter("engine.egress_bytes_encoded").value - enc0
            ) / num_rows,
        }
        if egress_on:
            report = result.row_level_egress
            out["egress_status"] = report.status
            out["rows_clean"] = report.rows_clean
            out["rows_quarantined"] = report.rows_quarantined
        return out

    try:
        on = run(True)
        off = run(False)
        return {
            "rows": num_rows,
            "egress_on": on,
            "egress_off": off,
            "wall_overhead": (
                on["wall_s"] / off["wall_s"] if off["wall_s"] > 0 else 0.0
            ),
        }
    finally:
        for d in workdirs:
            shutil.rmtree(d, ignore_errors=True)


def bench_egress_resume(num_rows: int = 800_000):
    """Exactly-once egress resume (docs/EGRESS.md "Durable egress"):
    the quarantine suite streamed uninterrupted, then the SAME suite
    killed at its halfway batch and resumed from the durable span
    cursor. The exactly-once claims are priced and pinned in one
    config: the killed+resumed pair must finish within 10% of the
    uninterrupted wall (the resume's cursor skips every durably
    flushed span — only the open span is recomputed),
    ``engine.egress_rows_replayed`` must stay 0, and the published
    clean/quarantine split must be BYTE-equal to the uninterrupted
    artifact."""
    import shutil
    import tempfile

    import pyarrow as pa

    from deequ_tpu import config
    from deequ_tpu.checks import Check, CheckLevel
    from deequ_tpu.data import Dataset
    from deequ_tpu.egress import RowLevelSink
    from deequ_tpu.engine.resilience import ScanKilled
    from deequ_tpu.engine.scan import AnalysisEngine
    from deequ_tpu.io.state_provider import ScanCheckpointer
    from deequ_tpu.telemetry import get_telemetry
    from deequ_tpu.testing.faults import FaultInjectingDataset
    from deequ_tpu.verification.suite import VerificationSuite

    rng = np.random.default_rng(23)
    amount = rng.gamma(2.0, 40.0, num_rows)
    amount[rng.random(num_rows) < 0.01] *= -1.0
    user = rng.integers(0, max(1, num_rows // 50), num_rows)
    domain = np.where(rng.random(num_rows) < 0.05, "bad addr", "ex.com")
    email = np.char.add(
        np.char.add("u", user.astype("U12")), np.char.add("@", domain)
    ).astype(object)
    email[rng.random(num_rows) < 0.02] = None
    data = Dataset.from_arrow(
        pa.table(
            {
                "event_id": pa.array(np.arange(num_rows, dtype=np.int64)),
                "amount": pa.array(amount),
                "email": pa.array(email, type=pa.string()),
            }
        )
    )
    checks = [
        Check(CheckLevel.ERROR, "hygiene")
        .is_complete("email")
        .has_pattern("email", r"@ex\.com$")
        .satisfies("amount >= 0", "amount_non_negative")
    ]
    batch_size = max(4096, num_rows // 64)
    nbatches = (num_rows + batch_size - 1) // batch_size
    kill_at = nbatches // 2
    tm = get_telemetry()
    root = tempfile.mkdtemp(prefix="deequ_tpu_bench_egresume_")

    def run(arm, ds):
        sink = RowLevelSink(os.path.join(root, arm, "out"))
        engine = AnalysisEngine(
            checkpointer=ScanCheckpointer(os.path.join(root, arm, "ckpt"))
        )
        return VerificationSuite.do_verification_run(
            ds, checks, engine=engine, row_level_sink=sink
        )

    def split_bytes(arm):
        out = {}
        for split in ("clean", "quarantine"):
            path = os.path.join(
                root, arm, "out", split, "part-00000.parquet"
            )
            with open(path, "rb") as fh:
                out[split] = fh.read()
        return out

    try:
        with config.configure(
            device_cache_bytes=0,
            batch_size=batch_size,
            checkpoint_every_batches=4,
        ):
            run("warm", data)  # priced arms below are steady-state
            wall_solo, _, _, solo_result = _timed(lambda: run("solo", data))

            killed_ds = FaultInjectingDataset(data, kill_at_batch=kill_at)
            replayed0 = tm.counter("engine.egress_rows_replayed").value
            resumes0 = tm.counter("engine.resumes").value

            def killed_then_resumed():
                try:
                    run("killed", killed_ds)
                    raise RuntimeError("injected kill never fired")
                except ScanKilled:
                    pass
                # same artifact dir + checkpoint path: the relaunch
                # shape, minus the process spawn (priced elsewhere)
                return run("killed", killed_ds)

            wall_killed, _, _, resumed_result = _timed(killed_then_resumed)
        rows_replayed = int(
            tm.counter("engine.egress_rows_replayed").value - replayed0
        )
        resumes = int(tm.counter("engine.resumes").value - resumes0)
        solo_report = solo_result.row_level_egress
        report = resumed_result.row_level_egress
        byte_equal = split_bytes("solo") == split_bytes("killed")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    added = (
        (wall_killed - wall_solo) / wall_solo if wall_solo > 0 else 0.0
    )
    return {
        "rows": num_rows,
        "batches": nbatches,
        "kill_at_batch": kill_at,
        "wall_uninterrupted_s": round(wall_solo, 3),
        "wall_killed_plus_resume_s": round(wall_killed, 3),
        "added_wall_pct": round(added * 100.0, 2),
        # 10% relative plus a small absolute floor (same rationale as
        # service_preemption: sub-second walls flip on scheduler noise)
        "resume_within_10pct": bool(
            wall_killed <= wall_solo * 1.10 + 0.25
        ),
        "resumes": resumes,
        "rows_replayed": rows_replayed,
        "egress_status": report.status,
        "rows_clean": report.rows_clean,
        "rows_quarantined": report.rows_quarantined,
        "counters_conserved": bool(
            report.rows_clean == solo_report.rows_clean
            and report.rows_quarantined == solo_report.rows_quarantined
            and report.rows_clean + report.rows_quarantined == num_rows
        ),
        "split_byte_equal": bool(byte_equal),
    }


_FLEET_VICTIM_SRC = r"""
import signal, sys
fleet_dir, journal_dir = sys.argv[1], sys.argv[2]
rows, n_runs = int(sys.argv[3]), int(sys.argv[4])
heartbeat_s, lease_timeout_s = float(sys.argv[5]), float(sys.argv[6])
import numpy as np
from deequ_tpu import config
from deequ_tpu.checks import Check, CheckLevel
from deequ_tpu.data import Dataset
from deequ_tpu.service import Priority, RunRequest, VerificationService

rng = np.random.default_rng(11)
data = {
    "a": rng.normal(size=rows).tolist(),
    "g": (np.arange(rows) % 7).tolist(),
}
checks = [
    Check(CheckLevel.ERROR, "fleet-bench")
    .has_size(lambda s: s == rows)
    .is_complete("a")
]
with config.configure(
    checkpoint_every_batches=4,
    batch_size=max(4096, rows // 32),
    device_cache_bytes=0,
    service_fleet_heartbeat_s=heartbeat_s,
    service_fleet_lease_timeout_s=lease_timeout_s,
):
    svc = VerificationService(
        workers=1, isolated=False, journal_dir=journal_dir,
        fleet_dir=fleet_dir, replica_id="bench-victim",
    ).start()
    handles = [
        svc.submit(RunRequest(
            tenant="bench", checks=checks,
            dataset_key=f"bench-fleet-{i}",
            dataset_factory=lambda: Dataset.from_pydict(data),
            priority=Priority.STANDARD,
        ))
        for i in range(n_runs)
    ]
    for i, h in enumerate(handles):
        h.wait(timeout=600)
        print(f"DONE {i}", flush=True)  # the parent's SIGKILL trigger
    svc.stop()
print("ALL", flush=True)
"""


def bench_fleet_failover(num_rows: int = 400_000, n_runs: int = 4):
    """Fleet failover under a REAL replica kill (docs/SERVICE.md "Fleet
    failover"): a whole replica process — service, fleet supervisor,
    heartbeat thread, a queue of journaled runs — is SIGKILLed from
    outside at 50% queue progress. A survivor replica in this process
    shares the fleet dir, sees the lease go stale, wins the adoption
    CAS, and replays the orphan's pending runs; the mid-flight run
    resumes from the shared durable checkpoint cursor. Priced and
    pinned: time-to-adoption (~one lease timeout), ``runs_lost`` and
    ``runs_double_persisted`` both 0, and the adopted backlog finishing
    within 10% of uninterrupted cost."""
    import shutil
    import signal as _signal
    import subprocess
    import tempfile

    from deequ_tpu import config
    from deequ_tpu.checks import Check, CheckLevel
    from deequ_tpu.data import Dataset
    from deequ_tpu.service import RunRequest, RunState, VerificationService
    from deequ_tpu.service.journal import RunJournal
    from deequ_tpu.verification.suite import VerificationSuite

    heartbeat_s, lease_timeout_s = 0.3, 1.2
    kill_after_done = n_runs // 2 - 1  # mid-queue: run n_runs//2 in flight
    root = tempfile.mkdtemp(prefix="deequ_tpu_bench_fleet_")
    fleet_dir = os.path.join(root, "fleet")
    victim_journal = os.path.join(root, "victim-journal")
    survivor_journal = os.path.join(root, "survivor-journal")

    rng = np.random.default_rng(11)  # the victim builds the SAME table
    data = {
        "a": rng.normal(size=num_rows).tolist(),
        "g": (np.arange(num_rows) % 7).tolist(),
    }
    checks = [
        Check(CheckLevel.ERROR, "fleet-bench")
        .has_size(lambda s: s == num_rows)
        .is_complete("a")
    ]
    scan_opts = dict(
        checkpoint_every_batches=4,
        batch_size=max(4096, num_rows // 32),
        device_cache_bytes=0,
        service_fleet_heartbeat_s=heartbeat_s,
        service_fleet_lease_timeout_s=lease_timeout_s,
    )
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = (
        repo_root + os.pathsep + env.get("PYTHONPATH", "")
    ).rstrip(os.pathsep)

    try:
        with config.configure(**scan_opts):
            # oracle: one uninterrupted run of the same suite, warmed —
            # the unit the adopted backlog's wall is priced against
            ds = Dataset.from_pydict(data)
            VerificationSuite.do_verification_run(ds, checks)
            wall_solo, _, _, oracle = _timed(
                lambda: VerificationSuite.do_verification_run(ds, checks)
            )

            proc = subprocess.Popen(
                [
                    sys.executable, "-c", _FLEET_VICTIM_SRC,
                    fleet_dir, victim_journal,
                    str(num_rows), str(n_runs),
                    str(heartbeat_s), str(lease_timeout_s),
                ],
                stdout=subprocess.PIPE,
                text=True,
                env=env,
            )
            killed = False
            try:
                for line in proc.stdout:
                    if line.strip() == f"DONE {kill_after_done}":
                        os.kill(proc.pid, _signal.SIGKILL)
                        killed = True
                        break
            finally:
                if not killed and proc.poll() is None:
                    proc.kill()
                proc.wait()
                proc.stdout.close()
            t_kill = time.monotonic()

            victim_records = RunJournal(victim_journal).replay()
            done_before = {
                r["run_id"]
                for r in victim_records
                if r.get("type") == "terminal"
                and r.get("state") == RunState.DONE
            }
            pending_before = RunJournal(victim_journal).pending_runs()

            svc = VerificationService(
                workers=1, isolated=False,
                journal_dir=survivor_journal,
                fleet_dir=fleet_dir,
                replica_id="bench-survivor",
                adopt_resolve=lambda entry: RunRequest(
                    tenant=entry["tenant"],
                    checks=checks,
                    dataset_key=entry.get("dataset_key"),
                    dataset_factory=lambda: Dataset.from_pydict(data),
                ),
            )
            adoptions = []
            adopt_deadline = time.monotonic() + 30.0
            while not adoptions and time.monotonic() < adopt_deadline:
                adoptions = svc.fleet.poll()
                if not adoptions:
                    time.sleep(0.05)
            time_to_adoption = time.monotonic() - t_kill
            adopted = svc.adopted_runs()

            svc.start()
            try:
                t0 = time.monotonic()
                for h in adopted:
                    h.wait(timeout=300)
                wall_adopted = time.monotonic() - t0
                adopted_done = sum(
                    1 for h in adopted if h.status == RunState.DONE
                )
                results_match = all(
                    sorted(
                        (str(a), m.value.get())
                        for a, m in h.result(timeout=0).metrics.items()
                    )
                    == sorted(
                        (str(a), m.value.get())
                        for a, m in oracle.metrics.items()
                    )
                    for h in adopted
                    if h.status == RunState.DONE
                )
            finally:
                svc.stop(drain=False, timeout=10)

            survivor_records = RunJournal(survivor_journal).replay()
            adopted_from = [
                r["adopted_from"]
                for r in survivor_records
                if r.get("type") == "submitted" and r.get("adopted_from")
            ]
        runs_lost = n_runs - len(done_before) - adopted_done
        runs_double_persisted = len(
            set(adopted_from) & done_before
        ) + (len(adopted_from) - len(set(adopted_from)))
    finally:
        shutil.rmtree(root, ignore_errors=True)

    backlog = max(1, len(adopted))
    return {
        "rows": num_rows,
        "runs": n_runs,
        "heartbeat_s": heartbeat_s,
        "lease_timeout_s": lease_timeout_s,
        "victim_killed": bool(killed),
        "runs_done_before_kill": len(done_before),
        "runs_pending_at_kill": len(pending_before),
        "runs_adopted": len(adopted),
        "runs_adopted_done": adopted_done,
        "runs_lost": int(runs_lost),
        "runs_double_persisted": int(runs_double_persisted),
        "time_to_adoption_s": round(time_to_adoption, 3),
        "adoption_within_3x_timeout": bool(
            time_to_adoption <= lease_timeout_s * 3 + 2.0
        ),
        "lease_stale_for_s": (
            round(adoptions[0].stale_for_s, 3) if adoptions else None
        ),
        "wall_uninterrupted_per_run_s": round(wall_solo, 3),
        "wall_adopted_backlog_s": round(wall_adopted, 3),
        # the resumed run skips its checkpointed prefix, so the backlog
        # must land within the uninterrupted cost of the same runs (10%
        # relative + absolute floor, as service_preemption/egress_resume)
        "adopted_within_10pct": bool(
            wall_adopted <= wall_solo * backlog * 1.10 + 0.25
        ),
        "results_match_oracle": bool(results_match),
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--budget",
        type=float,
        default=float(os.environ.get("DEEQU_TPU_BENCH_BUDGET_S", "1200")),
        help="overall wall budget in seconds; secondary configs are "
        "skipped once the remainder can't cover their estimated cost "
        "(default: $DEEQU_TPU_BENCH_BUDGET_S or 1200)",
    )
    parser.add_argument(
        "--quick",
        action="store_true",
        help="headline profiler config only, at 1/8 scale",
    )
    parser.add_argument(
        "--configs",
        default="",
        help="comma-separated config names to run (e.g. "
        "'streaming_ingest_parallel'); skips the headline profiler "
        "unless 'profiler' is listed",
    )
    parser.add_argument(
        "--artifact",
        default="",
        help="also write the full detail JSON (the stderr document) "
        "to this path",
    )
    parser.add_argument(
        "--inline",
        action="store_true",
        default=os.environ.get("DEEQU_TPU_BENCH_INLINE", "0") == "1",
        help="run configs in-process instead of subprocess-per-config "
        "(debugging only: one SIGSEGV then kills the whole bench); "
        "also $DEEQU_TPU_BENCH_INLINE=1",
    )
    args = parser.parse_args(argv)
    wanted = {
        name.strip() for name in args.configs.split(",") if name.strip()
    }

    start = time.time()

    def remaining() -> float:
        return args.budget - (time.time() - start)

    # what can THIS host sustain? probe first, size everything from it
    host = probe_host()
    sizing = autosize(host)
    scale = sizing["row_scale"]
    print(
        f"[bench] host: {host.get('cpu_count')} cores, "
        f"{host.get('mem_available_mb')} MB available; "
        f"row scale {scale}"
        + (
            f", streamed rows capped at {sizing['streaming_row_cap']}"
            if sizing["streaming_row_cap"]
            else ""
        ),
        file=sys.stderr,
        flush=True,
    )

    # scaled to one chip: 4M rows x 20 cols for the headline profiler
    # run at scale 1.0, auto-sized down on small hosts
    prof_rows = _sized(500_000 if args.quick else 4_000_000, sizing)
    prof_cols = 20
    detail = {
        "budget_s": args.budget,
        "quick": args.quick,
        "isolated": not args.inline,
        "host": host,
        "sizing": sizing,
        "skipped": [],
        "config_status": {},
    }

    def run_one(name: str, cfg_args: dict, est_s: float) -> dict:
        """ONE config through a spawn-started child (crash isolation:
        a config that segfaults or stalls becomes a status entry, not
        the end of the bench). Fills detail[name] on success and
        detail["config_status"][name] always. A child CRASH (killed by
        a signal — usually the OOM killer on a small host) steps the
        config's row count down by halving and retries, so EVERY
        config yields a number somewhere on any host; the step-down
        trail rides the status entry (``row_step_downs``,
        ``rows_effective``)."""
        cfg_args = dict(cfg_args)
        status = {"rows": cfg_args.get("rows"), "estimated_s": est_s}
        t0 = time.time()
        step_downs: list = []
        while True:
            payload = {"name": name, "args": dict(cfg_args)}
            restore_env = _apply_child_env(name)
            try:
                if args.inline:
                    detail[name] = _bench_child(payload)
                else:
                    from deequ_tpu.engine.subproc import IsolatedRunner

                    runner = IsolatedRunner(
                        key=f"bench:{name}",
                        # bench configs are not checkpointer-resumable,
                        # so one crash = one failed attempt, no relaunch
                        max_relaunches=1,
                        use_breaker=False,
                        timeout_s=max(120.0, min(remaining(), est_s * 3.0)),
                    )
                    detail[name] = runner.run(_bench_child, payload)
                status["status"] = "ok"
                # a success after step-downs is a success — the trail
                # below documents the crashes that led here
                for key in ("error", "signal", "exitcode"):
                    status.pop(key, None)
                break
            except BaseException as exc:  # noqa: BLE001 — a status, never a crash
                sig = getattr(exc, "last_signal", None) or getattr(
                    exc, "signal_name", None
                )
                rc = getattr(exc, "last_exitcode", None)
                if rc is None:
                    rc = getattr(exc, "exitcode", None)
                if sig == "timeout":
                    status["status"] = "timeout"
                elif sig is not None or rc is not None:
                    status["status"] = "crashed"
                else:
                    status["status"] = "error"
                status["error"] = repr(exc)
                if sig is not None:
                    status["signal"] = sig
                if rc is not None:
                    status["exitcode"] = rc
                rows = cfg_args.get("rows")
                if (
                    status["status"] == "crashed"
                    and isinstance(rows, int)
                    and rows // 2 >= 100_000
                    and len(step_downs) < 3
                ):
                    cfg_args["rows"] = rows // 2
                    step_downs.append(cfg_args["rows"])
                    print(
                        f"[bench] {name} crashed at {rows} rows "
                        f"({sig or rc}); stepping down to "
                        f"{cfg_args['rows']}",
                        file=sys.stderr,
                        flush=True,
                    )
                    continue
                detail.setdefault("errors", {})[name] = repr(exc)
                break
            finally:
                restore_env()
        if step_downs:
            status["row_step_downs"] = step_downs
            status["rows_effective"] = cfg_args.get("rows")
        status["wall_s"] = round(time.time() - t0, 1)
        detail["config_status"][name] = status
        detail.setdefault("config_walls", {})[name] = status["wall_s"]
        return status

    if not wanted or "profiler" in wanted:
        st = run_one(
            "profiler", {"rows": prof_rows, "cols": prof_cols}, 300
        )
        if st["status"] != "ok":
            detail["error"] = st.get("error", "headline config failed")

    def headline_line() -> dict:
        prof = detail.get("profiler")
        if isinstance(prof, dict):
            rows_per_sec = prof["rows_per_sec"]
            return {
                "metric": "rows/sec/chip, full ColumnProfiler "
                f"({prof_rows}x{prof_cols} scaled TPC-DS-like)",
                "value": round(rows_per_sec, 1),
                "unit": "rows/sec/chip",
                "vs_baseline": round(
                    rows_per_sec / NORTH_STAR_ROWS_PER_SEC_PER_CHIP, 4
                ),
                # decomposition context: fresh-data walls include the
                # host->device transfer;
                # resident_rows_per_sec is the chip's compute/dispatch
                # capability with data in HBM (what a real pod reading
                # from local storage at GB/s would see)
                "link_mb_per_sec": round(prof["link_mb_per_sec"], 2),
                "resident_rows_per_sec": round(
                    prof["resident_rows_per_sec"], 1
                ),
            }
        return {  # headline config failed: the line still prints
            "metric": "rows/sec/chip, full ColumnProfiler "
            f"({prof_rows}x{prof_cols} scaled TPC-DS-like)",
            "value": 0.0,
            "unit": "rows/sec/chip",
            "vs_baseline": 0.0,
            "error": detail.get("error", "headline config failed"),
        }

    # print (and FLUSH) the headline line the moment it exists: if the
    # harness kills the process mid-secondary (rc=124), stdout still
    # carries a parseable result — the enriched final line below
    # supersedes it when the run finishes
    print(json.dumps({**headline_line(), "preliminary": True}), flush=True)
    print(
        f"[bench] headline done at {time.time() - start:.1f}s, "
        f"{remaining():.0f}s of budget left",
        file=sys.stderr,
        flush=True,
    )

    # (name, base args, streamed?, estimated cost in seconds at scale
    # 1.0) — the estimate is the gate: a config only starts when the
    # remaining budget covers it, so the overall wall stays under
    # --budget instead of rc=124-ing the harness. Rows are
    # auto-sized per host before launch; streamed configs additionally
    # respect the streaming row cap.
    # ORDER MATTERS (r6): the two wide-profiler configs run FIRST so
    # the cell-rate headline fields (ns_per_cell_50col,
    # projected_1b_x50_resident_8chip_s) exist even when the harness
    # rc=124-kills the process partway through the slower tail configs
    # — 4M x 50 is the round-over-round cell-rate headline, 8M x 50 is
    # the scaling check the <60 s north-star verdict reads (both on the
    # table as it is since PR 21; see _tpcds_faithful)
    secondary = (
        []
        if args.quick
        else [
            ("profiler_50col", {"rows": 4_000_000}, False, 150),
            ("profiler_50col_8m", {"rows": 8_000_000}, False, 200),
            ("fused_bundle_10col", {"rows": 8_000_000}, False, 60),
            ("grouping_5cat", {"rows": 4_000_000}, False, 60),
            ("one_pass_spill_grouping", {"rows": 4_000_000}, False, 100),
            ("sketches_hll_kll", {"rows": 8_000_000}, False, 60),
            ("resilience_overhead", {"rows": 4_000_000}, False, 90),
            ("memory_backoff_overhead", {"rows": 4_000_000}, False, 90),
            ("watchdog_overhead", {"rows": 4_000_000}, False, 90),
            (
                "service_concurrent_suites",
                {"rows": 2_000_000, "clients": 8},
                False,
                90,
            ),
            (
                "service_coalesced_suites",
                {"rows": 2_000_000, "clients": 4},
                False,
                120,
            ),
            (
                "service_elastic_placement",
                {"rows": 1_000_000, "clients": 4},
                False,
                120,
            ),
            (
                "service_preemption",
                {"rows": 1_000_000, "clients": 4},
                False,
                150,
            ),
            ("spill_grouping_12M_distinct", {"rows": 12_000_000}, False, 120),
            (
                "joint_grouping_mi_1Mcard_pair",
                {"rows": 4_000_000},
                False,
                120,
            ),
            # streaming ests = worst observed link, not the median —
            # gating on the median overruns the budget
            ("streaming_parquet", {"rows": 4_000_000, "cols": 10}, True, 390),
            ("streaming_wire_diet", {"rows": 4_000_000}, True, 390),
            (
                "streaming_ingest_parallel",
                {"rows": 4_000_000, "cols": 10},
                True,
                400,
            ),
            ("streaming_bundle_100m", {"rows": 100_000_000}, True, 330),
            ("rowlevel_egress", {"rows": 4_000_000}, True, 200),
            ("egress_resume", {"rows": 800_000}, True, 150),
            ("fleet_failover", {"rows": 400_000}, False, 150),
        ]
    )

    def merge_wide(result: dict) -> dict:
        # the 50-col cell-rate headline (VERDICT r4) plus the r6 8M
        # scaling check: resident rate on the north-star-shaped config
        # and its link-independent projection — the one number to
        # compare round over round regardless of what the link did
        # during the run. The 8M x 50 run supersedes 4M x 50 for
        # the projection (amortizes per-step overhead the way a 1B run
        # would); 4M x 50 remains the comparable-cell-rate field.
        wide = detail.get("profiler_50col")
        if isinstance(wide, dict) and "resident_rows_per_sec" in wide:
            result["resident_rows_per_sec_50col"] = round(
                wide["resident_rows_per_sec"], 1
            )
            result["ns_per_cell_50col"] = round(wide["ns_per_cell"], 2)
            result["projected_1b_x50_resident_8chip_s"] = round(
                wide["projected_1b_x50_resident_8chip_s"], 1
            )
        wide8 = detail.get("profiler_50col_8m")
        if isinstance(wide8, dict) and "resident_rows_per_sec" in wide8:
            result["ns_per_cell_50col_8m"] = round(
                wide8["ns_per_cell"], 2
            )
            result["projected_1b_x50_resident_8chip_s"] = round(
                wide8["projected_1b_x50_resident_8chip_s"], 1
            )
        return result

    try:
        for name, base_args, streamed, est_s in secondary:
            if wanted and name not in wanted:
                continue
            # a scaled-down config finishes faster; the +20s covers the
            # child's own import+compile on top of the scaled run
            est_eff = (
                est_s
                if scale >= 1.0
                else max(45, int(est_s * scale) + 20)
            )
            if remaining() < est_eff:
                detail["skipped"].append(
                    {
                        "config": name,
                        "estimated_s": est_eff,
                        "remaining_s": round(remaining(), 1),
                    }
                )
                detail["config_status"][name] = {
                    "status": "skipped",
                    "estimated_s": est_eff,
                    "remaining_s": round(remaining(), 1),
                }
                print(
                    f"[bench] SKIPPED {name} (est {est_eff}s > "
                    f"{remaining():.0f}s remaining)",
                    file=sys.stderr,
                    flush=True,
                )
                continue
            cfg = dict(base_args)
            cfg["rows"] = _sized(base_args["rows"], sizing, streamed)
            print(
                f"[bench] running {name} ({cfg['rows']} rows)...",
                file=sys.stderr,
                flush=True,
            )
            st = run_one(name, cfg, est_eff)
            print(
                f"[bench] {name}: {st['status']} in {st['wall_s']}s "
                f"({remaining():.0f}s of budget left)",
                file=sys.stderr,
                flush=True,
            )
            if name in ("profiler_50col", "profiler_50col_8m"):
                # re-emit the preliminary line the moment a wide config
                # lands: the cell-rate/projection fields survive an
                # rc=124 kill during the remaining (slower) tail configs
                print(
                    json.dumps(
                        {**merge_wide(headline_line()), "preliminary": True}
                    ),
                    flush=True,
                )
    finally:
        # the artifact and the headline line ALWAYS emit, complete with
        # per-config status, whatever the configs did — partial results
        # with provenance beat a dead harness (the exit code still
        # reports a failed headline)
        from deequ_tpu.telemetry import get_telemetry

        # the process-wide telemetry picture of everything the bench
        # ran: counter totals + the pass-latency histogram
        # (docs/OBSERVABILITY.md); children's counters/events were
        # merged in by IsolatedRunner as each config completed
        try:
            detail["telemetry"] = get_telemetry().metrics.snapshot()
        except Exception as exc:  # noqa: BLE001
            detail["telemetry_error"] = repr(exc)
        detail["total_wall_s"] = round(time.time() - start, 1)

        result = merge_wide(headline_line())
        print(json.dumps(detail, indent=2, default=str), file=sys.stderr)
        if args.artifact:
            try:
                with open(args.artifact, "w", encoding="utf-8") as fh:
                    json.dump(detail, fh, indent=2, default=str)
                    fh.write("\n")
            except OSError as exc:
                print(
                    f"[bench] artifact write failed: {exc!r}",
                    file=sys.stderr,
                    flush=True,
                )
        print(json.dumps(result, default=str))
    headline = detail["config_status"].get("profiler", {})
    return 1 if headline.get("status") not in (None, "ok") else 0


if __name__ == "__main__":
    sys.exit(main())
