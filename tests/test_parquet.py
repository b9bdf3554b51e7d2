"""Streaming parquet ingest: multi-file sources feed the fused scan
batch-by-batch with bounded host memory and results identical to the
in-memory path (SURVEY.md §7 stage 0)."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from deequ_tpu import (
    ApproxCountDistinct,
    Completeness,
    Compliance,
    Dataset,
    Histogram,
    Maximum,
    Mean,
    Minimum,
    PatternMatch,
    Size,
    StandardDeviation,
    Sum,
    config,
)
from deequ_tpu.analyzers import AnalysisRunner
from deequ_tpu.engine import AnalysisEngine


@pytest.fixture(scope="module")
def parquet_dir(tmp_path_factory):
    """Three parquet files with numeric, nullable, and string columns."""
    directory = tmp_path_factory.mktemp("pq")
    rng = np.random.default_rng(5)
    tables = []
    for i in range(3):
        n = 1000 + i * 500
        x = rng.normal(10.0, 2.0, n)
        x_arr = pa.array(x, pa.float64(), mask=(rng.random(n) < 0.1))
        tables.append(
            pa.table(
                {
                    "x": x_arr,
                    "k": pa.array(rng.integers(0, 1 << 40, n)),
                    "s": pa.array(
                        rng.choice(["red", "green", "blue", "mail@x.io"], n)
                    ),
                }
            )
        )
        pq.write_table(tables[-1], os.path.join(directory, f"part-{i}.parquet"))
    full = pa.concat_tables(tables)
    return str(directory), full


ANALYZERS = [
    Size(),
    Completeness("x"),
    Mean("x"),
    Sum("x"),
    Minimum("x"),
    Maximum("x"),
    StandardDeviation("x"),
    Compliance("big x", "x > 10"),
    ApproxCountDistinct("k"),
    PatternMatch("s", r"@"),
    Histogram("s"),
]


def metrics_of(ctx):
    out = {}
    for a in ANALYZERS:
        m = ctx.metric(a)
        if m.value.is_success and not hasattr(m.value.get(), "values"):
            out[repr(a)] = m.value.get()
    return out


class TestParquetStreaming:
    def test_matches_in_memory_results(self, parquet_dir):
        directory, full = parquet_dir
        streamed = Dataset.from_parquet(directory)
        in_memory = Dataset.from_arrow(full)
        assert streamed.num_rows == full.num_rows
        ctx_stream = AnalysisRunner.do_analysis_run(streamed, ANALYZERS)
        ctx_memory = AnalysisRunner.do_analysis_run(in_memory, ANALYZERS)
        want, got = metrics_of(ctx_memory), metrics_of(ctx_stream)
        assert set(want) == set(got)
        for k in want:
            assert got[k] == pytest.approx(want[k], rel=1e-9), k
        # histogram too (string global dictionary must be stable)
        h_stream = ctx_stream.metric(Histogram("s")).value.get()
        h_memory = ctx_memory.metric(Histogram("s")).value.get()
        assert {k: v.absolute for k, v in h_stream.values.items()} == {
            k: v.absolute for k, v in h_memory.values.items()
        }

    def test_streaming_path_never_materializes_columns(self, parquet_dir):
        """With the device cache disabled, the engine must stream: no
        full-column host materialization happens."""
        directory, _ = parquet_dir
        streamed = Dataset.from_parquet(directory, read_batch_rows=512)
        with config.configure(device_cache_bytes=0):
            engine = AnalysisEngine(batch_size=700)
            ctx = AnalysisRunner.do_analysis_run(
                streamed, [Mean("x"), Size()], engine=engine
            )
        assert ctx.metric(Size()).value.get() == streamed.num_rows
        # materialize() caches full columns; the streaming path bypasses it
        assert not streamed._materialized
        assert engine.trace_count == 1 or engine.plan_cache_hit

    def test_small_read_batches_rechunk_correctly(self, parquet_dir):
        directory, full = parquet_dir
        streamed = Dataset.from_parquet(directory, read_batch_rows=333)
        with config.configure(device_cache_bytes=0):
            engine = AnalysisEngine(batch_size=1000)
            ctx = AnalysisRunner.do_analysis_run(
                streamed, [Size(), Sum("x")], engine=engine
            )
        in_memory = Dataset.from_arrow(full)
        want = AnalysisRunner.do_analysis_run(in_memory, [Sum("x")])
        assert ctx.metric(Sum("x")).value.get() == pytest.approx(
            want.metric(Sum("x")).value.get(), rel=1e-9
        )

    def test_resident_path_also_works(self, parquet_dir):
        """Under the budget, the resident fast path materializes from
        parquet and still matches."""
        directory, full = parquet_dir
        streamed = Dataset.from_parquet(directory)
        ctx = AnalysisRunner.do_analysis_run(streamed, [Mean("x")])
        want = AnalysisRunner.do_analysis_run(
            Dataset.from_arrow(full), [Mean("x")]
        )
        assert ctx.metric(Mean("x")).value.get() == pytest.approx(
            want.metric(Mean("x")).value.get(), rel=1e-9
        )

    def test_single_file_and_metadata(self, parquet_dir):
        directory, full = parquet_dir
        one = Dataset.from_parquet(os.path.join(directory, "part-0.parquet"))
        assert one.num_rows == 1000
        assert one.num_columns == 3
        assert one.schema.kind_of("x").is_numeric

    def test_streaming_plan_cache_reuse(self, parquet_dir):
        """A second streamed run of the SAME plan reuses the cached
        jitted update: no Python retrace (r4: the streaming path joined
        the plan cache; before, every profile retraced ~100 analyzers)."""
        directory, _ = parquet_dir
        plan = [Size(), Mean("x"), Completeness("x")]
        with config.configure(device_cache_bytes=0):
            first = AnalysisEngine(batch_size=1000)
            AnalysisRunner.do_analysis_run(
                Dataset.from_parquet(directory), plan, engine=first
            )
            second = AnalysisEngine(batch_size=1000)
            ctx = AnalysisRunner.do_analysis_run(
                Dataset.from_parquet(directory), plan, engine=second
            )
        assert second.plan_cache_hit
        assert second.trace_count == 0
        assert ctx.metric(Size()).value.is_success

    def test_streaming_phase_decomposition_recorded(self, parquet_dir):
        """Every scan records its wall decomposition (host_wait / put /
        dispatch / sync) as a scan_phases event (VERDICT r3 next #2)."""
        directory, _ = parquet_dir
        with config.configure(device_cache_bytes=0):
            engine = AnalysisEngine(batch_size=1000)
            ctx = AnalysisRunner.do_analysis_run(
                Dataset.from_parquet(directory), [Mean("x")], engine=engine
            )
        events = [
            e
            for e in ctx.run_metadata.events
            if e.get("event") == "scan_phases"
        ]
        assert len(events) == 1
        phases = events[0]
        assert phases["mode"] == "streaming"
        for key in ("host_wait_s", "put_s", "dispatch_s", "sync_s"):
            assert phases[key] >= 0.0
        # resident runs record the same decomposition
        ctx2 = AnalysisRunner.do_analysis_run(
            Dataset.from_parquet(directory), [Mean("x")]
        )
        modes = [
            e["mode"]
            for e in ctx2.run_metadata.events
            if e.get("event") == "scan_phases"
        ]
        assert modes == ["resident"]
