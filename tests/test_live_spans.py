"""The program's live spans (docs/OBSERVABILITY.md "Span catalogue"):
each stage span lands in a CPU ``jax.profiler`` trace on the thread
that did the work, with the record's wall; ingest threads join the
run's capture under their traversal span; every traversal of a source
is one ``traverse:<kind>`` span and one ``engine.data_passes``; the
``ingest_pool`` event's stage seconds are the stage spans' walls."""

import glob
import gzip
import json
import os
from collections import defaultdict

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from deequ_tpu import config
from deequ_tpu.analyzers import (
    AnalysisRunner,
    ApproxQuantile,
    Mean,
    Size,
    Uniqueness,
)
from deequ_tpu.data import Dataset
from deequ_tpu.data.parquet import ParquetDataset
from deequ_tpu.telemetry import TraceContext, get_telemetry

ROWS_PER_FILE = 1500

STREAMED = [Size(), Mean("f"), ApproxQuantile("f", 0.5)]


@pytest.fixture(scope="module")
def parquet_dir(tmp_path_factory):
    directory = tmp_path_factory.mktemp("live_spans")
    rng = np.random.default_rng(23)
    for i in range(2):
        pq.write_table(
            pa.table(
                {
                    "f": pa.array(rng.normal(10.0, 3.0, ROWS_PER_FILE)),
                    "k": pa.array(
                        rng.integers(0, 400, ROWS_PER_FILE, dtype=np.int64)
                    ),
                }
            ),
            os.path.join(directory, f"part-{i}.parquet"),
        )
    return str(directory)


def _high_cardinality():
    rng = np.random.default_rng(5)
    return Dataset.from_pydict(
        {
            "id": rng.integers(0, 2**40, 4000).tolist(),
            "x": rng.normal(size=4000).tolist(),
        }
    )


class _Spans:
    """Every finished span record of the process telemetry."""

    def __enter__(self):
        self.records = []
        get_telemetry().add_span_sink(self.records.append)
        return self.records

    def __exit__(self, *exc):
        get_telemetry().remove_span_sink(self.records.append)


def _streamed(source, workers, analyzers=STREAMED, batch_size=4096):
    # by default one engine batch holds the whole table: the pool has
    # one item, so no two workers run a span of one name at once
    with config.configure(
        device_cache_bytes=0, batch_size=batch_size, ingest_workers=workers
    ):
        return AnalysisRunner.do_analysis_run(
            Dataset.from_parquet(source), analyzers
        )


def _deferred_spill():
    with config.configure(one_pass_spill=False):
        return AnalysisRunner.do_analysis_run(
            _high_cardinality(), [Uniqueness(["id"])]
        )


def _host_groupby():
    with config.configure(
        device_spill_grouping=False, dense_grouping_budget_bytes=1
    ):
        return AnalysisRunner.do_analysis_run(
            _high_cardinality(), [Uniqueness(["id"])]
        )


def _uniques(source):
    # the grouping planner sizes an integer key's dictionary with a
    # pre-pass over the streamed source
    return _streamed(source, 1, [Uniqueness(["k"])])


STAGE_SPANS = {
    "traverse:fused", "traverse:spill", "traverse:groupby",
    "traverse:uniques", "plan:prepare", "plan:lookup", "scan:wait",
    "scan:put", "scan:first_step", "scan:dispatch", "scan:sync",
    "scan:fold", "ingest:read", "ingest:decode", "ingest:encode",
    "spill:sort", "cache:upload",
}


def _perfetto_events(trace_dir):
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*perfetto_trace.json.gz")))
    assert path, "the profiler wrote no perfetto trace"
    with gzip.open(path[-1]) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    out = defaultdict(list)
    for e in events:
        name = (e.get("args") or {}).get("long_name") or e.get("name", "")
        if e.get("ph") == "X" and name.startswith("deequ_tpu:"):
            out[name[len("deequ_tpu:"):]].append(e)
    return out


class TestSpansInTheProfilerTrace:
    def test_each_stage_span_is_on_its_thread_with_its_wall(
        self, parquet_dir, tmp_path
    ):
        import jax

        trace_dir = str(tmp_path / "trace")
        with _Spans() as records:
            jax.profiler.start_trace(trace_dir, create_perfetto_trace=True)
            try:
                _streamed(parquet_dir, 2)
                # the prefetch thread, and several steps on the scan thread
                _streamed(parquet_dir, 1, batch_size=1024)
                _deferred_spill()
                _host_groupby()
                _uniques(parquet_dir)
            finally:
                jax.profiler.stop_trace()
        events = _perfetto_events(trace_dir)
        by_name = defaultdict(list)
        for r in records:
            by_name[r["name"]].append(r)
        assert STAGE_SPANS <= set(by_name), STAGE_SPANS - set(by_name)
        tids = defaultdict(set)
        for name in STAGE_SPANS:
            recs = sorted(by_name[name], key=lambda r: r["started_at"])
            evs = sorted(events[name], key=lambda e: e["ts"])
            assert len(evs) == len(recs), name
            for r, e in zip(recs, evs):
                # perfetto durations are in microseconds
                assert abs(e["dur"] / 1e6 - r["wall_s"]) <= 1e-3, (name, r)
                tids[r["thread"]].add(e["tid"])
        # each thread's spans sit on one trace thread, and no worker's
        # on the scan thread's (a worker may reuse the id of one that
        # has exited, never that of the live scan thread)
        assert all(len(t) == 1 for t in tids.values()), tids
        main = tids.pop("MainThread")
        assert all(t != main for t in tids.values()), tids
        threads = {r["thread"] for n in ("ingest:read", "ingest:decode")
                   for r in by_name[n]}
        assert any(t.startswith("deequ-tpu-ingest-reader") for t in threads)
        assert any(t.startswith("deequ-tpu-ingest-") and "reader" not in t
                   for t in threads)
        assert "deequ-tpu-prefetch" in threads


class TestIngestThreadsJoinTheRun:
    @pytest.mark.parametrize("workers", [1, 2], ids=["prefetch", "pool"])
    def test_worker_spans_parent_to_their_traversal(
        self, parquet_dir, workers
    ):
        tm = get_telemetry()
        ctx = TraceContext.mint("ingest")
        with tm.trace_scope(ctx):
            result = _streamed(parquet_dir, workers)
        spans = result.telemetry["spans"]
        fused = [s for s in spans if s["name"] == "traverse:fused"]
        assert len(fused) == 1
        workers_spans = [
            s for s in spans if s["thread"].startswith("deequ-tpu-")
        ]
        names = {s["name"] for s in workers_spans}
        assert {"ingest:read", "ingest:decode", "ingest:encode"} <= names
        for s in workers_spans:
            assert s["parent_id"] == fused[0]["span_id"], s
            assert s["trace_id"] == ctx.trace_id
            assert s["run_id"] == result.telemetry["run_id"]


class TestTraversals:
    @pytest.mark.parametrize(
        "path", ["fused", "spill", "groupby", "uniques"]
    )
    def test_data_passes_count_traversal_spans(self, parquet_dir, path):
        run = {
            "fused": lambda: _streamed(parquet_dir, 2),
            "spill": _deferred_spill,
            "groupby": _host_groupby,
            "uniques": lambda: _uniques(parquet_dir),
        }[path]
        passes = get_telemetry().counter("engine.data_passes")
        before = passes.value
        with _Spans() as records:
            run()
        traversals = [
            r for r in records if r["name"].startswith("traverse:")
        ]
        assert passes.value - before == len(traversals)
        assert f"traverse:{path}" in {r["name"] for r in traversals}
        for r in traversals:
            assert r["attributes"]["rows"] > 0
            assert r["attributes"]["columns"] >= 1


class TestIngestPoolEvent:
    FIELDS = {
        "workers", "released", "decode_s", "encode_s", "commit_s",
        "idle_s", "stall_s", "wall_s", "peak_in_flight",
        "peak_in_flight_bytes",
    }

    def test_stage_seconds_are_the_stage_spans(self, parquet_dir):
        with _Spans() as records:
            with config.configure(
                device_cache_bytes=0, batch_size=512, ingest_workers=3
            ):
                ctx = AnalysisRunner.do_analysis_run(
                    Dataset.from_parquet(parquet_dir), STREAMED
                )
        events = [
            e for e in ctx.run_metadata.events
            if e.get("event") == "ingest_pool"
        ]
        assert len(events) == 1
        event = events[0]
        assert self.FIELDS <= set(event)
        decode = [r["wall_s"] for r in records
                  if r["name"] == "ingest:decode"]
        encode = [r["wall_s"] for r in records
                  if r["name"] == "ingest:encode"]
        assert len(decode) == event["released"] > 1
        # each side rounds to the microsecond
        assert event["decode_s"] == pytest.approx(
            sum(decode), abs=1e-6 * (len(decode) + 1))
        assert event["encode_s"] == pytest.approx(
            sum(encode), abs=1e-6 * (len(encode) + 1))
        assert event["wall_s"] >= event["commit_s"] >= 0


class TestUniquesPrePass:
    def test_dictionary_pre_pass_is_one_traversal(self, parquet_dir):
        passes = get_telemetry().counter("engine.data_passes")
        before = passes.value
        with _Spans() as records:
            ParquetDataset(parquet_dir).dictionary("k")
        assert passes.value - before == 1
        (traversal,) = [r for r in records if r["name"] == "traverse:uniques"]
        reads = [r for r in records if r["name"] == "ingest:read"]
        assert reads and all(
            r["parent_id"] == traversal["span_id"] for r in reads
        )
