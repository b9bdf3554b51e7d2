"""Wire-diet streaming ingest (engine/wire.py, docs/PERF.md):
per-column wire codecs, one-pass dictionary deltas, and the staged put
pipeline.

The load-bearing assertion is DIFFERENTIAL: every metric computed over
the codec wire must equal the codecs-off oracle (today's wire) exactly
— on the resident, streaming and mesh paths alike. Codecs narrow only
where the decode provably round-trips, so equality is exact, not
approximate. The fallback leg (stats lied -> widen + retrace), the
mid-stream dictionary-growth delta, the corrupt-wire quarantine, and
the one-pass data_passes pin each get their own scenario.
"""

import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from deequ_tpu import config
from deequ_tpu.analyzers import (
    AnalysisRunner,
    ApproxCountDistinct,
    Completeness,
    DataType,
    Histogram,
    Maximum,
    Mean,
    Minimum,
    Size,
)
from deequ_tpu.data import Dataset
from deequ_tpu.engine import wire as wire_mod
from deequ_tpu.engine.resilience import RetryPolicy
from deequ_tpu.engine.scan import AnalysisEngine
from deequ_tpu.telemetry import get_telemetry
from deequ_tpu.testing.faults import FaultInjectingDataset

FAST_RETRY = RetryPolicy(max_attempts=2, sleep=lambda _s: None)


@pytest.fixture(scope="module")
def parquet_dir(tmp_path_factory):
    """Three parquet files shaped to exercise every codec family:
    - ``k_small``: int64, range [0, 90] -> i8 from stats
    - ``k_mid``:   int64, range fits i16 -> i16 from stats
    - ``k_wide``:  int64, needs the full width -> no codec
    - ``f_exact``: float64 holding f32-exact values -> f32 probe
    - ``f_lossy``: float64 with real doubles -> probe keeps f64
    - ``s_grow``:  strings whose vocabulary GROWS per file, so the
      delta protocol ships non-zero-start deltas mid-stream
    - ``s_flat``:  strings with a stable vocabulary (deltas after
      batch 1 cost zero bytes)
    - ``x``:       nullable float (masks stay on the 1-bit wire)
    """
    directory = tmp_path_factory.mktemp("wirepq")
    rng = np.random.default_rng(13)
    tables = []
    for i in range(3):
        n = 700 + i * 200
        vocab = np.array([f"w{j:03d}" for j in range((i + 1) * 6)])
        f32 = rng.normal(50.0, 9.0, n).astype(np.float32)
        x = rng.normal(0.0, 1.0, n)
        tables.append(
            pa.table(
                {
                    "k_small": pa.array(
                        rng.integers(0, 91, n, dtype=np.int64)
                    ),
                    "k_mid": pa.array(
                        rng.integers(-20_000, 20_000, n, dtype=np.int64)
                    ),
                    "k_wide": pa.array(
                        rng.integers(-(2**40), 2**40, n, dtype=np.int64)
                    ),
                    "f_exact": pa.array(f32.astype(np.float64)),
                    "f_lossy": pa.array(x * np.pi),
                    "s_grow": pa.array(
                        vocab[rng.integers(0, len(vocab), n)]
                    ),
                    "s_flat": pa.array(
                        rng.choice(["red", "green", "blue"], n)
                    ),
                    "x": pa.array(
                        x, pa.float64(), mask=(rng.random(n) < 0.1)
                    ),
                }
            )
        )
        pq.write_table(
            tables[-1], os.path.join(directory, f"part-{i}.parquet")
        )
    return str(directory), pa.concat_tables(tables)


ANALYZERS = [
    Size(),
    Completeness("x"),
    Mean("x"),
    Mean("f_exact"),
    Minimum("f_lossy"),
    Maximum("f_lossy"),
    Minimum("k_small"),
    Maximum("k_mid"),
    Mean("k_wide"),
    ApproxCountDistinct("s_grow"),
    ApproxCountDistinct("s_flat"),
    DataType("s_grow"),
    Histogram("s_flat"),
]


def _metric_values(ctx, analyzers=ANALYZERS):
    out = {}
    for a in analyzers:
        m = ctx.metric(a)
        assert m.value.is_success, (a, m.value)
        v = m.value.get()
        if hasattr(v, "values"):  # histograms compare by bucket
            v = tuple(
                (k, d.absolute) for k, d in sorted(v.values.items())
            )
        out[repr(a)] = v
    return out


def _run(dataset, wire_codecs, *, engine=None, analyzers=ANALYZERS,
         **overrides):
    with config.configure(wire_codecs=wire_codecs, **overrides):
        ctx = AnalysisRunner.do_analysis_run(
            dataset, analyzers, engine=engine
        )
    return _metric_values(ctx, analyzers)


# --------------------------------------------------------------------------
# codec unit behavior (engine/wire.py)
# --------------------------------------------------------------------------


class TestCodecTable:
    def test_narrowest_int_dtype_boundaries(self):
        assert wire_mod.narrowest_int_dtype(0, 127) == np.int8
        assert wire_mod.narrowest_int_dtype(0, 128) == np.int16
        assert wire_mod.narrowest_int_dtype(-129, 0) == np.int16
        assert wire_mod.narrowest_int_dtype(0, 2**20) == np.int32
        assert wire_mod.narrowest_int_dtype(-(2**40), 7) == np.int64

    def _int_table(self, wire=np.int8):
        table = wire_mod.CodecTable()
        table.codecs["k::values"] = wire_mod.ColumnCodec(
            "k::values", np.dtype(np.int64), np.dtype(wire), "stats"
        )
        return table

    def test_int_encode_roundtrips_and_guards(self):
        table = self._int_table()
        enc = table.encode(
            "k::values", np.array([1, 2, 127], dtype=np.int64)
        )
        assert enc.dtype == np.int8
        assert enc.astype(np.int64).tolist() == [1, 2, 127]
        with pytest.raises(wire_mod.CodecViolation) as e:
            table.encode("k::values", np.array([300], dtype=np.int64))
        assert e.value.key == "k::values"
        assert e.value.required == np.int16

    def test_widen_bumps_version_and_token(self):
        table = self._int_table()
        t0 = table.token()
        table.widen("k::values", np.dtype(np.int16))
        assert table.version == 1
        assert table.codecs["k::values"].wire == np.int16
        assert table.token() != t0
        # widening never narrows back, and hitting the canonical width
        # disables the codec entirely (identity encode)
        table.widen("k::values", np.dtype(np.int64))
        assert table.codecs["k::values"].wire == np.int64
        assert not table.codecs["k::values"].active

    def test_float_probe_narrows_only_bit_exact(self):
        table = wire_mod.CodecTable()
        for key in ("exact::values", "lossy::values"):
            table.codecs[key] = wire_mod.ColumnCodec(
                key, np.dtype(np.float64), None, "probe"
            )
        exact = np.linspace(0, 1, 64, dtype=np.float32).astype(
            np.float64
        )
        enc = table.encode("exact::values", exact)
        assert enc.dtype == np.float32
        assert np.array_equal(
            enc.astype(np.float64).view(np.int64), exact.view(np.int64)
        )
        lossy = np.array([0.1, 0.2, np.pi], dtype=np.float64)
        assert table.encode("lossy::values", lossy).dtype == np.float64

    def test_float_guard_catches_later_lossy_batch(self):
        table = wire_mod.CodecTable()
        table.codecs["f::values"] = wire_mod.ColumnCodec(
            "f::values", np.dtype(np.float64), np.dtype(np.float32),
            "probe",
        )
        with pytest.raises(wire_mod.CodecViolation):
            table.encode("f::values", np.array([0.1], dtype=np.float64))

    def test_raw_bytes_accounting(self):
        table = self._int_table()
        enc = table.encode(
            "k::values", np.arange(10, dtype=np.int64)
        )
        assert enc.nbytes == 10
        assert table.raw_bytes_of("k::values", enc) == 80
        # keys without a codec count at face value
        other = np.zeros(4, dtype=np.float32)
        assert table.raw_bytes_of("other", other) == other.nbytes

    def test_resolve_from_parquet_stats(self, parquet_dir):
        directory, _ = parquet_dir
        ds = Dataset.from_parquet(directory)
        from deequ_tpu.data.table import ColumnRequest

        requests = [
            ColumnRequest("k_small", "values"),
            ColumnRequest("k_mid", "values"),
            ColumnRequest("k_wide", "values"),
            ColumnRequest("f_lossy", "values"),
            ColumnRequest("x", "mask"),
        ]
        table = wire_mod.resolve_codecs(ds, requests, enabled=True)
        small = table.codec("k_small::values")
        assert small is not None and small.wire == np.int8
        assert small.origin == "stats"
        mid = table.codec("k_mid::values")
        assert mid is not None and mid.wire == np.int16
        # stats prove k_wide cannot narrow: no codec at all
        assert table.codec("k_wide::values") is None
        # floats defer to the first-batch probe
        lossy = table.codec("f_lossy::values")
        assert lossy is not None and lossy.wire is None
        # masks never get codecs (already 1 bit/row on the wire)
        assert table.codec("x::mask") is None
        assert wire_mod.resolve_codecs(
            ds, requests, enabled=False
        ).codecs == {}


# --------------------------------------------------------------------------
# differential identity: codec wire == codecs-off oracle, all paths
# --------------------------------------------------------------------------


class TestDifferentialIdentity:
    def test_streaming_codecs_match_oracle_and_slim_the_wire(
        self, parquet_dir
    ):
        directory, _ = parquet_dir
        tm = get_telemetry()
        raw0 = tm.counter("engine.wire_bytes_raw").value
        enc0 = tm.counter("engine.wire_bytes_encoded").value
        on = _run(
            Dataset.from_parquet(directory, read_batch_rows=512),
            True,
            device_cache_bytes=0,
            batch_size=450,
        )
        raw = tm.counter("engine.wire_bytes_raw").value - raw0
        encoded = tm.counter("engine.wire_bytes_encoded").value - enc0
        off = _run(
            Dataset.from_parquet(directory, read_batch_rows=512),
            False,
            device_cache_bytes=0,
            batch_size=450,
        )
        assert on == off
        # the diet is real: i8/i16 ints + f32 floats + narrow codes
        assert 0 < encoded < raw

    def test_streaming_matches_resident_oracle(self, parquet_dir):
        directory, full = parquet_dir
        streamed = _run(
            Dataset.from_parquet(directory, read_batch_rows=512),
            True,
            device_cache_bytes=0,
            batch_size=450,
        )
        resident = _run(Dataset.from_arrow(full), False)
        assert streamed == resident

    def test_resident_flag_is_inert(self, parquet_dir):
        """Resident plans never pack a wire; the flag must not change
        results (or anything else) there."""
        _directory, full = parquet_dir
        assert _run(Dataset.from_arrow(full), True) == _run(
            Dataset.from_arrow(full), False
        )

    def test_mesh_codecs_match_oracle(self, parquet_dir, cpu_mesh):
        """The mesh path streams unpacked (pack=False) — codecs must
        not engage, and results must match the oracle bit-for-bit."""
        directory, _ = parquet_dir
        on = _run(
            Dataset.from_parquet(directory, read_batch_rows=512),
            True,
            engine=AnalysisEngine(mesh=cpu_mesh),
            device_cache_bytes=0,
            batch_size=512,
        )
        off = _run(
            Dataset.from_parquet(directory, read_batch_rows=512),
            False,
            engine=AnalysisEngine(mesh=cpu_mesh),
            device_cache_bytes=0,
            batch_size=512,
        )
        assert on == off

    def test_dict_deltas_match_pre_pass_oracle(self, parquet_dir):
        directory, _ = parquet_dir
        deltas = _run(
            Dataset.from_parquet(directory, read_batch_rows=512),
            True,
            device_cache_bytes=0,
            batch_size=450,
            dict_deltas=True,
        )
        pre_pass = _run(
            Dataset.from_parquet(directory, read_batch_rows=512),
            True,
            device_cache_bytes=0,
            batch_size=450,
            dict_deltas=False,
        )
        assert deltas == pre_pass


# --------------------------------------------------------------------------
# dictionary deltas: mid-stream growth, one-pass pin
# --------------------------------------------------------------------------


DELTA_ANALYZERS = [
    Size(),
    Mean("x"),
    ApproxCountDistinct("s_grow"),
    ApproxCountDistinct("s_flat"),
    DataType("s_grow"),
    DataType("s_flat"),
]


class TestDictionaryDeltas:
    def test_mid_stream_growth_ships_deltas(self, parquet_dir):
        directory, full = parquet_dir
        tm = get_telemetry()
        n0 = tm.counter("engine.dict_deltas").value
        v0 = tm.counter("engine.dict_delta_values").value
        with config.configure(device_cache_bytes=0, batch_size=450):
            with tm.run("delta-growth") as cap:
                ctx = AnalysisRunner.do_analysis_run(
                    Dataset.from_parquet(directory, read_batch_rows=512),
                    DELTA_ANALYZERS,
                )
        got = _metric_values(ctx, DELTA_ANALYZERS)
        want = _metric_values(
            AnalysisRunner.do_analysis_run(
                Dataset.from_arrow(full), DELTA_ANALYZERS
            ),
            DELTA_ANALYZERS,
        )
        assert got == want
        assert tm.counter("engine.dict_deltas").value > n0
        assert tm.counter("engine.dict_delta_values").value >= v0 + 18
        events = [
            e for e in cap.final["events"]
            if e.get("event") == "dictionary_delta"
        ]
        grow = [e for e in events if e.get("column") == "s_grow"]
        # the vocabulary grows in files 2 and 3: at least one delta
        # must APPEND (start > 0) rather than re-ship from scratch
        assert any(e.get("start", 0) > 0 for e in grow)
        # the stable vocabulary ships once, then stays free
        flat_values = sum(
            e.get("count", 0)
            for e in events
            if e.get("column") == "s_flat"
        )
        assert flat_values == 3

    def test_string_suite_is_one_pass(self, parquet_dir):
        """The headline: string-code suites traverse the parquet
        source EXACTLY once — no ``_collect_uniques`` pre-pass. The
        pre-pass oracle (dict_deltas off) pays one extra traversal per
        string column."""
        directory, _ = parquet_dir
        tm = get_telemetry()
        passes = tm.counter("engine.data_passes")
        with config.configure(device_cache_bytes=0, batch_size=450):
            before = passes.value
            AnalysisRunner.do_analysis_run(
                Dataset.from_parquet(directory, read_batch_rows=512),
                DELTA_ANALYZERS,
            )
            assert passes.value - before == 1
            before = passes.value
            with config.configure(dict_deltas=False):
                AnalysisRunner.do_analysis_run(
                    Dataset.from_parquet(directory, read_batch_rows=512),
                    DELTA_ANALYZERS,
                )
            assert passes.value - before == 3  # scan + 2 dictionaries

    def test_oversized_dictionary_overflows_loudly(self, tmp_path):
        """A column whose vocabulary is larger than dict_delta_capacity
        from its first rows on no longer overflows: it streams through
        the hashed-string lane (data/strings.py) in the same single
        pass and agrees with the resident run and the exact count. The
        overflow still guards a dictionary that outgrows the capacity
        only later in the stream."""
        rng = np.random.default_rng(3)
        n = 600
        table = pa.table(
            {
                "s": pa.array([f"u{j}" for j in range(n)]),
                "t": pa.array(
                    [f"v{j}" for j in rng.integers(0, n, n)]
                ),
            }
        )
        pq.write_table(table, str(tmp_path / "wide.parquet"))
        analyzers = [ApproxCountDistinct("s"), ApproxCountDistinct("t")]
        tm = get_telemetry()
        passes = tm.counter("engine.data_passes")
        with config.configure(
            device_cache_bytes=0,
            batch_size=256,
            dict_delta_capacity=64,
            scan_retry=RetryPolicy(max_attempts=1),
        ):
            before = passes.value
            lanes_before = tm.counter("strings.lane_columns").value
            streamed = AnalysisRunner.do_analysis_run(
                Dataset.from_parquet(str(tmp_path)), analyzers
            )
            assert passes.value - before == 1
            assert tm.counter("strings.lane_columns").value == (
                lanes_before + 2
            )
            resident = AnalysisRunner.do_analysis_run(
                Dataset.from_arrow(table), analyzers
            )
        for a in analyzers:
            got = streamed.metric(a).value.get()
            assert got == resident.metric(a).value.get()
            want = pc.count_distinct(table.column(a.column)).as_py()
            assert abs(got - want) / want < 0.02

        # first 2^20 rows within capacity, later rows past it: the
        # dictionary path's overflow still fires, naming the knob
        late = ["a"] * (1 << 20) + [f"w{j}" for j in range(100)]
        grow = pa.table({"s": pa.array(late), "t": pa.array(late)})
        pq.write_table(grow, str(tmp_path / "grow.parquet"))
        with config.configure(
            device_cache_bytes=0,
            dict_delta_capacity=64,
            scan_retry=RetryPolicy(max_attempts=1),
        ):
            ctx = AnalysisRunner.do_analysis_run(
                Dataset.from_parquet(str(tmp_path / "grow.parquet")),
                analyzers,
            )
        value = ctx.metric(ApproxCountDistinct("s")).value
        assert not value.is_success
        assert "dict_delta_capacity=64" in repr(value)


# --------------------------------------------------------------------------
# fallback: stats lied -> widen + retrace, same metrics
# --------------------------------------------------------------------------


class TestStatsFallback:
    def test_stats_violating_batch_widens_and_stays_correct(
        self, tmp_path
    ):
        """File 0 fits the (lying) i8 claim; file 1 carries values that
        don't. The guard catches the violation on the prefetch thread,
        widens the codec (one ``wire_codec_widened`` event), re-packs
        the same batch, and every metric still matches the oracle."""
        rng = np.random.default_rng(23)
        small = rng.integers(0, 90, 600, dtype=np.int64)
        big = rng.integers(200, 9_000, 600, dtype=np.int64)
        pq.write_table(
            pa.table({"k": pa.array(small)}),
            str(tmp_path / "part-0.parquet"),
        )
        pq.write_table(
            pa.table({"k": pa.array(big)}),
            str(tmp_path / "part-1.parquet"),
        )
        analyzers = [Size(), Minimum("k"), Maximum("k"), Mean("k")]
        want = _metric_values(
            AnalysisRunner.do_analysis_run(
                Dataset.from_arrow(
                    pa.table(
                        {"k": pa.array(np.concatenate([small, big]))}
                    )
                ),
                analyzers,
            ),
            analyzers,
        )
        ds = Dataset.from_parquet(str(tmp_path), read_batch_rows=512)
        ds.integral_range = lambda column: (0, 90)  # the lie
        tm = get_telemetry()
        # a listener, not ``tm.run``: the violation is caught and the
        # table widened on the PREFETCH thread, outside the main
        # thread's capture scope
        from deequ_tpu.telemetry import CollectingRunListener

        listener = tm.add_listener(CollectingRunListener())
        try:
            with config.configure(device_cache_bytes=0, batch_size=300):
                ctx = AnalysisRunner.do_analysis_run(ds, analyzers)
        finally:
            tm.remove_listener(listener)
        assert _metric_values(ctx, analyzers) == want
        widened = [
            e for e in listener.engine_events
            if e.get("event") == "wire_codec_widened"
        ]
        assert len(widened) == 1
        assert widened[0]["key"] == "k::values"
        assert widened[0]["wire_from"] == "int8"
        assert widened[0]["wire_to"] == "int16"
        assert widened[0]["origin"] == "stats"
        # no quarantine, no retry: a lost narrowing bet is not a fault
        assert ctx.degradation is None or (
            ctx.degradation.batches_quarantined == 0
        )


# --------------------------------------------------------------------------
# corrupt encoded wire -> quarantine (testing/faults.py)
# --------------------------------------------------------------------------


class TestCorruptWire:
    def test_corrupt_encoded_batch_is_quarantined(self):
        """Corruption on the ENCODED wire (truncated leaves after the
        codec engaged) is detected by the layout guard and quarantined
        — the codec layer must not turn integrity failures into wrong
        metrics or widen-loops."""
        rng = np.random.default_rng(5)
        n = 1000
        ds = FaultInjectingDataset(
            Dataset.from_pydict(
                {
                    "a": rng.normal(size=n).tolist(),
                    "k": rng.integers(0, 80, n).tolist(),
                }
            ),
            corrupt={1},
        )
        tm = get_telemetry()
        enc0 = tm.counter("engine.wire_bytes_encoded").value
        raw0 = tm.counter("engine.wire_bytes_raw").value
        with config.configure(
            device_cache_bytes=0, batch_size=104, scan_retry=FAST_RETRY
        ):
            ctx = AnalysisRunner.do_analysis_run(
                ds, [Size(), Mean("a"), Maximum("k")]
            )
        degr = ctx.degradation
        assert degr.batches_quarantined == 1
        assert degr.error_classes == ["BatchIntegrityError"]
        assert ctx.metric(Size()).value.get() == n - 104
        # the codec DID engage on the healthy batches
        raw = tm.counter("engine.wire_bytes_raw").value - raw0
        encoded = tm.counter("engine.wire_bytes_encoded").value - enc0
        assert 0 < encoded < raw


# --------------------------------------------------------------------------
# parallel ingest over the codec wire (engine/ingest.py "r10")
# --------------------------------------------------------------------------


class TestParallelIngestDifferential:
    """The r10 ordered worker pool encodes batches CONCURRENTLY but
    releases them in source order; every codec behavior above must be
    invariant under worker count, with workers=1 running the exact
    pre-pool path as the oracle."""

    def test_codec_wire_is_worker_count_invariant(self, parquet_dir):
        directory, _ = parquet_dir
        tm = get_telemetry()

        def wire(workers):
            raw0 = tm.counter("engine.wire_bytes_raw").value
            enc0 = tm.counter("engine.wire_bytes_encoded").value
            vals = _run(
                Dataset.from_parquet(directory, read_batch_rows=512),
                True,
                device_cache_bytes=0,
                batch_size=450,
                ingest_workers=workers,
            )
            return (
                vals,
                tm.counter("engine.wire_bytes_raw").value - raw0,
                tm.counter("engine.wire_bytes_encoded").value - enc0,
            )

        ref, raw1, enc1 = wire(1)
        got, raw4, enc4 = wire(4)
        assert got == ref
        # same batches, same codecs -> the same bytes cross the wire
        assert (raw4, enc4) == (raw1, enc1)
        assert 0 < enc4 < raw4

    def test_pool_deltas_match_pre_pass_oracle(self, parquet_dir):
        """Both axes at once: dictionary deltas cut at ordered release
        under 4 workers vs the pre-pass consts path under 1."""
        directory, _ = parquet_dir
        pooled = _run(
            Dataset.from_parquet(directory, read_batch_rows=512),
            True,
            device_cache_bytes=0,
            batch_size=450,
            dict_deltas=True,
            ingest_workers=4,
        )
        oracle = _run(
            Dataset.from_parquet(directory, read_batch_rows=512),
            True,
            device_cache_bytes=0,
            batch_size=450,
            dict_deltas=False,
            ingest_workers=1,
        )
        assert pooled == oracle

    def test_pool_mesh_matches_oracle(self, parquet_dir, cpu_mesh):
        directory, _ = parquet_dir
        got = _run(
            Dataset.from_parquet(directory, read_batch_rows=512),
            True,
            engine=AnalysisEngine(mesh=cpu_mesh),
            device_cache_bytes=0,
            batch_size=512,
            ingest_workers=4,
        )
        ref = _run(
            Dataset.from_parquet(directory, read_batch_rows=512),
            True,
            engine=AnalysisEngine(mesh=cpu_mesh),
            device_cache_bytes=0,
            batch_size=512,
            ingest_workers=1,
        )
        assert got == ref
