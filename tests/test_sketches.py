"""Sketch accuracy + merge + serde tests (reference shape:
KLLDistanceTest / KLLSketchTest / HLL accuracy tests — SURVEY.md §4)."""

import jax.numpy as jnp
import numpy as np
import pytest

from deequ_tpu.analyzers import (
    AnalysisRunner,
    ApproxCountDistinct,
    ApproxQuantile,
    ApproxQuantiles,
    KLLSketch,
)
from deequ_tpu.data import Dataset
from deequ_tpu.engine import AnalysisEngine
from deequ_tpu.io import FileSystemStateProvider, InMemoryStateProvider
from deequ_tpu.sketches.kll import KLLParameters, KLLSketchState


def value(metric):
    assert metric.value.is_success, f"metric failed: {metric.value}"
    return metric.value.get()


class TestHLL:
    def test_exact_small(self):
        ds = Dataset.from_pydict({"x": [1, 2, 3, 2, 1]})
        est = value(ApproxCountDistinct("x").calculate(ds))
        assert est == pytest.approx(3.0, rel=0.01)

    def test_accuracy_numeric(self):
        rng = np.random.default_rng(1)
        n_distinct = 80_000
        vals = rng.integers(0, n_distinct, 300_000)
        true = len(np.unique(vals))
        ds = Dataset.from_pydict({"x": vals})
        est = value(ApproxCountDistinct("x").calculate(ds))
        assert est == pytest.approx(true, rel=0.03)

    def test_strings(self):
        ds = Dataset.from_pydict(
            {"s": [f"user-{i % 500}" for i in range(5_000)]}
        )
        est = value(ApproxCountDistinct("s").calculate(ds))
        assert est == pytest.approx(500, rel=0.03)

    def test_nulls_ignored(self):
        import pyarrow as pa

        ds = Dataset.from_arrow(
            pa.table({"x": pa.array([1.0, None, 2.0, None], pa.float64())})
        )
        assert value(ApproxCountDistinct("x").calculate(ds)) == pytest.approx(
            2.0, rel=0.01
        )

    def test_merge_equals_union(self):
        rng = np.random.default_rng(2)
        a = rng.integers(0, 10_000, 50_000)
        b = rng.integers(5_000, 15_000, 50_000)
        analyzer = ApproxCountDistinct("x")
        providers = []
        for part in (a, b):
            p = InMemoryStateProvider()
            AnalysisRunner.do_analysis_run(
                Dataset.from_pydict({"x": part}), [analyzer],
                save_states_with=p,
            )
            providers.append(p)
        merged = AnalysisRunner.run_on_aggregated_states(
            Dataset.from_pydict({"x": a[:1]}).schema, [analyzer], providers
        )
        union = AnalysisRunner.do_analysis_run(
            Dataset.from_pydict({"x": np.concatenate([a, b])}), [analyzer]
        )
        # register-max merge must give the IDENTICAL estimate
        assert value(merged.metric(analyzer)) == value(union.metric(analyzer))

    def test_hash_consistency_within_type_class(self):
        """Columns of the same type class with equal values hash
        identically regardless of storage width (required for
        cross-dataset merges: day 1 stores int32, day 2 int64). Int vs
        float need NOT agree — integral columns hash the raw 64-bit
        payload (exact for the full int64 range), matching the
        reference's HLL++ hashing the raw long."""
        i32 = Dataset.from_pydict({"x": np.arange(1000, dtype=np.int32)})
        i64 = Dataset.from_pydict({"x": np.arange(1000, dtype=np.int64)})
        f32 = Dataset.from_pydict({"x": np.arange(1000, dtype=np.float32)})
        f64 = Dataset.from_pydict({"x": np.arange(1000, dtype=np.float64)})
        assert value(ApproxCountDistinct("x").calculate(i32)) == value(
            ApproxCountDistinct("x").calculate(i64)
        )
        assert value(ApproxCountDistinct("x").calculate(f32)) == value(
            ApproxCountDistinct("x").calculate(f64)
        )

    def test_large_int64_accuracy(self):
        """IDs above 2^53 (snowflake/epoch-nanos scale) must not
        collide: float canonicalization would estimate ~99 distinct for
        100k consecutive values at 2^62."""
        vals = np.arange(100_000, dtype=np.int64) + (1 << 62)
        ds = Dataset.from_pydict({"x": vals})
        est = value(ApproxCountDistinct("x").calculate(ds))
        assert abs(est - 100_000) / 100_000 < 0.03


class TestKLL:
    def test_exact_small(self):
        ds = Dataset.from_pydict({"x": list(range(1, 101))})
        q = value(ApproxQuantile("x", 0.5).calculate(ds))
        assert q == pytest.approx(50.0, abs=1.0)

    def test_rank_error_uniform(self):
        rng = np.random.default_rng(3)
        vals = rng.uniform(0, 1, 500_000)
        ds = Dataset.from_pydict({"x": vals})
        engine = AnalysisEngine(batch_size=65_536)
        analyzer = ApproxQuantiles("x", (0.1, 0.25, 0.5, 0.75, 0.9))
        ctx = AnalysisRunner.do_analysis_run(ds, [analyzer], engine=engine)
        result = value(ctx.metric(analyzer))
        for q in (0.1, 0.25, 0.5, 0.75, 0.9):
            # uniform[0,1]: value at quantile q is ~q; rank error < 1%
            assert result[str(q)] == pytest.approx(q, abs=0.01)

    def test_merge_across_partitions(self):
        rng = np.random.default_rng(4)
        vals = rng.normal(0, 1, 200_000)
        analyzer = ApproxQuantile("x", 0.5)
        providers = []
        for part in np.array_split(vals, 4):
            p = InMemoryStateProvider()
            AnalysisRunner.do_analysis_run(
                Dataset.from_pydict({"x": part}), [analyzer],
                save_states_with=p,
            )
            providers.append(p)
        merged = AnalysisRunner.run_on_aggregated_states(
            Dataset.from_pydict({"x": vals[:1]}).schema, [analyzer], providers
        )
        med = value(merged.metric(analyzer))
        assert med == pytest.approx(np.median(vals), abs=0.02)

    def test_kll_metric_buckets(self):
        ds = Dataset.from_pydict({"x": list(range(1000))})
        analyzer = KLLSketch("x", KLLParameters(number_of_buckets=10))
        dist = value(analyzer.calculate(ds))
        assert len(dist.buckets) == 10
        assert sum(b.count for b in dist.buckets) == pytest.approx(
            1000, abs=20
        )
        assert dist.buckets[0].low_value == 0.0
        assert dist.buckets[-1].high_value == 999.0

    def test_filesystem_roundtrip(self, tmp_path):
        rng = np.random.default_rng(5)
        ds = Dataset.from_pydict({"x": rng.normal(0, 1, 10_000)})
        analyzer = ApproxQuantile("x", 0.9)
        provider = FileSystemStateProvider(str(tmp_path))
        ctx = AnalysisRunner.do_analysis_run(
            ds, [analyzer], save_states_with=provider
        )
        reloaded = AnalysisRunner.run_on_aggregated_states(
            ds.schema, [analyzer], [FileSystemStateProvider(str(tmp_path))]
        )
        assert value(reloaded.metric(analyzer)) == value(ctx.metric(analyzer))

    def test_nonnumeric_fails(self):
        ds = Dataset.from_pydict({"s": ["a", "b"]})
        metric = ApproxQuantile("s", 0.5).calculate(ds)
        assert metric.value.is_failure

    def test_bad_quantile_fails(self):
        ds = Dataset.from_pydict({"x": [1.0, 2.0]})
        metric = ApproxQuantile("x", 1.5).calculate(ds)
        assert metric.value.is_failure


class TestKLLSketchStateUnit:
    def test_streaming_matches_exact(self):
        rng = np.random.default_rng(6)
        vals = rng.exponential(2.0, 100_000)
        sk = KLLSketchState()
        for chunk in np.array_split(vals, 37):
            sk.update_batch(chunk)
        assert sk.count == 100_000
        for q in (0.05, 0.5, 0.95):
            exact = np.quantile(vals, q)
            # compare by rank: estimated value's true rank within 1.5%
            est = sk.quantile(q)
            true_rank = np.mean(vals <= est)
            assert abs(true_rank - q) < 0.015, (q, est, exact)

    def test_monoid_merge(self):
        rng = np.random.default_rng(7)
        a, b = rng.normal(0, 1, 50_000), rng.normal(5, 1, 50_000)
        sa, sb = KLLSketchState(), KLLSketchState()
        sa.update_batch(a)
        sb.update_batch(b)
        merged = KLLSketchState.merge(sa, sb)
        assert merged.count == 100_000
        both = np.concatenate([a, b])
        est = merged.quantile(0.5)
        assert np.mean(both <= est) == pytest.approx(0.5, abs=0.02)


class TestKLLRegressions:
    def test_sparse_where_filter(self):
        """Compaction level derives from surviving rows, not batch size
        (a heavy where-filter must not starve the sketch)."""
        rng = np.random.default_rng(8)
        n = 200_000
        ds = Dataset.from_pydict(
            {
                "x": rng.uniform(0, 1, n),
                "y": (np.arange(n) % 2000 == 0).astype(np.int64),
            }
        )
        analyzer = ApproxQuantile("x", 0.5, where="y = 1")
        metric = analyzer.calculate(ds)
        assert metric.value.is_success, metric.value
        assert 0.3 < metric.value.get() < 0.7

    def test_nan_values_excluded(self):
        vals = np.arange(1000, dtype=np.float64)
        vals[5] = np.nan
        ds = Dataset.from_pydict({"x": vals})
        metric = ApproxQuantile("x", 1.0).calculate(ds)
        assert metric.value.is_success, metric.value
        assert metric.value.get() == 999.0

    def test_sharded_step_host_folds_kll(self, cpu_mesh):
        """Host-folded ops ride the explicit shard_map step: each
        shard's per-batch output is all_gathered and folded once on the
        host (the fold IS the sketch merge, so sharding can't change
        the metric on data small enough to stay uncompacted)."""
        import jax

        from deequ_tpu.engine import AnalysisEngine

        # small enough that BOTH paths keep every value at level 0
        # (nv < sketch_size), so sharded and single are exactly equal
        n = 8 * 128
        vals = np.arange(float(n))
        ds = Dataset.from_pydict({"x": list(vals)})
        analyzer = ApproxQuantile("x", 0.5)
        planned = [(analyzer, analyzer.make_ops(ds))]
        engine = AnalysisEngine(mesh=cpu_mesh)
        step = engine.build_sharded_step(ds, planned, cpu_mesh)
        requests = [
            r for a, _ in planned for r in a.device_requests(ds)
        ]
        (batch,) = list(ds.device_batches(requests, n))
        states = tuple(op.init() for _, op in planned)
        out = jax.block_until_ready(step(states, batch))
        (final,) = engine.fold_sharded_host_outputs(
            [op for _, op in planned], out, 8
        )
        got = analyzer.compute_metric_from_state(final).value.get()
        want = AnalysisRunner.do_analysis_run(ds, [analyzer]).metric(
            analyzer
        ).value.get()
        assert got == pytest.approx(want, rel=1e-9)


class TestKLLAdversarial:
    """docs/KLL_ERROR.md §4: the strided-batch compaction's O(1/k)
    bound must hold for adversarial ORDERINGS and degenerate value
    distributions, not just i.i.d. uniform — a broken random offset,
    boundary weight loss, or order sensitivity would blow the
    envelope on at least one of these."""

    N = 400_000
    QS = tuple(round(q / 100, 2) for q in range(1, 100))

    def _max_rank_error(self, vals: np.ndarray) -> float:
        from deequ_tpu.sketches.kll import DEFAULT_SKETCH_SIZE as k

        ds = Dataset.from_pydict({"x": vals})
        engine = AnalysisEngine(batch_size=65_536)
        analyzer = ApproxQuantiles("x", self.QS)
        ctx = AnalysisRunner.do_analysis_run(ds, [analyzer], engine=engine)
        result = value(ctx.metric(analyzer))
        hi = np.sort(vals)
        n = len(vals)
        worst = 0.0
        for q in self.QS:
            est = result[str(q)]
            # the estimate's rank in the TRUE data is an INTERVAL
            # (value plateaus hold many ranks); the sketch is correct
            # if the target rank falls inside it, and its error is the
            # distance to the interval otherwise
            lo = np.searchsorted(hi, est, side="left")
            rhi = np.searchsorted(hi, est, side="right")
            target = q * n
            err = max(lo - target, target - rhi, 0.0)
            worst = max(worst, err)
        assert worst <= 3 * n / k, worst
        return worst

    def test_sorted_input(self):
        self._max_rank_error(np.arange(self.N, dtype=np.float64))

    def test_reverse_sorted_input(self):
        self._max_rank_error(np.arange(self.N, dtype=np.float64)[::-1])

    def test_constant_heavy(self):
        rng = np.random.default_rng(7)
        vals = np.where(
            rng.random(self.N) < 0.9, 42.0, rng.normal(0, 1, self.N)
        )
        # ranks are ambiguous across a 90% constant plateau; check the
        # plateau's quantiles resolve to the constant and the tails
        # stay in-envelope via the generic check on the mixed data
        self._max_rank_error(np.sort(vals))

    def test_organ_pipe_ordering(self):
        # small/large interleaved: worst case for sequential samplers
        a = np.arange(self.N, dtype=np.float64)
        pipe = np.empty(self.N)
        pipe[0::2] = a[: self.N // 2]
        pipe[1::2] = a[self.N // 2:][::-1]
        self._max_rank_error(pipe)

    def test_few_distinct(self):
        rng = np.random.default_rng(9)
        self._max_rank_error(
            rng.integers(0, 5, self.N).astype(np.float64)
        )


class TestPresenceDTiling:
    """The presence compare-reduce chunks its D axis (r4 advisory —
    bounds the (C, TILE, B) intermediate). Multi-tile results must be
    bit-identical to the single-tile math."""

    def test_hll_presence_multi_tile_matches_unchunked(self):
        from deequ_tpu.sketches.hll import (
            _PRESENCE_D_TILE,
            registers_from_code_presence,
            registers_from_hash_pair_stacked,
        )

        rng = np.random.default_rng(11)
        C, B, D = 3, 1024, _PRESENCE_D_TILE * 2 + 64  # 3 tiles, ragged
        codes = rng.integers(-1, D, (C, B)).astype(np.int32)
        mask = codes >= 0
        lut1 = rng.integers(0, 2**32, (C, D), dtype=np.uint64).astype(
            np.uint32
        )
        lut2 = rng.integers(0, 2**32, (C, D), dtype=np.uint64).astype(
            np.uint32
        )
        got = np.asarray(
            registers_from_code_presence(codes, mask, lut1, lut2)
        )
        # oracle: presence computed densely on host
        present = np.zeros((C, D), dtype=bool)
        for c in range(C):
            occurring = np.unique(codes[c][mask[c]])
            present[c, occurring] = True
        want = np.asarray(
            registers_from_hash_pair_stacked(lut1, lut2, present)
        )
        np.testing.assert_array_equal(got, want)

    def test_datatype_presence_multi_tile_matches_host(self):
        from deequ_tpu.analyzers.datatype import (
            DataTypeHistogram,
            counts_from_code_presence,
        )
        from deequ_tpu.sketches.hll import _PRESENCE_D_TILE

        rng = np.random.default_rng(12)
        C, B, D = 2, 2048, _PRESENCE_D_TILE + 33  # 2 tiles, ragged
        codes = rng.integers(-1, D, (C, B)).astype(np.int32)
        valid = codes >= 0
        rows = np.ones(B, dtype=bool)
        table = rng.integers(0, 6, (C, D)).astype(np.int32)
        got = np.asarray(
            counts_from_code_presence(codes, valid, rows, table)
        )
        want = np.zeros((C, 6), dtype=np.int64)
        for c in range(C):
            for b in range(B):
                if valid[c, b]:
                    want[c, table[c, codes[c, b]]] += 1
                else:
                    want[c, DataTypeHistogram.NULL] += 1
        np.testing.assert_array_equal(got, want)


ENGINE_PATHS = ["resident", "streamed", "mesh"]


def _engine_registers(columns, path, request):
    """Each column's ``ApproxCountDistinct`` state registers after a run
    in 1,024-row batches on one engine path: resident, streamed with
    the device cache off, or on the 8-device CPU mesh. ``columns``:
    name -> (values, valid); nulls ride Arrow's validity."""
    import pyarrow as pa

    from deequ_tpu import config

    table = pa.table(
        {c: pa.array(v, mask=~valid) for c, (v, valid) in columns.items()}
    )
    analyzers = [ApproxCountDistinct(c) for c in columns]
    options = {"batch_size": 1024}
    if path == "streamed":
        options["device_cache_bytes"] = 0
    engine = (
        AnalysisEngine(mesh=request.getfixturevalue("cpu_mesh"))
        if path == "mesh"
        else None
    )
    provider = InMemoryStateProvider()
    with config.configure(**options):
        AnalysisRunner.do_analysis_run(
            Dataset.from_arrow(table), analyzers, save_states_with=provider,
            **({"engine": engine} if engine else {}),
        )
    return {
        c: np.asarray(provider.load(ApproxCountDistinct(c)).registers)
        for c in columns
    }


def _assert_registers_match_host(columns, path, request):
    from fixtures import host_hll_registers

    got = _engine_registers(columns, path, request)
    for c, (values, valid) in columns.items():
        np.testing.assert_array_equal(
            got[c], host_hll_registers(values, valid), err_msg=c
        )


def _dtype_columns(dtype, n=4096, seed=0):
    """A mid- and a high-cardinality column of ``dtype``, ~5% null."""
    rng = np.random.default_rng(seed)
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        mid = rng.integers(1, min(info.max, 300), n)  # no 0: a null's fill
        high = rng.integers(info.min, info.max, n, endpoint=True)
    else:
        mid = np.round(rng.normal(0, 40, n)) / 4
        high = rng.normal(0, 1e6, n)
    return {
        name: (v.astype(dtype), rng.random(n) > 0.05)
        for name, v in (("mid", mid), ("high", high))
    }


class TestRegisterBuild:
    """The one numeric register build (the XLA scatter here; the MXU
    kernel on a TPU, held to the same registers by TestMXURegisters)
    through the engine, against a host ``np.maximum.at``: every storage
    dtype on every engine path, and the edge values (nulls, +inf, -inf,
    NaN, -0.0/+0.0, iinfo.max)."""

    @pytest.mark.parametrize("path", ENGINE_PATHS)
    @pytest.mark.parametrize(
        "dtype",
        [np.int8, np.int16, np.int32, np.int64, np.float32, np.float64],
    )
    def test_engine_registers_equal_host(self, dtype, path, request):
        _assert_registers_match_host(
            _dtype_columns(dtype, seed=np.dtype(dtype).itemsize),
            path, request,
        )

    @pytest.mark.parametrize("path", ENGINE_PATHS)
    def test_float_edges_match_scatter(self, path, request):
        rng = np.random.default_rng(31)
        B = 4096
        rows = [
            # mid-card with nulls
            np.round(rng.normal(100, 25, B) * 100).astype(np.float32)
            / 100,
            # real +inf / -inf / NaN / -0.0 / +0.0 mixture
            np.array(
                [np.inf, -np.inf, np.nan, -0.0, 0.0, 1.5] * (B // 6)
                + [1.5] * (B % 6),
                dtype=np.float32,
            ),
            # constant column
            np.full(B, 42.0, dtype=np.float32),
        ]
        columns = {
            f"f{i}": (x, rng.random(B) > 0.1) for i, x in enumerate(rows)
        }
        _assert_registers_match_host(columns, path, request)

    @pytest.mark.parametrize("path", ENGINE_PATHS)
    def test_int_edges_match_scatter(self, path, request):
        rng = np.random.default_rng(32)
        B = 4096
        columns = {
            # int16 beside int32: two groups of one, so the
            # single-analyzer build runs too
            "small": (
                rng.integers(1, 100, B).astype(np.int16),
                rng.random(B) > 0.2,
            ),
            "sentinel": (
                np.where(
                    rng.random(B) < 0.1,
                    np.iinfo(np.int32).max,
                    rng.integers(-50, 50, B),
                ).astype(np.int32),
                rng.random(B) > 0.2,
            ),
        }
        _assert_registers_match_host(columns, path, request)


class TestDedupFromSortedPool:
    """An HLL column profiled beside its quantiles reports the same
    count as the analyzer run alone."""

    def test_profiler_pool_equality_end_to_end(self):
        """A profile with KLL + HLL co-planned must report the same
        ApproxCountDistinct as a run with the analyzer alone."""
        from deequ_tpu.analyzers import (
            AnalysisRunner,
            ApproxCountDistinct,
            ApproxQuantiles,
        )
        from deequ_tpu.data import Dataset

        rng = np.random.default_rng(42)
        n = 30_000
        ds = Dataset.from_pydict(
            {
                "p1": (
                    np.round(rng.normal(50, 9, n) * 100) / 100
                ).astype(np.float32),
                "p2": rng.normal(0, 1, n).astype(np.float32),
            }
        )
        together = AnalysisRunner.do_analysis_run(
            ds,
            [
                ApproxCountDistinct("p1"),
                ApproxCountDistinct("p2"),
                ApproxQuantiles("p1", [0.5]),
                ApproxQuantiles("p2", [0.5]),
            ],
        )
        for col in ("p1", "p2"):
            alone = AnalysisRunner.do_analysis_run(
                ds, [ApproxCountDistinct(col)]
            )
            a = together.metric(ApproxCountDistinct(col)).value.get()
            b = alone.metric(ApproxCountDistinct(col)).value.get()
            assert a == b, (col, a, b)


class TestIntPoolDedup:
    """The same for integer columns, bounded and wide."""

    def test_end_to_end_int_pool_equality(self):
        """Quantity-style int column profiled WITH quantiles must
        report the same ApproxCountDistinct as alone."""
        from deequ_tpu.analyzers import (
            AnalysisRunner,
            ApproxCountDistinct,
            ApproxQuantiles,
        )
        from deequ_tpu.data import Dataset

        rng = np.random.default_rng(52)
        n = 30_000
        ds = Dataset.from_pydict(
            {
                "qty": rng.integers(1, 101, n),
                "k": rng.integers(0, 1 << 22, n),
            }
        )
        together = AnalysisRunner.do_analysis_run(
            ds,
            [
                ApproxCountDistinct("qty"),
                ApproxCountDistinct("k"),
                ApproxQuantiles("qty", [0.5]),
                ApproxQuantiles("k", [0.5]),
            ],
        )
        for col in ("qty", "k"):
            alone = AnalysisRunner.do_analysis_run(
                ds, [ApproxCountDistinct(col)]
            )
            a = together.metric(ApproxCountDistinct(col)).value.get()
            b = alone.metric(ApproxCountDistinct(col)).value.get()
            assert a == b, (col, a, b)
        assert together.metric(
            ApproxCountDistinct("qty")
        ).value.get() == pytest.approx(100, abs=2)


class TestHLLBesideKLLEdges:
    """Degenerate shapes for HLL groups planned beside KLL groups:
    empty tables, all-null columns, single rows, and a where-filtered
    HLL beside an unfiltered KLL group."""

    def test_empty_dataset_profiles(self):
        import pyarrow as pa

        from deequ_tpu import ColumnProfilerRunner, Dataset

        ds = Dataset.from_arrow(
            pa.table(
                {
                    "x": pa.array([], pa.float32()),
                    "q": pa.array([], pa.int64()),
                }
            )
        )
        p = ColumnProfilerRunner().on_data(ds).run()
        assert sorted(p.profiles) == ["q", "x"]

    def test_all_null_column(self):
        import pyarrow as pa

        from deequ_tpu.data import Dataset

        ds = Dataset.from_arrow(
            pa.table(
                {
                    "a": pa.array([None] * 50, pa.float32()),
                    "b": pa.array([1.5] * 50, pa.float32()),
                }
            )
        )
        ctx = AnalysisRunner.do_analysis_run(
            ds,
            [
                ApproxCountDistinct("a"),
                ApproxCountDistinct("b"),
                ApproxQuantiles("a", [0.5]),
                ApproxQuantiles("b", [0.5]),
            ],
        )
        assert ctx.metric(ApproxCountDistinct("a")).value.get() == 0.0
        assert ctx.metric(
            ApproxCountDistinct("b")
        ).value.get() == pytest.approx(1.0, rel=0.01)

    def test_single_row(self):
        from deequ_tpu.data import Dataset

        ds = Dataset.from_pydict({"x": [2.5], "y": [3]})
        ctx = AnalysisRunner.do_analysis_run(
            ds,
            [
                ApproxCountDistinct("x"),
                ApproxCountDistinct("y"),
                ApproxQuantiles("x", [0.5]),
                ApproxQuantiles("y", [0.5]),
            ],
        )
        for c in ("x", "y"):
            assert ctx.metric(
                ApproxCountDistinct(c)
            ).value.get() == pytest.approx(1.0, rel=0.01)

    def test_where_filtered_hll_beside_unfiltered_kll(self):
        from deequ_tpu.data import Dataset

        ds = Dataset.from_pydict(
            {"v": [1.0, 2.0, 2.0, 3.0] * 25, "g": [1, 0, 1, 0] * 25}
        )
        a = ApproxCountDistinct("v", where="g = 1")
        ctx = AnalysisRunner.do_analysis_run(
            ds, [a, ApproxQuantiles("v", [0.5])]
        )
        # where g=1 keeps values {1.0, 2.0}
        assert ctx.metric(a).value.get() == pytest.approx(2.0, rel=0.01)


def _mxu_case(case):
    """(idx, rho) i32 (C, B) inputs for the MXU register-build
    differential, one named edge each."""
    from deequ_tpu.sketches import hll

    rng = np.random.default_rng(sum(map(ord, case)))

    def hashed(cols, rows, valid=0.9):
        h1, h2 = (
            rng.integers(0, 1 << 32, (cols, rows), dtype=np.uint64)
            .astype(np.uint32)
            for _ in range(2)
        )
        idx, rho = hll._index_and_rank(h1, h2, rng.random((cols, rows)) < valid)
        return np.asarray(idx), np.asarray(rho)

    if case == "random":
        return hashed(3, 8192)
    if case == "one_register_at_band_tops":
        # a whole tile in ONE register at each band's top rank (and the
        # largest rank): the sum reaches 2^12 * 2^104, the bound itself
        rho = np.repeat(np.array([[9], [18], [27], [33]], np.int32), 4096, 1)
        return np.full_like(rho, 12345), rho
    if case == "band_edges":
        edges = np.array([9, 10, 18, 19, 27, 28, 33], np.int32)
        rho = edges[rng.integers(0, len(edges), (2, 6000))]
        idx = rng.integers(0, 24, (2, 6000)).astype(np.int32) * 700
        return idx, rho
    if case == "masked":
        return hashed(2, 4096, valid=0.3)
    if case == "ragged_rows":
        return hashed(2, 5000)
    if case == "one_column":
        return hashed(1, 4096)
    if case == "seventeen_columns":
        return hashed(17, 2048)
    if case == "dictionary_d_plus_3":  # three rows past a tile multiple
        return hashed(1, 16387)
    if case == "no_rows":
        return hashed(2, 0)
    raise ValueError(case)


MXU_CASES = [
    "random", "one_register_at_band_tops", "band_edges", "masked",
    "ragged_rows", "one_column", "seventeen_columns", "dictionary_d_plus_3",
    "no_rows",
]


class TestMXURegisters:
    """The banded one-hot matmul build (sketches/hll_mxu.py) against
    the XLA scatter-max, bit for bit: as the plain-jnp reference of the
    math and as the Pallas kernel through the interpreter. On the chip
    the kernel's probe repeats the band-top case against a numpy
    scatter-max."""

    @pytest.mark.parametrize("form", ["reference", "kernel"])
    @pytest.mark.parametrize("case", MXU_CASES)
    def test_bit_identical_to_scatter(self, case, form, monkeypatch):
        from deequ_tpu.sketches import hll, hll_mxu

        idx, rho = _mxu_case(case)
        assert hll.register_build_path() == "xla"  # the CPU's build
        want = np.asarray(hll._build_registers(idx, rho))
        if form == "kernel":
            monkeypatch.setenv("DEEQU_TPU_PALLAS_INTERPRET", "1")
            got = hll_mxu.registers(idx, rho, hll.M)
        else:
            got = hll_mxu.reference_registers(idx, rho, hll.M)
        got = np.asarray(got)
        assert got.shape == want.shape == (idx.shape[0], hll.M)
        np.testing.assert_array_equal(got.astype(np.int8), want)
        assert want.max() == rho.max(initial=0)

    def _force_mxu(self, monkeypatch):
        from deequ_tpu.sketches import hll_mxu

        monkeypatch.setenv("DEEQU_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setattr(hll_mxu, "available", lambda: True)

    def test_dispatch_counts_the_build(self, monkeypatch):
        """With the MXU build available, both public builders take it,
        count it under ``hll.register_builds.mxu`` and still equal the
        scatter."""
        from deequ_tpu.sketches import hll
        from deequ_tpu.telemetry import get_telemetry

        h1, h2, mask = (
            np.random.default_rng(5).integers(0, 1 << 32, (3, 2, 3000))
            .astype(np.uint32)
        )
        mask = mask % 4 != 0
        want = np.asarray(hll.registers_from_hash_pair_stacked(h1, h2, mask))
        want_one = np.asarray(
            hll.registers_from_hash_pair(h1[0], h2[0], mask[0])
        )
        self._force_mxu(monkeypatch)
        counter = get_telemetry().metrics.counter("hll.register_builds.mxu")
        before = counter.value
        assert hll.register_build_path() == "mxu"
        got = np.asarray(hll.registers_from_hash_pair_stacked(h1, h2, mask))
        got_one = np.asarray(
            hll.registers_from_hash_pair(h1[0], h2[0], mask[0])
        )
        assert counter.value == before + 2
        assert got.dtype == want.dtype == hll.REGISTER_DTYPE
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got_one, want_one)

    @pytest.mark.parametrize("mesh", [False, True])
    def test_engine_metrics_equal(self, mesh, monkeypatch, cpu_mesh):
        """A profile-like run through the engine, resident and on the
        8-device mesh (each device builds its rows' registers, a max
        all-reduce merges them): the same counts as the scatter."""
        from deequ_tpu import config

        rng = np.random.default_rng(6)
        data = {
            "id": rng.integers(0, 1 << 40, 6000),
            "price": rng.integers(0, 100_000, 6000).astype(np.int32),
            "x": rng.normal(size=6000),
        }
        analyzers = [ApproxCountDistinct(c) for c in data] + [
            ApproxQuantile("price", 0.5)
        ]
        engine = AnalysisEngine(mesh=cpu_mesh) if mesh else None

        def run():
            with config.configure(batch_size=2048):
                ctx = AnalysisRunner.do_analysis_run(
                    Dataset.from_pydict(data), analyzers,
                    **({"engine": engine} if engine else {}),
                )
            return {a: value(ctx.metric(a)) for a in analyzers}

        from deequ_tpu.telemetry import get_telemetry

        want = run()
        self._force_mxu(monkeypatch)
        counter = get_telemetry().metrics.counter("hll.register_builds.mxu")
        before = counter.value
        assert run() == want
        assert counter.value > before  # the plan was traced with the MXU
