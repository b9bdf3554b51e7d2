"""DP-mesh tests on the 8-virtual-CPU-device mesh: sharded execution must
equal single-device, and the explicit shard_map + monoid-all-reduce step
must compile and agree (SURVEY.md §4: the no-real-cluster multi-device
story)."""

import jax
import numpy as np
import pytest

from deequ_tpu.analyzers import (
    AnalysisRunner,
    Completeness,
    Maximum,
    Mean,
    Minimum,
    Size,
    StandardDeviation,
    Sum,
)
from deequ_tpu.engine import AnalysisEngine, monoid_all_reduce
from fixtures import big_numeric


ANALYZERS = [
    Size(),
    Completeness("x"),
    Mean("x"),
    Sum("x"),
    Minimum("x"),
    Maximum("x"),
    StandardDeviation("x"),
]


def test_mesh_equals_single_device(cpu_mesh):
    data = big_numeric(50_000)
    ctx_single = AnalysisRunner.do_analysis_run(
        data, ANALYZERS, engine=AnalysisEngine()
    )
    ctx_mesh = AnalysisRunner.do_analysis_run(
        data,
        ANALYZERS,
        engine=AnalysisEngine(mesh=cpu_mesh, batch_size=8_192),
    )
    for analyzer in ANALYZERS:
        a = ctx_single.metric(analyzer).value.get()
        b = ctx_mesh.metric(analyzer).value.get()
        assert a == pytest.approx(b, rel=1e-9), analyzer


@pytest.mark.parametrize(
    "cache_bytes", [8 << 30, 0], ids=["resident", "streaming"]
)
def test_mesh_scan_traces_once_over_many_batches(
    cpu_mesh, cache_bytes, monkeypatch
):
    """The init carry is placed on the mesh like the carry each step
    returns, so step 2 does not retrace (and, on a chip, recompile)
    the whole fused scan — found on 4 v5e chips in PR 21. An empty
    plan cache makes the run trace fresh: exactly one trace."""
    from collections import OrderedDict

    from deequ_tpu import config
    from deequ_tpu.engine import scan as scan_mod

    monkeypatch.setattr(scan_mod, "_PLAN_CACHE", OrderedDict())
    data = big_numeric(40_000)
    engine = AnalysisEngine(mesh=cpu_mesh, batch_size=8_192)
    with config.configure(device_cache_bytes=cache_bytes):
        AnalysisRunner.do_analysis_run(data, ANALYZERS, engine=engine)
    assert data.num_batches(8_192) > 2
    assert not engine.plan_cache_hit
    assert engine.trace_count == 1


def test_explicit_shard_map_step(cpu_mesh):
    """The explicit-SPMD path: per-shard update + monoid all-reduce."""
    data = big_numeric(16_384)
    planned = [(a, a.make_ops(data)) for a in ANALYZERS]
    engine = AnalysisEngine(mesh=cpu_mesh)
    step = engine.build_sharded_step(data, planned, cpu_mesh)

    requests = [
        r for a, _ in planned for r in a.device_requests(data)
    ]
    (batch,) = list(data.device_batches(requests, 16_384))
    states = tuple(ops.init() for _, ops in planned)
    out_states = step(states, batch)

    ctx = AnalysisRunner.do_analysis_run(data, ANALYZERS)
    for (analyzer, _), state in zip(planned, out_states):
        metric = analyzer.compute_metric_from_state(jax.device_get(state))
        expected = ctx.metric(analyzer).value.get()
        assert metric.value.get() == pytest.approx(expected, rel=1e-9)


def test_mesh_grouping_equals_single_device(cpu_mesh):
    """Dense frequency scans under the mesh (NamedSharding batches, XLA
    collectives) must equal the single-device result."""
    from deequ_tpu import Dataset
    from deequ_tpu.analyzers import CountDistinct, Histogram, Uniqueness

    rng = np.random.default_rng(9)
    data = Dataset.from_pydict(
        {"g": rng.integers(0, 500, 40_000), "h": rng.choice(["a", "b", "c"], 40_000)}
    )
    analyzers = [CountDistinct("g"), Uniqueness("g"), Histogram("h")]
    single = AnalysisRunner.do_analysis_run(data, analyzers)
    meshed = AnalysisRunner.do_analysis_run(
        data, analyzers, engine=AnalysisEngine(mesh=cpu_mesh, batch_size=8_192)
    )
    for a in (CountDistinct("g"), Uniqueness("g")):
        assert single.metric(a).value.get() == pytest.approx(
            meshed.metric(a).value.get()
        ), a
    hs = single.metric(Histogram("h")).value.get()
    hm = meshed.metric(Histogram("h")).value.get()
    assert {k: v.absolute for k, v in hs.values.items()} == {
        k: v.absolute for k, v in hm.values.items()
    }


def test_mesh_sketches_equal_single_device(cpu_mesh):
    """Sketch/LUT families NAMED in the mesh regression file (VERDICT
    r4 weak #6): HLL (numeric + dict-encoded), DataType, KLL,
    CustomSql under the mesh vs single-device. HLL registers and
    DataType counts merge exactly (max / add monoids), so equality is
    exact; KLL merged across shard boundaries is a different (valid)
    sketch, so it is held to the rank-error envelope instead."""
    from deequ_tpu import Dataset
    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        ApproxQuantile,
        CustomSql,
    )
    from deequ_tpu.analyzers.datatype import DataType

    rng = np.random.default_rng(21)
    n = 40_000
    xs = rng.normal(50.0, 9.0, n)
    data = Dataset.from_pydict(
        {
            "x": xs,
            "k": rng.integers(0, 30_000, n),
            "s": rng.choice(["1", "2.5", "x", "true", ""], n),
        }
    )
    exact = [
        ApproxCountDistinct("x"),
        ApproxCountDistinct("k"),
        ApproxCountDistinct("s"),
        DataType("s"),
        CustomSql("SUM(x) / COUNT(*)"),
    ]
    quantile = ApproxQuantile("x", 0.5)
    analyzers = exact + [quantile]
    single = AnalysisRunner.do_analysis_run(data, analyzers)
    meshed = AnalysisRunner.do_analysis_run(
        data,
        analyzers,
        engine=AnalysisEngine(mesh=cpu_mesh, batch_size=8_192),
    )
    for a in exact[:3] + exact[4:]:
        got = meshed.metric(a).value.get()
        want = single.metric(a).value.get()
        assert got == pytest.approx(want, rel=1e-9), (a, got, want)
    ds_hist = single.metric(DataType("s")).value.get()
    dm_hist = meshed.metric(DataType("s")).value.get()
    assert {k: v.absolute for k, v in ds_hist.values.items()} == {
        k: v.absolute for k, v in dm_hist.values.items()
    }
    # KLL: both sketches answer within the rank-error envelope
    got_q = meshed.metric(quantile).value.get()
    want_q = single.metric(quantile).value.get()
    srt = np.sort(xs)
    for q in (got_q, want_q):
        rank = np.searchsorted(srt, q) / n
        assert abs(rank - 0.5) < 0.02, (q, rank)


def test_incremental_tree_merge_many_states(tmp_path):
    """run_on_aggregated_states over MANY providers (tree fold)."""
    import os

    from deequ_tpu import Dataset, FileSystemStateProvider
    from deequ_tpu.analyzers import CountDistinct, Mean, Size

    analyzers = [Size(), Mean("x"), CountDistinct("x")]
    providers = []
    total = 0
    for i in range(9):
        ds = Dataset.from_pydict(
            {"x": list(np.arange(i * 10.0, i * 10.0 + 10.0))}
        )
        p = FileSystemStateProvider(os.path.join(tmp_path, f"s{i}"))
        AnalysisRunner.do_analysis_run(ds, analyzers, save_states_with=p)
        providers.append(p)
        total += 10
    schema = Dataset.from_pydict({"x": [1.0]}).schema
    ctx = AnalysisRunner.run_on_aggregated_states(schema, analyzers, providers)
    assert ctx.metric(Size()).value.get() == total
    assert ctx.metric(CountDistinct("x")).value.get() == 90.0
    assert ctx.metric(Mean("x")).value.get() == pytest.approx(44.5)
