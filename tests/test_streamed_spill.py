"""A streamed near-unique integer key groups in the one-pass collector
(``spill.streamed_spill_eligible``): with the device cache off, a
parquet source's single INTEGRAL grouping column whose statistics
range spans more values than one read batch sorts on the device inside
the fused scan, with no dictionary pre-pass and no per-batch encode
against it. Ground truth is the host Arrow group-by and the
resident-cache route, which must agree EXACTLY. The routes this must
not change (a range within one batch, a bounded domain, an in-memory
table, the cache-on route) are pinned at the planner."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from deequ_tpu import config
from deequ_tpu.analyzers import (
    AnalysisRunner,
    CountDistinct,
    Distinctness,
    Histogram,
    Mean,
    Size,
    Uniqueness,
)
from deequ_tpu.analyzers.grouping import plan_frequency_passes, plans_for
from deequ_tpu.data import Dataset
from deequ_tpu.engine.scan import AnalysisEngine
from deequ_tpu.telemetry import get_telemetry

N = 20_000
READ_BATCH = 4096
KEY_RANGE = 100_000
STREAMED = dict(device_cache_bytes=0, batch_size=4096)
# the host Arrow group-by: no device sort, no room for a dense vector
HOST_ARROW = dict(
    STREAMED, device_spill_grouping=False, dense_grouping_budget_bytes=1024
)
RESIDENT = dict(device_cache_bytes=1 << 30, batch_size=4096)


def _write(tmp_path, key, shards=4):
    rng = np.random.default_rng(7)
    v = rng.normal(size=len(key))
    step = len(key) // shards
    for i in range(shards):
        part = slice(i * step, (i + 1) * step)
        pq.write_table(
            pa.table({"k": key[part], "v": v[part]}),
            str(tmp_path / f"part{i}.parquet"),
        )
    return str(tmp_path)


def _near_unique(dtype=pa.int64(), nulls=False, key_range=KEY_RANGE):
    rng = np.random.default_rng(11)
    keys = rng.choice(key_range, N, replace=False)
    keys[:300] = keys[300:600]  # some repeats, so counts differ from 1
    mask = np.zeros(N, dtype=bool)
    if nulls:
        mask[rng.choice(N, N // 20, replace=False)] = True
    return pa.array(keys, type=dtype, mask=mask)


def _analyzers(with_histogram=False):
    out = [
        Size(),
        Mean("v"),
        Uniqueness(["k"]),
        CountDistinct(["k"]),
        Distinctness(["k"]),
    ]
    if with_histogram:
        # a null bin (an include_nulls plan), and every group a bin, so
        # no tie among equal counts decides which groups show
        out.append(Histogram("k", max_detail_bins=N))
    return out


def _metrics(dataset, analyzers, engine=None, **options):
    with config.configure(**options):
        ctx = AnalysisRunner.do_analysis_run(dataset, analyzers, engine=engine)
    out = []
    for a in analyzers:
        value = ctx.metric(a).value
        assert value.is_success, (a, value)
        out.append((str(a), value.get()))
    return out


def _deltas(names, thunk):
    tm = get_telemetry()
    before = {n: tm.counter(n).value for n in names}
    out = thunk()
    return out, {n: tm.counter(n).value - before[n] for n in names}


COUNTERS = (
    "engine.data_passes",
    "grouping.spill.device-sort",
    "grouping.spill.host-arrow",
    "spill.finalizes",
)


@pytest.mark.parametrize(
    "dtype, nulls, histogram, mesh",
    [
        (pa.int64(), False, False, False),
        (pa.int64(), True, True, False),
        (pa.int32(), False, False, False),
        (pa.int64(), True, False, True),
    ],
    ids=["int64", "int64-nulls-histogram", "int32", "int64-nulls-mesh"],
)
def test_streamed_key_groups_in_one_pass(
    tmp_path, cpu_mesh, dtype, nulls, histogram, mesh
):
    directory = _write(tmp_path, _near_unique(dtype, nulls))
    analyzers = _analyzers(histogram)
    engine = AnalysisEngine(mesh=cpu_mesh) if mesh else None

    def streamed():
        return _metrics(
            Dataset.from_parquet(directory, read_batch_rows=READ_BATCH),
            analyzers, engine, **STREAMED,
        )

    got, moved = _deltas(COUNTERS, streamed)
    # one traversal: no ``traverse:uniques`` pre-pass, no re-scan
    assert moved["engine.data_passes"] == 1
    plans = 2 if histogram else 1  # Histogram's plan keeps nulls
    assert moved["grouping.spill.device-sort"] == plans
    assert moved["grouping.spill.host-arrow"] == 0
    assert moved["spill.finalizes"] == plans

    arrow, routed = _deltas(
        COUNTERS,
        lambda: _metrics(
            Dataset.from_parquet(directory, read_batch_rows=READ_BATCH),
            analyzers, engine, **HOST_ARROW,
        ),
    )
    assert routed["grouping.spill.host-arrow"] == plans
    resident = _metrics(
        Dataset.from_parquet(directory, read_batch_rows=READ_BATCH),
        analyzers, engine, **RESIDENT,
    )
    assert got == arrow == resident


def _routes(dataset, columns, **options):
    """Where the planner sends the plan of ``Uniqueness(c)`` for each
    of ``columns``, in that order: "dense", or the path of the
    collector's scan fallback ("deferred", "host-arrow")."""
    plans = list(plans_for([Uniqueness([c]) for c in columns]).keys())
    with config.configure(**options):
        dense, collectors, deferred = plan_frequency_passes(
            dataset, plans, AnalysisEngine()
        )
    assert not deferred
    routes = {spec[0].columns[0]: "dense" for spec in dense}
    routes.update(
        (spec.plan.columns[0], spec.scan_fallback[0]) for spec in collectors
    )
    return [routes[c] for c in columns]


def _route(dataset, **options):
    """Where the planner sends the plan of ``Uniqueness(k)``."""
    (route,) = _routes(dataset, ["k"], **options)
    return route


@pytest.mark.parametrize(
    "key_range, read_batch, options, expected",
    [
        (KEY_RANGE, READ_BATCH, STREAMED, "host-arrow"),
        # the range fits one read batch: the dictionary is cheap
        (KEY_RANGE, 1 << 17, STREAMED, "dense"),
        # a bounded domain stays dense whatever the read batch
        (3000, 1024, STREAMED, "dense"),
        # with the cache on, the resident route re-scans on failure
        (KEY_RANGE, READ_BATCH, RESIDENT, "deferred"),
        # the collector needs one-pass spill on
        (KEY_RANGE, READ_BATCH, dict(STREAMED, one_pass_spill=False),
         "dense"),
    ],
    ids=["past-batch", "within-batch", "bounded", "cache-on",
         "no-one-pass"],
)
def test_parquet_route_follows_the_key_range(
    tmp_path, key_range, read_batch, options, expected
):
    n = min(N, key_range)
    rng = np.random.default_rng(3)
    key = pa.array(rng.choice(key_range, n, replace=False))
    directory = _write(tmp_path, key)
    dataset = Dataset.from_parquet(directory, read_batch_rows=read_batch)
    assert _route(dataset, **options) == expected


def test_parquet_key_without_statistics_stays_dense(tmp_path):
    pq.write_table(
        pa.table({"k": _near_unique(), "v": np.zeros(N)}),
        str(tmp_path / "nostats.parquet"),
        write_statistics=False,
    )
    dataset = Dataset.from_parquet(str(tmp_path), read_batch_rows=READ_BATCH)
    assert dataset.integral_range("k") is None
    assert _route(dataset, **STREAMED) == "dense"


def test_in_memory_table_stays_dense(tmp_path):
    """An in-memory table's dictionary is a hash over memory already
    held: the cache-off route stays dense, with the same device
    fetches and passes as a run that cannot sort at all."""
    directory = _write(tmp_path, _near_unique())
    table = pq.read_table(directory)
    assert Dataset.from_arrow(table).dictionary_traversal_batch is None
    assert Dataset.from_parquet(
        directory, read_batch_rows=READ_BATCH
    ).dictionary_traversal_batch == READ_BATCH
    assert _route(Dataset.from_arrow(table), **STREAMED) == "dense"
    names = COUNTERS + ("engine.device_fetches",)
    analyzers = _analyzers()
    got, moved = _deltas(
        names,
        lambda: _metrics(Dataset.from_arrow(table), analyzers, **STREAMED),
    )
    want, unsorted = _deltas(
        names,
        lambda: _metrics(
            Dataset.from_arrow(table), analyzers,
            **dict(STREAMED, device_spill_grouping=False),
        ),
    )
    assert got == want
    assert moved == unsorted
    assert moved["grouping.spill.device-sort"] == 0


@pytest.mark.parametrize(
    "room, lane, expected",
    [
        (2, False, ["host-arrow", "host-arrow"]),
        (1, False, ["host-arrow", "dense"]),
        (0, False, ["dense", "dense"]),
        # the lane's collector routes first and takes the one slot,
        # though its plan comes last
        (1, True, ["dense", "dense"]),
        (3, True, ["host-arrow", "host-arrow"]),
    ],
    ids=["both-fit", "one-fits", "none-fits", "lane-takes-it",
         "beside-lane"],
)
def test_streamed_route_fits_the_device(
    tmp_path, monkeypatch, room, lane, expected
):
    """The collectors of one scan, at 64 B a buffer slot each, take at
    most half of the device's memory; a streamed key past that keeps
    the dense route. ``room`` is how many collectors that half holds."""
    from deequ_tpu import config as config_mod
    from deequ_tpu.analyzers import spill

    keys = _near_unique()
    columns = {"k": keys, "k2": pa.array(np.asarray(keys) + 7)}
    if lane:
        columns["s"] = pa.array([f"id-{i:06d}" for i in range(N)])
    pq.write_table(pa.table(columns), str(tmp_path / "keys.parquet"))
    dataset = Dataset.from_parquet(str(tmp_path), read_batch_rows=READ_BATCH)
    engine = AnalysisEngine()
    # the CPU backend reports no memory limit: there the gate is open
    assert spill.device_memory_bytes(engine) is None
    with config_mod.configure(**STREAMED):
        slot_bytes = (
            spill._collector_geometry(dataset, engine)[3]
            * spill.COLLECTOR_BYTES_PER_SLOT
        )
    monkeypatch.setattr(
        spill, "device_memory_bytes", lambda _engine: 2 * room * slot_bytes
    )
    names = ["k", "k2"] + (["s"] if lane else [])
    options = dict(STREAMED, dict_delta_capacity=64)
    routes = _routes(dataset, names, **options)
    assert routes[:2] == expected
    if lane:
        assert routes[2] == "host-arrow"
