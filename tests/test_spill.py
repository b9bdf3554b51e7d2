"""Device sort+segment grouping (analyzers/spill.py): the TPU-native
replacement for the host Arrow spill on high-cardinality single numeric
columns. The ground truth is the host path itself (device_spill_grouping
= False forces it), mirroring the reference's exact groupBy semantics."""

import numpy as np
import pytest

from deequ_tpu import config
from deequ_tpu.analyzers import (
    AnalysisRunner,
    CountDistinct,
    Distinctness,
    Entropy,
    Histogram,
    Uniqueness,
    UniqueValueRatio,
)
from deequ_tpu.data import Dataset


def _metrics(dataset, analyzers, spill: bool):
    with config.configure(device_spill_grouping=spill):
        ctx = AnalysisRunner.do_analysis_run(dataset, analyzers)
    return {a: ctx.metric(a) for a in analyzers}


def _assert_paths_agree(dataset, analyzers):
    device = _metrics(dataset, analyzers, spill=True)
    host = _metrics(dataset, analyzers, spill=False)
    for a in analyzers:
        d, h = device[a].value, host[a].value
        assert d.is_success and h.is_success, (a, d, h)
        dv, hv = d.get(), h.get()
        if isinstance(dv, float):
            assert dv == pytest.approx(hv, rel=1e-9), a
        else:
            assert dv == hv, a


class TestDeviceSpillAgainstHost:
    def test_int_column_all_count_metrics(self):
        rng = np.random.default_rng(11)
        ids = rng.integers(0, 5_000, 20_000, dtype=np.int64)
        ids[::97] = np.iinfo(np.int64).max  # extreme values are legal keys
        ids[::101] = np.iinfo(np.int64).min
        ds = Dataset.from_pydict({"id": list(ids)})
        _assert_paths_agree(
            ds,
            [
                CountDistinct("id"),
                Uniqueness("id"),
                Distinctness("id"),
                UniqueValueRatio("id"),
                Entropy("id"),
            ],
        )

    def test_float_column_with_nulls_nan_negzero(self):
        vals = [1.5, -0.0, 0.0, float("nan"), float("nan"), None, 2.5, 1.5]
        ds = Dataset.from_pydict({"x": vals * 100})
        # host dictionary_encode groups NaN==NaN but keeps -0.0 and 0.0
        # distinct; the device path canonicalizes NaN bits to match
        _assert_paths_agree(
            ds, [CountDistinct("x"), Uniqueness("x"), Distinctness("x")]
        )

    def test_where_filter(self):
        rng = np.random.default_rng(5)
        ds = Dataset.from_pydict(
            {
                "id": list(rng.integers(0, 500, 4_000, dtype=np.int64)),
                "flag": list(rng.integers(0, 2, 4_000, dtype=np.int64)),
            }
        )
        _assert_paths_agree(
            ds,
            [
                CountDistinct("id", where="flag = 1"),
                Uniqueness("id", where="flag = 1"),
            ],
        )

    def test_histogram_includes_null_bin_and_topk(self):
        rng = np.random.default_rng(7)
        vals = rng.integers(0, 50, 5_000).astype(object)
        vals[::13] = None
        ds = Dataset.from_pydict({"v": list(vals)})
        device = _metrics(ds, [Histogram("v", max_detail_bins=10)], True)
        host = _metrics(ds, [Histogram("v", max_detail_bins=10)], False)
        d = device[Histogram("v", max_detail_bins=10)].value.get()
        h = host[Histogram("v", max_detail_bins=10)].value.get()
        assert d.number_of_bins == h.number_of_bins
        # top-10 bin COUNTS agree exactly (the k-th bin may tie-break to
        # a different equally-frequent key); keys common to both agree
        dd = {k: v.absolute for k, v in d.values.items()}
        hh = {k: v.absolute for k, v in h.values.items()}
        assert sorted(dd.values()) == sorted(hh.values())
        for k in set(dd) & set(hh):
            assert dd[k] == hh[k]

    def test_float32_labels_match_dense_path(self):
        import pyarrow as pa

        vals = np.array([1.1, 2.2, 1.1, 3.3] * 50, dtype=np.float32)
        ds = Dataset.from_arrow(pa.table({"x": pa.array(vals)}))
        h = Histogram("x")
        device = _metrics(ds, [h], True)[h].value.get()
        host = _metrics(ds, [h], False)[h].value.get()
        # keys decode in the column's OWN dtype: str(np.float32(1.1))
        # == "1.1", not the widened float64 repr "1.100000023841858"
        assert set(device.values) == set(host.values)
        assert {k: v.absolute for k, v in device.values.items()} == {
            k: v.absolute for k, v in host.values.items()
        }

    def test_empty_and_all_null(self):
        ds = Dataset.from_pydict({"x": [None, None, None]})
        with config.configure(device_spill_grouping=True):
            ctx = AnalysisRunner.do_analysis_run(ds, [CountDistinct("x")])
        # all rows null -> empty state -> failure metric, like the host path
        assert not ctx.metric(CountDistinct("x")).value.is_success


class TestSpillStateInterop:
    def test_device_state_merges_with_host_state(self):
        from deequ_tpu.analyzers.grouping import (
            FrequenciesAndNumRows,
            FrequencyPlan,
            compute_many_frequencies,
        )

        a = Dataset.from_pydict({"id": [1, 2, 2, 3]})
        b = Dataset.from_pydict({"id": [3, 4, 4, 5]})
        plan = FrequencyPlan(("id",), None, False)
        with config.configure(device_spill_grouping=True):
            fa = compute_many_frequencies(a, [plan])[plan]
        with config.configure(device_spill_grouping=False):
            fb = compute_many_frequencies(b, [plan])[plan]
        merged = FrequenciesAndNumRows.merge(fa, fb)
        assert merged.num_rows == 8
        assert merged.num_groups == 5
        got = {
            k: c for k, c in zip(merged.keys[:, 0], merged.counts)
        }
        assert got == {1: 1, 2: 2, 3: 2, 4: 2, 5: 1}

    def test_joint_multicolumn_spill_equals_host(self):
        """Two-column plans whose joint key space exceeds the dense
        budget but fits a u64 lane take the packed-joint-code device
        sort; results must equal the Arrow host path exactly."""
        rng = np.random.default_rng(17)
        a = rng.integers(0, 300, 6_000).astype(object)
        b = rng.integers(0, 300, 6_000).astype(object)
        a[::31] = None
        b[::17] = None
        ds = Dataset.from_pydict({"a": list(a), "b": list(b)})
        analyzers = [
            CountDistinct(["a", "b"]),
            Uniqueness(["a", "b"]),
            Distinctness(["a", "b"]),
            Entropy(["a", "b"]),
        ]
        # force the dense path out: joint (301*301 ~ 90k) > budget slots
        with config.configure(dense_grouping_budget_bytes=4 * 1024):
            device = _metrics(ds, analyzers, spill=True)
            host = _metrics(ds, analyzers, spill=False)
        for z in analyzers:
            d, h = device[z].value, host[z].value
            assert d.is_success and h.is_success, (z, d, h)
            assert d.get() == pytest.approx(h.get(), rel=1e-9), z

    def test_joint_spill_event_and_merge(self):
        from deequ_tpu.analyzers.grouping import (
            FrequenciesAndNumRows,
            FrequencyPlan,
            compute_many_frequencies,
        )

        x = Dataset.from_pydict({"a": [1, 1, 2], "b": [5, 5, 6]})
        y = Dataset.from_pydict({"a": [2, 3], "b": [6, 7]})
        plan = FrequencyPlan(("a", "b"), None, False)
        with config.configure(
            dense_grouping_budget_bytes=16,  # joint (4*4=16) > 4 slots
            device_spill_grouping=True,
        ):
            events = []
            fx = compute_many_frequencies(x, [plan], events=events)[plan]
            # the list also carries scan_phases events (the one-pass
            # collector runs the shared scan), so filter by shape
            assert any(
                e.get("path") == "device-sort-joint" for e in events
            ), events
        with config.configure(device_spill_grouping=False):
            fy = compute_many_frequencies(y, [plan])[plan]
        merged = FrequenciesAndNumRows.merge(fx, fy)
        got = {
            (k[0], k[1]): c
            for k, c in zip(merged.keys, merged.counts)
        }
        assert got == {(1, 5): 2, (2, 6): 2, (3, 7): 1}
        assert merged.num_rows == 5

    def test_sharded_spill_equals_single_device(self, cpu_mesh):
        """The hash-bucket all_to_all re-shard (SURVEY §7 hard part #1):
        a high-cardinality int column under an 8-device mesh must give
        exactly the single-device answer."""
        from deequ_tpu.engine import AnalysisEngine

        rng = np.random.default_rng(21)
        ids = rng.integers(0, 40_000, 64_000, dtype=np.int64)
        ids[::513] = np.iinfo(np.int64).max  # exercises the sentinel path
        vals = ids.astype(object)
        vals[::97] = None
        ds = Dataset.from_pydict({"id": list(vals)})
        analyzers = [
            CountDistinct("id"),
            Uniqueness("id"),
            Distinctness("id"),
            Entropy("id"),
            Histogram("id", max_detail_bins=20),
        ]
        single = AnalysisRunner.do_analysis_run(ds, analyzers)
        meshed = AnalysisRunner.do_analysis_run(
            ds, analyzers, engine=AnalysisEngine(mesh=cpu_mesh)
        )
        for a in analyzers[:4]:
            assert meshed.metric(a).value.get() == pytest.approx(
                single.metric(a).value.get(), rel=1e-9
            ), a
        hs = single.metric(analyzers[4]).value.get()
        hm = meshed.metric(analyzers[4]).value.get()
        assert hs.number_of_bins == hm.number_of_bins
        assert sorted(
            v.absolute for v in hs.values.values()
        ) == sorted(v.absolute for v in hm.values.values())

    def test_sharded_state_persists_and_reloads(self, cpu_mesh, tmp_path):
        """A ShardedDeviceFrequencies state round-trips through the
        FileSystemStateProvider like any dense-path state."""
        from deequ_tpu import FileSystemStateProvider
        from deequ_tpu.engine import AnalysisEngine

        rng = np.random.default_rng(41)
        ds = Dataset.from_pydict(
            {"id": list(rng.integers(0, 2_000, 8_000, dtype=np.int64))}
        )
        a = CountDistinct("id")
        provider = FileSystemStateProvider(str(tmp_path))
        ctx = AnalysisRunner.do_analysis_run(
            ds, [a], engine=AnalysisEngine(mesh=cpu_mesh),
            save_states_with=provider,
        )
        want = ctx.metric(a).value.get()
        reloaded = provider.load(a)
        assert reloaded is not None
        assert a.compute_metric_from_state(reloaded).value.get() == want

    def test_sharded_spill_with_where_filter(self, cpu_mesh):
        from deequ_tpu.engine import AnalysisEngine

        rng = np.random.default_rng(33)
        ds = Dataset.from_pydict(
            {
                "id": list(rng.integers(0, 4_000, 16_000, dtype=np.int64)),
                "flag": list(rng.integers(0, 2, 16_000, dtype=np.int64)),
            }
        )
        analyzers = [
            CountDistinct("id", where="flag = 1"),
            Uniqueness("id", where="flag = 1"),
        ]
        single = AnalysisRunner.do_analysis_run(ds, analyzers)
        meshed = AnalysisRunner.do_analysis_run(
            ds, analyzers, engine=AnalysisEngine(mesh=cpu_mesh)
        )
        for a in analyzers:
            assert meshed.metric(a).value.get() == pytest.approx(
                single.metric(a).value.get(), rel=1e-9
            ), a

    def test_spill_event_recorded_in_run_metadata(self):
        # key range must exceed DENSE_DOMAIN_RANGE: bounded-domain
        # integers now (r5) ride the dense fused scan instead
        rng = np.random.default_rng(3)
        ds = Dataset.from_pydict(
            {"id": list(rng.integers(0, 10**7, 1_000, dtype=np.int64))}
        )
        with config.configure(device_spill_grouping=True):
            ctx = AnalysisRunner.do_analysis_run(ds, [Uniqueness("id")])
        events = ctx.run_metadata.events
        assert any(
            e["event"] == "grouping_spill" and e["path"] == "device-sort"
            for e in events
        )


class TestR4JointExtensions:
    """r4 (VERDICT r3 next #7): joint key spaces past one u64 lane ride
    TWO sort lanes; high-cardinality multi-column plans re-probe full
    cardinalities instead of falling to Arrow; f64 keys pack on the
    host where the backend lacks the 64-bit bitcast."""

    def test_two_lane_joint_exceeds_u64_equals_host(self):
        from deequ_tpu.analyzers import spill as spill_mod

        rng = np.random.default_rng(23)
        n = 60_000
        # four ~55k-cardinality columns: joint radix product
        # ~(55k)^4 ~ 1e19 > 2^62, needing the second sort lane
        cols = {
            f"c{j}": list(
                rng.integers(0, 500_000, n, dtype=np.int64)
            )
            for j in range(4)
        }
        ds = Dataset.from_pydict(cols)
        names = list(cols)
        # confirm the joint genuinely exceeds one u64 lane
        sizes = [
            len(ds.dictionary(c)) + 1 for c in names
        ]
        joint = 1
        for s in sizes:
            joint *= s
        assert joint >= 2**62
        assert spill_mod.split_joint_lanes(tuple(sizes)) is not None
        analyzers = [
            CountDistinct(names),
            Uniqueness(names),
            Distinctness(names),
            Entropy(names),
        ]
        with config.configure(dense_grouping_budget_bytes=4 * 1024):
            from deequ_tpu.analyzers.grouping import (
                FrequencyPlan,
                compute_many_frequencies,
            )

            events = []
            device = _metrics(ds, analyzers, spill=True)
            host = _metrics(ds, analyzers, spill=False)
            # path check: the plan takes the joint device sort
            plan = FrequencyPlan(tuple(names), None, False)
            compute_many_frequencies(ds, [plan], events=events)
            assert any(
                e.get("path") == "device-sort-joint" for e in events
            ), events
        for z in analyzers:
            d, h = device[z].value, host[z].value
            assert d.is_success and h.is_success, (z, d, h)
            assert d.get() == pytest.approx(h.get(), rel=1e-9), z

    def test_high_cardinality_pair_mutual_information(self):
        """Two columns whose cardinality blows the dense probe's budget
        must still ride the device joint path (full-cardinality
        re-probe), and MutualInformation must equal the Arrow oracle."""
        from deequ_tpu.analyzers import MutualInformation
        from deequ_tpu.analyzers.grouping import (
            FrequencyPlan,
            compute_many_frequencies,
        )

        rng = np.random.default_rng(29)
        n = 40_000
        a = rng.integers(0, 30_000, n, dtype=np.int64)
        b = np.where(
            rng.random(n) < 0.5, a, rng.integers(0, 30_000, n)
        )
        ds = Dataset.from_pydict({"a": list(a), "b": list(b)})
        analyzers = [
            MutualInformation(["a", "b"]),
            Uniqueness(["a", "b"]),
        ]
        with config.configure(dense_grouping_budget_bytes=1024):
            events = []
            plan = FrequencyPlan(("a", "b"), None, False)
            compute_many_frequencies(ds, [plan], events=events)
            assert any(
                e.get("path") == "device-sort-joint" for e in events
            ), events
            device = _metrics(ds, analyzers, spill=True)
            host = _metrics(ds, analyzers, spill=False)
        for z in analyzers:
            assert device[z].value.get() == pytest.approx(
                host[z].value.get(), rel=1e-9
            ), z

    def test_host_f64_keys_match_device_builder(self):
        """host_f64_u64_keys (the TPU path's host twin) must produce
        bit-identical keys to the jitted f64 builder (the CPU device
        path) — divergence would make TPU and CPU group differently."""
        import jax.numpy as jnp

        from deequ_tpu.analyzers.spill import (
            _chunk_key_fn,
            host_f64_u64_keys,
        )

        rng = np.random.default_rng(31)
        vals = rng.normal(0, 1e300, 4096)
        vals[::5] = np.nan
        vals[::7] = -0.0
        vals[::11] = 0.0
        vals[::13] = np.inf
        mask = rng.random(4096) < 0.9
        rows = rng.random(4096) < 0.95
        for include_nulls in (False, True):
            dk, dns, dnn = _chunk_key_fn("f64", include_nulls)(
                jnp.asarray(vals), jnp.asarray(mask), jnp.asarray(rows)
            )
            hk, hns, hnn = host_f64_u64_keys(
                vals, mask, rows, include_nulls
            )
            assert (np.asarray(dk) == hk).all()
            assert int(dns) == hns and int(dnn) == hnn

    def test_split_joint_lanes(self):
        from deequ_tpu.analyzers.spill import split_joint_lanes

        assert split_joint_lanes((10, 10)) == 2  # fits one lane
        big = 2**40
        assert split_joint_lanes((big, big)) == 1  # needs two lanes
        assert split_joint_lanes((big, big, big, big)) is None
        assert split_joint_lanes((2**63,)) is None  # single digit too big

    def test_meshed_joint_spill_equals_host(self, cpu_mesh):
        """r4: meshed multi-column joint spills ride the hash-bucket
        all_to_all shuffle (single-u64-lane joints) instead of falling
        to host Arrow — metrics must equal the Arrow oracle exactly."""
        from deequ_tpu.analyzers import MutualInformation
        from deequ_tpu.analyzers.grouping import (
            FrequencyPlan,
            compute_many_frequencies,
        )
        from deequ_tpu.engine.scan import AnalysisEngine

        rng = np.random.default_rng(41)
        n = 24_000
        a = rng.integers(0, 3_000, n, dtype=np.int64)
        b = np.where(rng.random(n) < 0.5, a, rng.integers(0, 3_000, n))
        ds = Dataset.from_pydict({"a": list(a), "b": list(b)})
        analyzers = [
            CountDistinct(["a", "b"]),
            Uniqueness(["a", "b"]),
            Entropy(["a", "b"]),
            MutualInformation(["a", "b"]),
        ]
        engine = AnalysisEngine(mesh=cpu_mesh, batch_size=n)
        with config.configure(dense_grouping_budget_bytes=1024):
            events = []
            plan = FrequencyPlan(("a", "b"), None, False)
            compute_many_frequencies(
                ds, [plan], engine=engine, events=events
            )
            assert any(
                e.get("path") == "device-sort-joint" for e in events
            ), events
            with config.configure(device_spill_grouping=True):
                ctx_mesh = AnalysisRunner.do_analysis_run(
                    ds, analyzers, engine=engine
                )
            with config.configure(device_spill_grouping=False):
                ctx_host = AnalysisRunner.do_analysis_run(ds, analyzers)
        for z in analyzers:
            d, h = ctx_mesh.metric(z).value, ctx_host.metric(z).value
            assert d.is_success and h.is_success, (z, d, h)
            assert d.get() == pytest.approx(h.get(), rel=1e-9), z

    def test_meshed_f64_host_bits_equals_host(self, cpu_mesh, monkeypatch):
        """r4: meshed f64 grouping via host-packed canonical bits (the
        TPU path, forced on via the test hook so the CPU mesh can
        exercise it) must equal the Arrow oracle — incl. NaN payloads
        and -0.0."""
        from deequ_tpu.analyzers import spill as spill_mod
        from deequ_tpu.engine.scan import AnalysisEngine

        rng = np.random.default_rng(43)
        n = 16_000
        vals = rng.normal(0, 1, n)
        vals[::7] = np.nan
        vals[::11] = -0.0
        vals[::13] = 0.0
        arr = vals.astype(object)
        arr[::17] = None
        ds = Dataset.from_pydict({"f": list(arr)})
        analyzers = [
            CountDistinct("f"),
            Uniqueness("f"),
            Distinctness("f"),
            Entropy("f"),
        ]
        monkeypatch.setattr(spill_mod, "_FORCE_HOST_F64_BITS", True)
        engine = AnalysisEngine(mesh=cpu_mesh, batch_size=n)
        with config.configure(device_spill_grouping=True):
            ctx_mesh = AnalysisRunner.do_analysis_run(
                ds, analyzers, engine=engine
            )
        # the device path must actually have run (not a vacuous
        # Arrow-vs-Arrow comparison)
        events = [
            e
            for e in (ctx_mesh.run_metadata.events or [])
            if e.get("event") == "grouping_spill"
        ]
        assert any(e["path"] == "device-sort" for e in events), events
        with config.configure(device_spill_grouping=False):
            ctx_host = AnalysisRunner.do_analysis_run(ds, analyzers)
        for z in analyzers:
            d, h = ctx_mesh.metric(z).value, ctx_host.metric(z).value
            assert d.is_success and h.is_success, (z, d, h)
            assert d.get() == pytest.approx(h.get(), rel=1e-9), z


class TestDenseDomainGate:
    """Bounded-domain integers (TPC-DS quantity shape) must ride the
    dense fused scan — the r5 range gate — with results equal to both
    the sort path it replaced and the host Arrow path."""

    def test_small_range_ints_stay_dense_and_exact(self):
        rng = np.random.default_rng(11)
        vals = rng.integers(1, 101, 50_000, dtype=np.int64)
        ds = Dataset.from_pydict({"q": list(vals)})
        with config.configure(device_spill_grouping=True):
            ctx = AnalysisRunner.do_analysis_run(
                ds, [Uniqueness("q"), CountDistinct("q")]
            )
        assert not any(
            e.get("event") == "grouping_spill"
            for e in ctx.run_metadata.events
        ), ctx.run_metadata.events
        with config.configure(device_spill_grouping=False):
            want = AnalysisRunner.do_analysis_run(
                ds, [Uniqueness("q"), CountDistinct("q")]
            )
        for a in (Uniqueness("q"), CountDistinct("q")):
            assert ctx.metric(a).value.get() == want.metric(a).value.get()


class TestMeshedTwoLaneJoint:
    def test_meshed_joint_exceeds_u64_equals_host(self, cpu_mesh):
        """Joint key spaces past one u64 lane (> 2^62) under a MESH
        (VERDICT r4 next #4): the hash-bucket all_to_all shuffle rides
        TWO key lanes with a per-shard lax.sort(num_keys=2); the
        count-family metrics must equal the host Arrow oracle exactly
        (the sharded two-lane fetch/decode path is pinned directly by
        test_meshed_two_lane_fetch_decodes_groups below — the only
        pairwise analyzer, MutualInformation, can never reach a
        > 2^62 joint)."""
        from deequ_tpu.analyzers import spill as spill_mod
        from deequ_tpu.engine.scan import AnalysisEngine

        rng = np.random.default_rng(29)
        n = 40_000
        # five ~38k-cardinality columns: joint radix product ~8e22,
        # well past one u64 lane
        cols = {
            f"c{j}": list(rng.integers(0, 500_000, n, dtype=np.int64))
            for j in range(5)
        }
        ds = Dataset.from_pydict(cols)
        names = list(cols)
        sizes = [len(ds.dictionary(c)) + 1 for c in names]
        joint = 1
        for s in sizes:
            joint *= s
        assert joint >= 2**62  # genuinely needs the second lane
        split = spill_mod.split_joint_lanes(tuple(sizes))
        assert split is not None and split < len(names)

        analyzers = [
            CountDistinct(names),
            Uniqueness(names),
            Distinctness(names),
            Entropy(names),
        ]
        engine = AnalysisEngine(mesh=cpu_mesh, batch_size=n)
        with config.configure(dense_grouping_budget_bytes=4 * 1024):
            with config.configure(device_spill_grouping=True):
                ctx_mesh = AnalysisRunner.do_analysis_run(
                    ds, analyzers, engine=engine
                )
            with config.configure(device_spill_grouping=False):
                ctx_host = AnalysisRunner.do_analysis_run(ds, analyzers)
        for z in analyzers:
            d, h = ctx_mesh.metric(z).value, ctx_host.metric(z).value
            assert d.is_success and h.is_success, (z, d, h)
            assert d.get() == pytest.approx(h.get(), rel=1e-9), z

    def test_meshed_two_lane_fetch_decodes_groups(self, cpu_mesh):
        """The sharded two-lane fetch path (keys + counts across
        shards) must reconstruct the exact group multiset."""
        from deequ_tpu.analyzers.grouping import (
            FrequencyPlan,
            compute_many_frequencies,
        )
        from deequ_tpu.analyzers import spill as spill_mod
        from deequ_tpu.engine.scan import AnalysisEngine

        rng = np.random.default_rng(30)
        n = 6_000
        cols = {
            f"c{j}": list(rng.integers(0, 400_000, n, dtype=np.int64))
            for j in range(5)
        }
        ds = Dataset.from_pydict(cols)
        names = tuple(cols)
        sizes = [len(ds.dictionary(c)) + 1 for c in names]
        joint = 1
        for s in sizes:
            joint *= s
        assert joint >= 2**62
        engine = AnalysisEngine(mesh=cpu_mesh, batch_size=n)
        plan = FrequencyPlan(names, None, False)
        with config.configure(
            dense_grouping_budget_bytes=1024, device_spill_grouping=True
        ):
            dev = compute_many_frequencies(ds, [plan], engine=engine)[
                plan
            ]
        assert isinstance(
            dev, spill_mod.TwoLaneDeviceFrequencies
        ), type(dev)
        # the groups stayed sharded across the mesh until the fetch
        assert len(dev._dev[1].sharding.device_set) == cpu_mesh.size
        with config.configure(device_spill_grouping=False):
            host = compute_many_frequencies(ds, [plan])[plan]
        got = sorted(
            (tuple(k), int(c)) for k, c in zip(dev.keys, dev.counts)
        )
        want = sorted(
            (tuple(k), int(c)) for k, c in zip(host.keys, host.counts)
        )
        assert got == want
