"""The device-sort spill finalize (analyzers/spill.py) against a numpy
oracle: ``np.unique(return_counts=True)`` over the same keys gives the
groups, their counts, every scalar the finalize returns and the top-k
order (descending count, ties in ascending packed-key order). The
finalize counts in sorted-position space with no scatter, compacts only
for a reader of group values, and counts both in telemetry."""

import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deequ_tpu import config
from deequ_tpu.analyzers import CountDistinct, Uniqueness
from deequ_tpu.analyzers import spill
from deequ_tpu.analyzers.grouping import (
    FrequencyPlan,
    compute_many_frequencies,
)
from deequ_tpu.analyzers.runner import AnalysisRunner
from deequ_tpu.data import Dataset
from deequ_tpu.engine.pack import packed_device_get
from deequ_tpu.io.state_provider import FileSystemStateProvider
from deequ_tpu.telemetry import get_telemetry

SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)
BIAS = np.uint64(1) << np.uint64(63)


def _ints(values) -> np.ndarray:
    """int64 values -> the finalize's biased u64 keys."""
    return np.asarray(values, dtype=np.int64).view(np.uint64) ^ BIAS


def _padded(real: np.ndarray, pad: int):
    """Real u64 keys plus ``pad`` sentinel rows (the pow2 padding and
    dropped rows); the correction is the padding alone, so a real
    all-ones key stays a group."""
    keys = np.concatenate([real, np.full(pad, SENTINEL)]).astype(np.uint64)
    return keys, pad


def _case(name):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    if name == "all_equal":
        return _padded(_ints(np.full(300, 42)), 212)
    if name == "all_distinct":
        return _padded(_ints(rng.permutation(1024) * 7919 - 3000), 0)
    if name == "all_sentinel":
        return _padded(np.zeros(0, np.uint64), 256)
    if name == "no_rows":
        # an empty plan pads its zero keys to one sentinel slot
        return _padded(np.zeros(0, np.uint64), 1)
    if name == "padding":
        return _padded(_ints(rng.integers(-50, 50, 700)), 324)
    if name == "all_ones_key":
        real = _ints(rng.integers(0, 20, 400))
        real[::9] = SENTINEL  # int64.max biases to the sentinel bits
        return _padded(real, 112)
    if name == "all_ones_key_unpadded":
        real = _ints(rng.integers(0, 9, 256))
        real[:3] = SENTINEL
        return _padded(real, 0)
    if name == "ties":
        # ten keys seen 5 times, ten seen 3 times: top-k cuts inside a tie
        real = _ints(np.repeat(np.arange(20)[::-1], [5] * 10 + [3] * 10))
        return _padded(rng.permutation(real), 512 - 80)
    raise KeyError(name)


CASES = ["all_equal", "all_distinct", "all_sentinel", "no_rows", "padding",
         "all_ones_key", "all_ones_key_unpadded", "ties"]


def _oracle(keys: np.ndarray, n_sentinel: int):
    """(sorted unique keys, counts) of the real rows: the sentinel
    group loses the ``n_sentinel`` rows that do not belong."""
    uniq, counts = np.unique(keys, return_counts=True)
    if len(uniq) and uniq[-1] == SENTINEL:
        counts[-1] -= n_sentinel
    live = counts > 0
    segments = len(uniq)
    return uniq[live], counts[live].astype(np.int64), segments


def _entropy(counts: np.ndarray) -> float:
    p = counts / max(counts.sum(), 1)
    return float(-np.sum(p * np.log(p)))


def _top_order(keys: np.ndarray, counts: np.ndarray, k: int):
    """Descending count, equal counts in ascending packed-key order."""
    order = np.lexsort((keys, -counts))[:k]
    return keys[order], counts[order]


def _single(keys: np.ndarray, n_sentinel: int):
    scalars, sorted_keys, count_at = spill._finalize_fn()(
        jnp.asarray(keys), jnp.int64(n_sentinel)
    )
    return spill.DeviceFrequencies(
        ("x",), np.dtype(np.int64), packed_device_get(scalars),
        sorted_keys, count_at, 0, False,
    ), packed_device_get(scalars)


class TestFinalizeAgainstOracle:
    @pytest.mark.parametrize("name", CASES)
    def test_one_lane(self, name):
        keys, n_sentinel = _case(name)
        want_keys, want_counts, segments = _oracle(keys, n_sentinel)
        freq, scalars = _single(keys, n_sentinel)
        assert int(scalars["num_segments"]) == segments
        assert int(scalars["total"]) == int(want_counts.sum())
        assert freq.num_groups == len(want_keys)
        assert freq.count_unique_groups() == int(np.sum(want_counts == 1))
        if len(want_keys):
            assert freq.entropy_nats() == pytest.approx(
                _entropy(want_counts), rel=1e-12, abs=1e-15
            )
        assert freq._dev[1].shape == (len(keys),)  # position space
        # the compacted group arrays, decoded to the column's values
        np.testing.assert_array_equal(freq.counts, want_counts)
        np.testing.assert_array_equal(
            freq.keys[:, 0].astype(np.int64),
            (want_keys ^ BIAS).view(np.int64),
        )
        for k in (1, 3, 10, 12, 1000):
            got_keys, got_counts = freq.top_groups(k)
            tk, tc = _top_order(want_keys, want_counts, k)
            assert list(got_counts) == list(tc), (k, got_counts, tc)
            assert list(got_keys) == list((tk ^ BIAS).view(np.int64)), k

    @pytest.mark.parametrize("name", CASES)
    def test_two_lanes(self, name):
        """The second lane splits some first-lane groups; the sentinel
        is the max on both lanes."""
        hi, n_sentinel = _case(name)
        lo = np.where(hi == SENTINEL, SENTINEL,
                      (hi * np.uint64(2654435761)) % np.uint64(3))
        real = np.arange(len(hi)) < len(hi) - n_sentinel
        pairs = np.stack([hi, lo], axis=1)
        uniq, counts = np.unique(pairs, axis=0, return_counts=True)
        segments = len(uniq)
        if len(uniq) and (uniq[-1] == SENTINEL).all():
            counts[-1] -= n_sentinel
        live = counts > 0
        uniq, counts = uniq[live], counts[live]
        assert int(real.sum()) == int(counts.sum())

        scalars, s_hi, s_lo, count_at = spill._finalize2_fn()(
            jnp.asarray(hi), jnp.asarray(lo), jnp.int64(n_sentinel)
        )
        scalars = packed_device_get(scalars)
        assert int(scalars["num_segments"]) == segments
        assert int(scalars["num_groups"]) == len(uniq)
        assert int(scalars["unique"]) == int(np.sum(counts == 1))
        assert int(scalars["total"]) == int(counts.sum())
        if len(uniq):
            assert float(scalars["entropy"]) == pytest.approx(
                _entropy(counts), rel=1e-12, abs=1e-15
            )
        (g_hi, g_lo), g_counts = spill._live_groups(
            *packed_device_get(((s_hi, s_lo), count_at))
        )
        np.testing.assert_array_equal(np.stack([g_hi, g_lo], 1), uniq)
        np.testing.assert_array_equal(g_counts, counts)

    @pytest.mark.parametrize("width", ["f32", "f64"])
    def test_nan_and_negative_zero_keys(self, width):
        """NaN payloads group as one NaN and -0.0 with 0.0, as the
        host's dictionary groups them."""
        vals = np.array(
            [1.5, -0.0, 0.0, np.nan, -np.nan, 2.5, 1.5, -0.0, np.inf,
             -np.inf, 1e-300 if width == "f64" else 1e-30] * 9,
            dtype=np.float32 if width == "f32" else np.float64,
        )
        n = 128
        values = np.zeros(n, vals.dtype)
        values[: len(vals)] = vals
        rows = np.arange(n) < len(vals)
        keys, n_sentinel, _ = spill._chunk_key_fn(width, False)(
            jnp.asarray(values), jnp.asarray(rows), jnp.asarray(rows)
        )
        scalars, sorted_keys, count_at = spill._finalize_fn()(
            keys, n_sentinel
        )
        freq = spill.DeviceFrequencies(
            ("x",), vals.dtype, packed_device_get(scalars), sorted_keys,
            count_at, 0, False,
        )
        uniq, counts = np.unique(vals, return_counts=True)  # NaNs as one
        assert freq.num_groups == len(uniq) == 7
        assert freq.count_unique_groups() == int(np.sum(counts == 1))
        assert freq.entropy_nats() == pytest.approx(
            _entropy(counts), rel=1e-12
        )
        def groups(keys, counts):
            return sorted(
                (bool(np.isnan(v)), 0.0 if np.isnan(v) else float(v), int(c))
                for v, c in zip(keys, counts)
            )

        assert groups(freq.keys[:, 0], freq.counts) == groups(uniq, counts)

    @pytest.mark.parametrize("name", ["padding", "all_ones_key", "ties",
                                      "all_distinct"])
    def test_sharded_equals_single_device(self, name, cpu_mesh):
        """The per-shard half of the mesh shuffle runs the same count;
        its scalars and compacted groups equal the single-device
        finalize's, and the int64.max group comes back analytically."""
        keys, n_sentinel = _case(name)  # every case divides over 8
        single, _ = _single(keys, n_sentinel)

        from jax.sharding import NamedSharding, PartitionSpec as P

        ndev = cpu_mesh.shape["dp"]
        cap = spill._mesh_bucket_cap(len(keys) // ndev, ndev)
        out = spill._sharded_spill_fn(cpu_mesh, "dp", cap)(
            jax.device_put(keys, NamedSharding(cpu_mesh, P("dp"))),
            jnp.int64(n_sentinel), jnp.int64(0),
        )
        scalars, g_keys, g_counts, overflow, _ = out
        assert int(overflow) == 0
        sharded = spill.ShardedDeviceFrequencies(
            ("x",), np.dtype(np.int64), packed_device_get(scalars),
            g_keys, g_counts, 0, False,
        )
        assert sharded.num_groups == single.num_groups
        assert sharded.count_unique_groups() == single.count_unique_groups()
        assert sharded.num_rows == single.num_rows
        if single.num_groups:
            assert sharded.entropy_nats() == pytest.approx(
                single.entropy_nats(), rel=1e-12
            )
        want = sorted(zip(single.keys[:, 0], single.counts))
        assert sorted(zip(sharded.keys[:, 0], sharded.counts)) == want
        k = 10
        assert sorted(sharded.top_groups(k)[1]) == sorted(
            single.top_groups(k)[1]
        )


class TestNoScatter:
    def test_lowered_finalizes_hold_no_scatter(self):
        keys = jax.ShapeDtypeStruct((1 << 12,), jnp.uint64)
        n_sentinel = jax.ShapeDtypeStruct((), jnp.int64)
        one = spill._finalize_fn().lower(keys, n_sentinel).as_text()
        two = spill._finalize2_fn().lower(keys, keys, n_sentinel).as_text()
        for text in (one, two):
            assert "stablehlo.sort" in text
            assert "scatter" not in text


def _counts(fn):
    tm = get_telemetry()
    finalizes, compactions = (
        tm.counter("spill.finalizes"), tm.counter("spill.compactions")
    )
    before = finalizes.value, compactions.value
    records = []
    tm.add_span_sink(records.append)
    try:
        out = fn()
    finally:
        tm.remove_span_sink(records.append)
    sorts = [r for r in records if r["name"] == "spill:sort"]
    return (
        out,
        finalizes.value - before[0],
        compactions.value - before[1],
        sorts,
    )


class TestEngagementCounters:
    def test_lane_grouping_without_repeats_compacts_nothing(self):
        """Distinct strings past the dictionary capacity ride the lane;
        no row hash repeats, so the collision check compacts nothing."""
        names = [f"customer-{i:06d}@example.org" for i in range(3_000)]
        with config.configure(dict_delta_capacity=64):
            ds = Dataset.from_pydict({"email": names})
            assert ds.string_lane("email")
            a = Uniqueness(["email"])
            ctx, finalizes, compactions, sorts = _counts(
                lambda: AnalysisRunner.do_analysis_run(
                    ds, [a, CountDistinct(["email"])]
                )
            )
        assert ctx.metric(a).value.get() == 1.0
        assert (finalizes, compactions) == (1, 0)
        assert sorts and all(
            s["attributes"]["compacted"] is False for s in sorts
        )

    def test_lane_grouping_with_repeats_compacts_once(self):
        names = [f"customer-{i % 2_000:06d}" for i in range(3_000)]
        with config.configure(dict_delta_capacity=64):
            ds = Dataset.from_pydict({"name": names})
            a = Uniqueness(["name"])
            ctx, finalizes, compactions, _ = _counts(
                lambda: AnalysisRunner.do_analysis_run(ds, [a])
            )
        # 1,000 names seen once, 1,000 seen twice
        assert ctx.metric(a).value.get() == pytest.approx(1_000 / 3_000)
        assert (finalizes, compactions) == (1, 1)

    def test_persisted_state_compacts_once(self, tmp_path):
        rng = np.random.default_rng(8)
        ids = rng.integers(0, 2**40, 5_000, dtype=np.int64)
        ids[:100] = ids[100:200]
        ds = Dataset.from_pydict({"id": ids})
        a = CountDistinct(["id"])
        provider = FileSystemStateProvider(str(tmp_path))
        ctx, finalizes, compactions, _ = _counts(
            lambda: AnalysisRunner.do_analysis_run(
                ds, [a], save_states_with=provider
            )
        )
        assert ctx.metric(a).value.get() == len(np.unique(ids))
        assert (finalizes, compactions) == (1, 1)
        assert a.compute_metric_from_state(
            provider.load(a)
        ).value.get() == len(np.unique(ids))

    def test_histogram_read_through_keys_compacts_once(self):
        rng = np.random.default_rng(9)
        ids = rng.integers(-(2**40), 2**40, 6_000, dtype=np.int64)
        ds = Dataset.from_pydict({"id": ids})
        plan = FrequencyPlan(("id",), None, False)
        (freq,), finalizes, _, _ = _counts(
            lambda: list(compute_many_frequencies(ds, [plan]).values())
        )
        assert isinstance(freq, spill.DeviceFrequencies)
        _, _, compactions, _ = _counts(
            lambda: (freq.top_groups(5), freq.keys, freq.counts,
                     freq.keys)
        )
        assert (finalizes, compactions) == (1, 1)
        uniq, counts = np.unique(ids, return_counts=True)
        np.testing.assert_array_equal(freq.keys[:, 0].astype(np.int64), uniq)
        np.testing.assert_array_equal(freq.counts, counts)
