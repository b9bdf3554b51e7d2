"""Differential tests for the r6 fast paths (ISSUE 6): the Pallas
scatter-max kernel behind ``config.pallas_scatter`` and the widened
runtime-gated sorted-dedup HLL pool behind ``config.hll_dedup_widening``.

Ground truth in both cases is the path the flag replaces — the XLA
``.at[].max()`` scatter and the static-probe-only pool — and the
contract is BIT identity, not tolerance: both forms feed the same
``_index_and_rank`` outputs into a max-reduction over the same
register file, so any divergence is a real bug (the v1/v2 max-merge
hazard in analyzers/states.py).

The Pallas kernel runs here in interpret mode
(``DEEQU_TPU_PALLAS_INTERPRET=1``), which executes the same kernel
logic on CPU — the Mosaic-compiled variant is exercised on TPU hosts
by tools/scatter_probe.py and the same differentials there.

Engine-level equality is checked across the three execution shapes
(resident, streaming, mesh) like tests/test_one_pass_spill.py, because
the flags change the compiled plan (plan-cache fingerprint) and each
shape traces its own program.
"""

import numpy as np
import pytest

from deequ_tpu import config
from deequ_tpu.analyzers import (
    AnalysisRunner,
    ApproxCountDistinct,
    ApproxQuantile,
    Mean,
)
from deequ_tpu.data import Dataset
from deequ_tpu.sketches import hll, pallas_scatter


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Force the Pallas kernel's interpret mode and re-probe; restore
    the real probe verdict afterwards so other tests see this host's
    actual availability."""
    monkeypatch.setenv("DEEQU_TPU_PALLAS_INTERPRET", "1")
    pallas_scatter._reset_probe_for_tests()
    yield
    monkeypatch.delenv("DEEQU_TPU_PALLAS_INTERPRET", raising=False)
    pallas_scatter._reset_probe_for_tests()


def _values(dataset, analyzers, engine=None, **options):
    with config.configure(**options):
        ctx = AnalysisRunner.do_analysis_run(
            dataset, analyzers, **({"engine": engine} if engine else {})
        )
    out = {}
    for a in analyzers:
        value = ctx.metric(a).value
        assert value.is_success, (a, value)
        out[a] = value.get()
    return out


class TestPallasScatterUnit:
    """registers_from_hash_pair(_stacked) bit-identity, kernel vs XLA."""

    def _hash_inputs(self, shape, seed):
        rng = np.random.default_rng(seed)
        h1 = rng.integers(0, 1 << 32, shape, dtype=np.uint64).astype(
            np.uint32
        )
        h2 = rng.integers(0, 1 << 32, shape, dtype=np.uint64).astype(
            np.uint32
        )
        mask = rng.random(shape) < 0.9
        return h1, h2, mask

    def _both(self, fn, pallas_on):
        with config.configure(pallas_scatter=pallas_on):
            if pallas_on:
                assert pallas_scatter.available(), (
                    "interpret-mode probe must succeed on CPU"
                )
                assert pallas_scatter.impl_token() == "pallas"
            return np.asarray(fn())

    def test_single_column_bit_identical(self, pallas_interpret):
        h1, h2, mask = self._hash_inputs(8192, 0)
        fn = lambda: hll.registers_from_hash_pair(h1, h2, mask)  # noqa: E731
        np.testing.assert_array_equal(
            self._both(fn, True), self._both(fn, False)
        )

    def test_stacked_bit_identical(self, pallas_interpret):
        h1, h2, mask = self._hash_inputs((6, 4096), 1)
        fn = lambda: hll.registers_from_hash_pair_stacked(h1, h2, mask)  # noqa: E731
        np.testing.assert_array_equal(
            self._both(fn, True), self._both(fn, False)
        )

    def test_all_collision_adversarial(self, pallas_interpret):
        """Every row targets the SAME register: the unroll-16 inner
        loop must still take the running max, not the last write."""
        n = 4096
        h1 = np.full((3, n), 7 << (32 - hll.P), dtype=np.uint32)
        rng = np.random.default_rng(2)
        h2 = rng.integers(0, 1 << 32, (3, n), dtype=np.uint64).astype(
            np.uint32
        )
        mask = np.ones((3, n), bool)
        fn = lambda: hll.registers_from_hash_pair_stacked(h1, h2, mask)  # noqa: E731
        got, want = self._both(fn, True), self._both(fn, False)
        np.testing.assert_array_equal(got, want)
        # sanity: exactly one live register per column
        assert (np.count_nonzero(got, axis=1) == 1).all()

    def test_disabled_without_probe(self):
        """On a host with no TPU and no interpret override the flag is
        inert: scatter_max returns None and XLA runs — never an error."""
        pallas_scatter._reset_probe_for_tests()
        try:
            import jax

            if jax.default_backend() == "tpu":
                pytest.skip("TPU host: kernel genuinely available")
            with config.configure(pallas_scatter=True):
                assert pallas_scatter.impl_token() == "xla"
                assert (
                    pallas_scatter.scatter_max(
                        np.zeros((1, 8), np.int32),
                        np.ones((1, 8), np.int32),
                        hll.M,
                    )
                    is None
                )
        finally:
            pallas_scatter._reset_probe_for_tests()


    def test_tpu_probe_failure_raises(self, monkeypatch):
        """On a TPU a kernel that fails to compile is an error, never a
        silent fall back to the XLA scatter."""
        import jax

        def refused(*_a, **_k):
            raise ValueError("Mosaic refused the kernel")

        pallas_scatter._reset_probe_for_tests()
        monkeypatch.delenv("DEEQU_TPU_PALLAS_INTERPRET", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(pallas_scatter, "_make_call", refused)
        try:
            with config.configure(pallas_scatter=True):
                with pytest.raises(ValueError, match="Mosaic refused"):
                    pallas_scatter.impl_token()
        finally:
            pallas_scatter._PROBE.clear()


def _profile_like_data(n=8192, seed=3):
    rng = np.random.default_rng(seed)
    return {
        "x": rng.normal(size=n).astype(np.float32),
        "y": rng.normal(size=n).astype(np.float32),
        "id": rng.integers(0, 1 << 30, n),
    }


PALLAS_ANALYZERS = [
    ApproxCountDistinct("x"),
    ApproxCountDistinct("y"),
    ApproxCountDistinct("id"),
    ApproxQuantile("x", 0.5),
    ApproxQuantile("y", 0.5),
    Mean("x"),
]


class TestPallasScatterEngine:
    """Full-run metric equality with the kernel wired into the fused
    scan (the plan-cache key carries the resolved impl token, so the
    flag flip really recompiles)."""

    def test_resident(self, pallas_interpret):
        data = _profile_like_data()
        on = _values(
            Dataset.from_pydict(data), PALLAS_ANALYZERS,
            pallas_scatter=True,
        )
        off = _values(
            Dataset.from_pydict(data), PALLAS_ANALYZERS,
            pallas_scatter=False,
        )
        assert on == off

    def test_streaming(self, pallas_interpret):
        data = _profile_like_data(seed=4)
        opts = {"batch_size": 1024, "device_cache_bytes": 0}
        on = _values(
            Dataset.from_pydict(data), PALLAS_ANALYZERS,
            pallas_scatter=True, **opts,
        )
        off = _values(
            Dataset.from_pydict(data), PALLAS_ANALYZERS,
            pallas_scatter=False, **opts,
        )
        assert on == off

    def test_mesh(self, pallas_interpret, cpu_mesh):
        from deequ_tpu.engine.scan import AnalysisEngine

        data = _profile_like_data(seed=5)
        on = _values(
            Dataset.from_pydict(data), PALLAS_ANALYZERS,
            engine=AnalysisEngine(mesh=cpu_mesh), pallas_scatter=True,
        )
        off = _values(
            Dataset.from_pydict(data), PALLAS_ANALYZERS,
            engine=AnalysisEngine(mesh=cpu_mesh), pallas_scatter=False,
        )
        assert on == off


def _widened_gate_data(n=65536, seed=6, mispredict=True):
    """Two i32 columns the STATIC probe cannot pool (span > 4*D) but
    the runtime gate can: batch 1 is mid-cardinality (~1000 distinct,
    seeding a low-cardinality register estimate), batch 2 is either
    mid-cardinality again (gate predicted right, dict path wins) or,
    with ``mispredict``, >16384 distinct — the gate says dict but the
    in-kernel U<=D probe must catch it and fall back to the scatter.
    All values sit inside the f32 24-bit mantissa so the pooled f32
    cast is exact."""
    rng = np.random.default_rng(seed)
    half = n // 2
    lo = rng.choice(np.arange(0, 200_000, 7), 1000, replace=False)
    batch1 = lo[rng.integers(0, 1000, half)]
    if mispredict:
        batch2 = np.arange(half) * 7 + rng.integers(0, 3, half)
    else:
        batch2 = lo[rng.integers(0, 1000, half)]
    cols = {}
    for i, rot in enumerate((0, half // 3)):
        cols[f"c{i}"] = np.concatenate(
            [batch1, np.roll(batch2, rot)]
        ).astype(np.int32)
    assert all(
        int(v.max()) < (1 << 24) and int(v.min()) >= 0
        for v in cols.values()
    )
    assert all(
        int(v.max()) - int(v.min()) > 4 * hll.DEDUP_DICT_CAP
        for v in cols.values()
    )
    return cols


GATE_ANALYZERS = [
    ApproxCountDistinct("c0"),
    ApproxCountDistinct("c1"),
    ApproxQuantile("c0", 0.5),
    ApproxQuantile("c1", 0.5),
]


class TestWidenedDedupGate:
    """Widening on vs off: identical metrics (the gate only changes
    WHICH program computes the registers, never the registers)."""

    @pytest.mark.parametrize("mispredict", [False, True])
    def test_resident(self, mispredict):
        data = _widened_gate_data(mispredict=mispredict)
        opts = {"batch_size": 32768}
        on = _values(
            Dataset.from_pydict(data), GATE_ANALYZERS,
            hll_dedup_widening=True, **opts,
        )
        off = _values(
            Dataset.from_pydict(data), GATE_ANALYZERS,
            hll_dedup_widening=False, **opts,
        )
        assert on == off

    @pytest.mark.parametrize("mispredict", [False, True])
    def test_streaming(self, mispredict):
        data = _widened_gate_data(seed=7, mispredict=mispredict)
        opts = {"batch_size": 32768, "device_cache_bytes": 0}
        on = _values(
            Dataset.from_pydict(data), GATE_ANALYZERS,
            hll_dedup_widening=True, **opts,
        )
        off = _values(
            Dataset.from_pydict(data), GATE_ANALYZERS,
            hll_dedup_widening=False, **opts,
        )
        assert on == off

    def test_mesh(self, cpu_mesh):
        from deequ_tpu.engine.scan import AnalysisEngine

        data = _widened_gate_data(seed=8)
        on = _values(
            Dataset.from_pydict(data), GATE_ANALYZERS,
            engine=AnalysisEngine(mesh=cpu_mesh),
            hll_dedup_widening=True, batch_size=32768,
        )
        off = _values(
            Dataset.from_pydict(data), GATE_ANALYZERS,
            engine=AnalysisEngine(mesh=cpu_mesh),
            hll_dedup_widening=False, batch_size=32768,
        )
        assert on == off

    def test_planner_gates_only_qualifying_columns(self, monkeypatch):
        """Structural: the runtime gate set contains exactly the
        KLL-covered integer columns the static probe could NOT pool —
        statically-poolable columns stay unconditional, columns with
        no KLL coverage stay on the plain scatter (zero added cost)."""
        from deequ_tpu.engine import vectorize

        rng = np.random.default_rng(9)
        n = 4096
        data = {
            # span < 4*D and inside the mantissa: statically pooled
            "narrow": rng.integers(0, 1000, n).astype(np.int32),
            # wide span, KLL-covered: runtime gated
            "wide": rng.integers(0, 1 << 20, n).astype(np.int32),
            # wide span, NO KLL analyzer: not in the candidate pool
            "nokll": rng.integers(0, 1 << 20, n).astype(np.int32),
        }
        analyzers = [
            ApproxCountDistinct("narrow"),
            ApproxCountDistinct("wide"),
            ApproxCountDistinct("nokll"),
            ApproxQuantile("narrow", 0.5),
            ApproxQuantile("wide", 0.5),
        ]
        captured = []
        real = vectorize._build_hll_group

        def spy(dataset, members, value_repr, where, **kwargs):
            captured.append(kwargs.get("runtime_gate_columns"))
            return real(dataset, members, value_repr, where, **kwargs)

        monkeypatch.setattr(vectorize, "_build_hll_group", spy)
        with config.configure(hll_dedup_widening=True):
            units, failures = vectorize.plan_scan_units(
                Dataset.from_pydict(data), analyzers
            )
        assert not failures
        gated = [g for g in captured if g]
        assert gated == [("wide",)], captured

        captured.clear()
        with config.configure(hll_dedup_widening=False):
            vectorize.plan_scan_units(
                Dataset.from_pydict(data), analyzers
            )
        assert [g for g in captured if g] == [], captured
