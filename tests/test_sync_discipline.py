"""Sync discipline (ISSUE 6 satellite): the engine's host<->device
contract, pinned with telemetry counters.

Every host<->device round trip is a fixed latency tax, so the
engine's whole design funnels synchronization into
ONE place: the packed epilogue fetch (engine/pack.py
``packed_device_get``). These tests pin the measured counter deltas —
a full ColumnProfiler run pays exactly 1 data pass + 1 device fetch
(2 of each when a string column numeric-promotes, the one legitimate
second pass), and a multi-batch streaming KLL run still fetches ONCE
at the end, never per step. A regression here (a stray
``device_get`` in a hot loop, a second accidental traversal) shows up
as a counter bump long before anyone notices seconds on a dashboard.

The static half of the same contract is tools/telemetry_lint.py:
``device_get``/``asarray`` NAME tokens inside ``deequ_tpu/engine/``
outside pack.py need a same-line ``# sync-ok:`` waiver. The last test
runs the lint over the repo so a new unwaived sync fails CI, not
production.
"""

import os

import numpy as np

from deequ_tpu import config
from deequ_tpu.analyzers import AnalysisRunner, ApproxQuantile, Mean
from deequ_tpu.data import Dataset
from deequ_tpu.profiles.profiler import ColumnProfiler
from deequ_tpu.telemetry import get_telemetry

COUNTERS = (
    "engine.scans",
    "engine.data_passes",
    "engine.device_fetches",
    "engine.fetch_bytes",
)


def _deltas(fn):
    """Run ``fn`` and return the engine counter deltas it caused."""
    tm = get_telemetry()
    before = tm.metrics.counters_snapshot()
    fn()
    after = tm.metrics.counters_snapshot()
    return {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}


def _mixed_profile_data(n=20_000, seed=0):
    rng = np.random.default_rng(seed)
    return Dataset.from_pydict(
        {
            "price": rng.normal(size=n).astype(np.float32),
            "qty": rng.integers(0, 100, n),
            "cat": np.array(["red", "green", "blue"])[
                rng.integers(0, 3, n)
            ],
        }
    )


class TestProfileSyncBudget:
    def test_mixed_profile_is_one_pass_one_fetch(self):
        """The common case: numeric + low-cardinality string columns.
        Speculative pass-1 histograms (engine/scan.py) mean NO second
        pass, and the packed epilogue means ONE fetch for the whole
        ~15-analyzer plan."""
        ds = _mixed_profile_data()
        d = _deltas(lambda: ColumnProfiler.profile(ds))
        assert d["engine.scans"] == 1, d
        assert d["engine.data_passes"] == 1, d
        assert d["engine.device_fetches"] == 1, d
        # the fetch actually moved the packed state (bytes attributed)
        assert d["engine.fetch_bytes"] > 0, d

    def test_promoted_string_profile_is_two_passes_two_fetches(self):
        """The one SANCTIONED second pass: a string column whose values
        all parse numeric promotes after pass 1, and the numeric
        analyzers re-scan. Exactly 2 passes / 2 fetches — not 3, and
        never per-column."""
        rng = np.random.default_rng(1)
        ds = Dataset.from_pydict(
            {
                "x": rng.normal(size=20_000).astype(np.float32),
                "as_text": [
                    f"{v:.3f}" for v in rng.normal(size=20_000)
                ],
            }
        )
        d = _deltas(lambda: ColumnProfiler.profile(ds))
        assert d["engine.scans"] == 2, d
        assert d["engine.data_passes"] == 2, d
        assert d["engine.device_fetches"] == 2, d


class TestStreamingSyncBudget:
    def test_multibatch_kll_run_fetches_once(self):
        """8 streaming batches through the KLL unit: the per-step
        sample fetch is folded into the scan's single packed epilogue
        (ISSUE 6 tentpole a) — the step loop itself never calls
        ``device_get``."""
        rng = np.random.default_rng(2)
        ds = Dataset.from_pydict(
            {
                "a": rng.normal(size=4096).astype(np.float32),
                "b": rng.normal(size=4096).astype(np.float32),
            }
        )
        analyzers = [
            ApproxQuantile("a", 0.5),
            ApproxQuantile("b", 0.5),
            Mean("a"),
        ]

        def run():
            with config.configure(batch_size=512, device_cache_bytes=0):
                ctx = AnalysisRunner.do_analysis_run(ds, analyzers)
            for a in analyzers:
                assert ctx.metric(a).value.is_success

        d = _deltas(run)
        assert d["engine.scans"] == 1, d
        assert d["engine.data_passes"] == 1, d
        assert d["engine.device_fetches"] == 1, d


class TestSyncLint:
    def test_engine_hot_paths_are_lint_clean(self):
        """The static rule behind the counters: no unwaived
        ``device_get``/``asarray`` token inside deequ_tpu/engine/
        outside the packed epilogue (tools/telemetry_lint.py)."""
        from tools.telemetry_lint import find_violations

        root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        assert find_violations(root) == []
