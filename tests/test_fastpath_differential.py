"""HLL beside KLL through the engine, against a host reference.

Two integer columns profiled with their quantiles: batch 1 is
mid-cardinality (~1000 distinct), batch 2 either mid-cardinality again
or, with ``mispredict``, past 16,384 distinct, the shape that once
steered a sort-based register build between its branches. The one
register build must give the registers a host ``np.maximum.at`` gives,
so each count equals the estimate of the host registers exactly; the
quantiles must equal the quantile run alone. Checked on the three
execution shapes (resident, streaming, mesh), since each traces its own
program.
"""

import numpy as np
import pytest

from deequ_tpu import config
from deequ_tpu.analyzers import (
    AnalysisRunner,
    ApproxCountDistinct,
    ApproxQuantile,
)
from deequ_tpu.data import Dataset
from deequ_tpu.sketches import hll
from fixtures import host_hll_registers


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


def _widened_gate_data(n=65536, seed=6, mispredict=True):
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
    return cols


GATE_ANALYZERS = [
    ApproxCountDistinct("c0"),
    ApproxCountDistinct("c1"),
    ApproxQuantile("c0", 0.5),
    ApproxQuantile("c1", 0.5),
]


def _check_against_host(data, engine=None, **options):
    got = _values(Dataset.from_pydict(data), GATE_ANALYZERS, engine,
                  **options)
    quantiles = [a for a in GATE_ANALYZERS if isinstance(a, ApproxQuantile)]
    alone = _values(Dataset.from_pydict(data), quantiles, engine, **options)
    for a in GATE_ANALYZERS:
        if isinstance(a, ApproxCountDistinct):
            x = data[a.column]
            want = hll.estimate(host_hll_registers(x, np.ones(len(x), bool)))
        else:
            want = alone[a]
        assert got[a] == want, (a, got[a], want)


class TestWidenedDedupGate:
    @pytest.mark.parametrize("mispredict", [False, True])
    def test_resident(self, mispredict):
        _check_against_host(
            _widened_gate_data(mispredict=mispredict), batch_size=32768
        )

    @pytest.mark.parametrize("mispredict", [False, True])
    def test_streaming(self, mispredict):
        _check_against_host(
            _widened_gate_data(seed=7, mispredict=mispredict),
            batch_size=32768, device_cache_bytes=0,
        )

    def test_mesh(self, cpu_mesh):
        from deequ_tpu.engine.scan import AnalysisEngine

        _check_against_host(
            _widened_gate_data(seed=8),
            engine=AnalysisEngine(mesh=cpu_mesh), batch_size=32768,
        )
