"""The readers of the metrics the program's spans give: on span records
built by hand, with a nested child; and on the records that runs timed
as the window times them leave in the program's ring, read as the
harness reads them once the window has closed."""

import time

import pytest

from benchmark.harness import Observation
from benchmark.metrics import (
    first_step_ms_per_run,
    ingest_s_per_mrow,
    plan_ms_per_run,
    rescan_share,
)
from benchmark.window import Run

READERS = (rescan_share, ingest_s_per_mrow, plan_ms_per_run,
           first_step_ms_per_run)


def span(sid, name, wall, parent=None, thread="MainThread", start=0.0):
    return {"type": "span", "span_id": sid, "parent_id": parent,
            "name": name, "wall_s": wall, "thread": thread,
            "started_at": start, "attributes": {}}


def obs_with(spans, runs):
    obs = Observation(chips=1, setup_s=1.0, runs=runs)
    obs.spans = spans
    return obs


RUNS = [Run(0.0, 1.0, 2_000_000, True), Run(1.0, 2.0, 2_000_000, True),
        Run(2.0, 2.5, 0, False)]

# two runs' worth: the planner starts a dictionary pre-pass, the fused
# scan's workers read, decode and encode, a decode nests a read
SPANS = [
    span(1, "pass:scan", 0.9),
    span(2, "plan:prepare", 0.30, parent=1),
    span(3, "traverse:uniques", 0.25, parent=2),
    span(4, "ingest:read", 0.20, parent=3),
    span(5, "traverse:fused", 0.55, parent=1),
    span(6, "plan:lookup", 0.01, parent=5),
    span(7, "scan:first_step", 0.004, parent=5),
    span(8, "ingest:read", 0.10, parent=5, thread="deequ-tpu-ingest-reader"),
    span(9, "ingest:decode", 0.30, parent=5, thread="deequ-tpu-ingest-0"),
    span(10, "ingest:read", 0.05, parent=9, thread="deequ-tpu-ingest-0"),
    span(11, "ingest:encode", 0.08, parent=5, thread="deequ-tpu-ingest-1"),
    span(12, "traverse:spill", 0.15, parent=1),
    span(13, "traverse:uniques", 0.05, parent=12),
    span(14, "spill:sort", 0.02, parent=12),
    span(15, "plan:prepare", 0.02, parent=1),
    span(16, "scan:first_step", 0.006, parent=5),
]


def test_rescan_share_counts_every_traversal_but_the_fused_once():
    # uniques 0.25 + spill 0.15 (its nested uniques counted within it)
    # over two runs of 1 s; the failed run adds no wall
    assert rescan_share.read(obs_with(SPANS, RUNS)) == pytest.approx(20.0)


def test_ingest_counts_outermost_stages_on_every_thread():
    # reads 0.20 + 0.10, decode 0.30 (its nested read inside it), encode
    # 0.08: 0.68 s over 4M rows
    assert ingest_s_per_mrow.read(obs_with(SPANS, RUNS)) == \
        pytest.approx(0.68 / 4.0)


def test_plan_is_self_time_without_the_pre_pass():
    # prepare 0.30 less its pre-pass 0.25, lookup 0.01, prepare 0.02
    assert plan_ms_per_run.read(obs_with(SPANS, RUNS)) == \
        pytest.approx(1e3 * (0.05 + 0.01 + 0.02) / 2)


def test_first_step_per_run():
    assert first_step_ms_per_run.read(obs_with(SPANS, RUNS)) == \
        pytest.approx(1e3 * 0.010 / 2)


def test_a_program_without_the_spans_gives_nothing():
    # the parent program: passes and runs, no traversal or stage spans
    old = [span(1, "run:analysis", 1.0), span(2, "pass:scan", 0.9, 1)]
    for reader in READERS:
        assert reader.read(obs_with(old, RUNS)) is None
        assert reader.read(obs_with([], RUNS)) is None
        assert reader.read(obs_with(SPANS, [])) is None


def test_only_fused_traversals_read_zero_rescans():
    fused = [span(1, "traverse:fused", 0.5)]
    assert rescan_share.read(obs_with(fused, RUNS)) == 0.0


def test_the_ring_after_runs_timed_like_the_window():
    """Spans left in the program's ring by runs timed as the window
    times them: only those inside the runs count."""
    from deequ_tpu.analyzers import AnalysisRunner, Mean, Size
    from deequ_tpu.data import Dataset
    from deequ_tpu.telemetry import get_telemetry

    ds = Dataset.from_pydict({"x": [1.0, 2.0, 3.0]})

    def one():
        t0 = time.perf_counter()
        AnalysisRunner.do_analysis_run(ds, [Size(), Mean("x")])
        return Run(t0, time.perf_counter(), 3, True)

    one()  # before the window: not read
    runs = [one(), one()]
    obs = Observation(chips=1, setup_s=1.0, runs=runs)
    ms = first_step_ms_per_run.read(obs)
    mine = [r for r in get_telemetry().recent()
            if r.get("name") == "scan:first_step"][-2:]
    assert ms == pytest.approx(1e3 * sum(r["wall_s"] for r in mine) / 2)
    assert plan_ms_per_run.read(obs) > 0
