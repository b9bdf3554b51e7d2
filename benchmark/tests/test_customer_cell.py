"""What decides ``correct`` in the two cells this file's configuration
and traffic add, at a size a test run holds, on the CPU: the hourly
partitions of ``store_sales_sf10.verify_hourly`` (40,000 rows) and the
customer gate of ``customer_sf1000.verify_strings`` (100,000 rows, so
its ids and e-mails pass 65,536 distinct values and ride the
hashed-string lane). A sound run of each is correct; the float32
control is not; each fault of benchmark/faults.py planted underneath
the timed path is not; nor is a lane that hashes every string to one
value."""

import time
from collections import OrderedDict

import numpy as np
import pytest

from benchmark import faults, harness

CELLS = ("store_sales_sf10.verify_hourly", "customer_sf1000.verify_strings")
SEED = 2**31 + 8765


def small(name):
    cell = harness.load_cell(name)
    src = cell.traffic["source"]
    if src["kind"] == "hourly":
        src["partition_rows"] = 40_000 // src["partitions"]
    else:
        cell.config["rows"] = 100_000
        src["shards"] = 2
    return cell


@pytest.fixture(autouse=True)
def fresh_program(monkeypatch, tmp_path):
    """A fresh plan cache (a fault must be traced into the program), the
    program's options restored after the run, and a work directory of
    the test's own."""
    from deequ_tpu import config
    from deequ_tpu.engine import scan

    monkeypatch.setattr(scan, "_PLAN_CACHE", OrderedDict())
    monkeypatch.setattr(config, "_options", config.options())
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))


def run(name, **kw):
    result = harness.run_cell(small(name), SEED, 0.5, False,
                              time.perf_counter(), require_tpu=False,
                              peaks={}, **kw)
    assert result is not None
    return result


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    result = run(name)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    result = run(name, control=True)
    assert not result["correct"]
    checks = result["checks"]
    assert checks["wide_rel_err"]["value"] > checks["wide_rel_err"]["limit"]


@pytest.mark.parametrize("name,fault", [
    (c, f) for c in CELLS
    for f in ("state_unchanged", "half_batch", "answer_altered")])
def test_a_planted_fault_is_not_correct(name, fault, monkeypatch):
    faults.plant(fault, monkeypatch.setattr)
    assert not run(name)["correct"]


def test_a_lane_hashing_every_string_alike_is_not_correct(monkeypatch):
    """Every string hashed to one value: the exact counts survive (the
    lane settles collisions from the values), the HLL estimates do
    not."""
    from deequ_tpu.data import strings

    monkeypatch.setattr(strings, "hash64",
                        lambda arr: np.zeros(len(arr), dtype=np.uint64))
    result = run("customer_sf1000.verify_strings")
    assert not result["correct"]
    checks = result["checks"]
    assert checks["hll_rel_err"]["value"] > checks["hll_rel_err"]["limit"]
    assert checks["exact_mismatch"]["value"] == 0


def test_the_lane_cell_reads_its_layer():
    """The traced line of the customer cell carries the lane's span
    metric, and no dictionary pre-pass runs over a lane column."""
    cell = small("customer_sf1000.verify_strings")
    result = harness.run_cell(cell, SEED, 0.5, True, time.perf_counter(),
                              require_tpu=False, peaks={})
    metrics = result["metrics"]
    assert metrics["string_lane_s_per_mrow"]["value"] > 0
    # the fused scan plus the numeric key's and the birth country's
    # dictionary pre-passes; the lane columns add none
    assert metrics["data_passes_per_run"]["value"] == 3.0
