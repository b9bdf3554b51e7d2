"""CPU rehearsals of chip_smoke.py's phases at a tiny size.

The script itself runs only on a TPU (``main()`` refuses anything
else); these tests call its phase functions directly, each against the
same plain numpy/pyarrow reference the chip run uses.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke
from deequ_tpu import Dataset

ROWS = 20_000


@pytest.fixture(scope="module")
def table():
    return chip_smoke.build_table(ROWS, chip_smoke.COLS, seed=0)


@pytest.fixture
def ds_ref(table):
    return Dataset.from_arrow(table), chip_smoke.Reference(table)


def test_table_shape(table):
    assert table.num_rows == ROWS
    assert table.num_columns == 50
    types = {str(f.type) for f in table.schema}
    assert {"double", "int64", "float"} <= types


def test_verify_phase(ds_ref):
    ds, ref = ds_ref
    values = chip_smoke.phase_verify(ds, ref)
    assert len(values) == len(chip_smoke.expected_metrics(ref))


def test_verify_phase_catches_a_wrong_metric(ds_ref, monkeypatch):
    """The comparison is live: a reference off by one row fails it."""
    ds, ref = ds_ref
    monkeypatch.setattr(ref, "n", ref.n + 1)
    with pytest.raises(chip_smoke.Mismatch):
        chip_smoke.phase_verify(ds, ref)


def test_profile_phase(ds_ref):
    chip_smoke.phase_profile(*ds_ref)


def test_stream_phase(table, tmp_path):
    chip_smoke.phase_stream(table, ROWS // 2, str(tmp_path))
    assert os.listdir(tmp_path) == []  # parquet shards cleaned up


def test_pallas_phase(ds_ref, monkeypatch):
    from deequ_tpu.sketches import pallas_scatter

    monkeypatch.setenv("DEEQU_TPU_PALLAS_INTERPRET", "1")
    pallas_scatter._reset_probe_for_tests()
    try:
        chip_smoke.phase_pallas(*ds_ref)
    finally:
        monkeypatch.delenv("DEEQU_TPU_PALLAS_INTERPRET")
        pallas_scatter._reset_probe_for_tests()


def test_mesh_phase_on_four_devices(ds_ref, monkeypatch):
    """Five steps on a fresh plan cache: a carry whose sharding changes
    after step 1 (the retrace found on 4 chips in PR 21) fails it."""
    from collections import OrderedDict

    from deequ_tpu.engine import scan

    monkeypatch.setattr(scan, "_PLAN_CACHE", OrderedDict())
    devices = jax.devices()
    assert len(devices) >= 4
    chip_smoke.phase_mesh(*ds_ref, devices[:4], batch_size=ROWS // 5)


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert "ok" not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Without the rest of the repo the script exits non-zero and
    prints no result."""
    shutil.copy(chip_smoke.__file__, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
