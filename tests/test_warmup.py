"""tools/warmup.py: schema-driven synthetic data must hit the same
static compile decisions as production data (kinds, wire dtypes,
nullability) so precompiled plans actually get reused."""

import numpy as np
import pyarrow as pa
import pytest

from deequ_tpu import config
from deequ_tpu.profiles.profiler import ColumnProfiler

from tools.warmup import _schema_from_parquet, synthetic_dataset, warm_once


SCHEMA = {
    "f": "float32",
    "d": "float64",
    "i": "int64",
    "s": "string",
    "b": "bool",
    "t": "timestamp",
}


def test_synthetic_dataset_matches_schema_kinds():
    ds = synthetic_dataset(SCHEMA, 1000, nullable=True, wide_ints=True)
    # high-card strings widen the code dtype (a distinct program)
    wide_s = synthetic_dataset(
        SCHEMA, 1000, nullable=False, wide_ints=False,
        high_card_strings=True,
    )
    from deequ_tpu.data.table import ColumnRequest as _CR

    assert wide_s.materialize(_CR("s", "codes")).dtype == np.int16
    kinds = {f.name: f.kind.name for f in ds.schema.fields}
    assert kinds == {
        "f": "FRACTIONAL",
        "d": "FRACTIONAL",
        "i": "INTEGRAL",
        "s": "STRING",
        "b": "BOOLEAN",
        "t": "TIMESTAMP",
    }
    # nullable=True must produce real masks (compiles differ)
    assert ds.table.column("f").null_count > 0
    # wide ints must NOT narrow to i32 (a narrowed program differs)
    from deequ_tpu.data.table import ColumnRequest

    assert ds.materialize(ColumnRequest("i", "values")).dtype == np.int64
    narrow = synthetic_dataset(SCHEMA, 1000, nullable=False, wide_ints=False)
    assert (
        narrow.materialize(ColumnRequest("i", "values")).dtype == np.int32
    )


def test_warm_once_runs_and_plan_is_reused():
    schema = {"x": "float32", "s": "string"}
    with config.configure(batch_size=512):
        warm_once(schema, 512, nullable=False, wide_ints=False, suite=False)
        # a fresh same-schema dataset reuses the in-process plan cache
        from deequ_tpu.engine.scan import AnalysisEngine

        engine = AnalysisEngine(batch_size=512)
        ds = synthetic_dataset(schema, 512, False, False, seed=7)
        ColumnProfiler.profile(ds, engine=engine)
        assert engine.plan_cache_hit or engine.trace_count == 0


def test_schema_from_parquet(tmp_path):
    import pyarrow.parquet as pq

    tbl = pa.table(
        {
            "a": pa.array([1.5], pa.float32()),
            "b": pa.array([1], pa.int64()),
            "c": pa.array(["x"]).dictionary_encode(),
        }
    )
    pq.write_table(tbl, str(tmp_path / "t.parquet"))
    assert _schema_from_parquet(str(tmp_path / "t.parquet")) == {
        "a": "float32",
        "b": "int64",
        "c": "string",
    }


# -- where the persistent compile cache lives (what warmup fills) -------


@pytest.mark.parametrize(
    "env, want",
    [
        ({"JAX_COMPILATION_CACHE_DIR": "/elsewhere/jax"}, "/elsewhere/jax"),
        ({}, config.REPO_CACHE_DIR),
        (
            {"DEEQU_TPU_COMPILE_CACHE": "",
             "JAX_COMPILATION_CACHE_DIR": "/elsewhere/jax"},
            "",
        ),
    ],
)
def test_compile_cache_dir_order(monkeypatch, env, want):
    for name in ("JAX_COMPILATION_CACHE_DIR", "DEEQU_TPU_COMPILE_CACHE"):
        monkeypatch.delenv(name, raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    assert config._default_compile_cache_dir() == want


@pytest.mark.parametrize("jax_owns_it", [True, False])
def test_install_sets_the_dir_only_when_jax_does_not(
    monkeypatch, tmp_path, jax_owns_it
):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself: nothing is set in
    code then. The torn-write-safe store is installed either way."""
    import jax

    from deequ_tpu.engine import compile_cache

    cache_dir = str(tmp_path / "cache")
    if jax_owns_it:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates, installed = [], []
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append(k))
    monkeypatch.setattr(
        compile_cache, "install", lambda d: installed.append(d) or True
    )
    monkeypatch.setattr(config, "_compile_cache_installed", False)
    with config.configure(compilation_cache_dir=cache_dir):
        config.install_compilation_cache()
    assert installed == [cache_dir]
    assert ("jax_compilation_cache_dir" in updates) != jax_owns_it


def test_install_failure_is_reported(monkeypatch, tmp_path):
    from deequ_tpu.engine import compile_cache

    cache_dir = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    monkeypatch.setattr(compile_cache, "install", lambda d: False)
    monkeypatch.setattr(config, "_compile_cache_installed", False)
    with config.configure(compilation_cache_dir=cache_dir):
        with pytest.warns(RuntimeWarning, match="not installed"):
            config.install_compilation_cache()
