"""Compile the main path's device programs for a described v5e chip,
without one (on-chip-measurement guide §2): what the TPU compiler
refuses here would otherwise cost a chip run to find.

The topology is described inside a module fixture, never at import:
only one process may load libtpu, and every pytest-xdist worker imports
this file. The persistent compile cache is off around the compiles (an
entry written for a described chip cannot be read back without one).
Where code branches on ``jax.default_backend()`` the test steers it
with monkeypatch; nothing here runs, so nothing here is a chip result.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

ROWS = 1 << 21  # the engine's real batch (engine/scan.py DEFAULT_MAX_BATCH)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), np.asarray(x).dtype if not hasattr(x, "dtype")
            else x.dtype, sharding=sharding,
        ),
        tree,
    )


def test_batch_size_is_the_engines(one_chip):
    from deequ_tpu.engine import scan

    assert scan.DEFAULT_MAX_BATCH == ROWS


def test_fused_scan_step(one_chip):
    """__graft_entry__.entry()'s fused analyzer step at 2^21 rows."""
    import __graft_entry__

    fn, (states, batch) = __graft_entry__.entry(ROWS)
    compiled = (
        jax.jit(fn)
        .lower(_shapes(states, one_chip), _shapes(batch, one_chip))
        .compile()
    )
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize(
    "cols, rows, m",
    [
        (8, ROWS, 1 << 14),  # production: C columns of one 2^21 batch
        (3, 5000, 1 << 14),  # a short tail batch, padded to the tile
        (2, 4096, 1024),  # the availability probe's own shape
    ],
)
def test_pallas_scatter(one_chip, cols, rows, m):
    """The repaired HLL scatter kernel lowers through Mosaic."""
    from deequ_tpu.sketches import hll, pallas_scatter

    assert hll.M == 1 << 14
    idx = jax.ShapeDtypeStruct((cols, rows), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(
            lambda i, r: pallas_scatter._scatter_max_call(i, r, m, False)
        )
        .lower(idx, idx)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_spill_finalize_u64(one_chip):
    """The device-sort spill finalize for one u64 key lane at 2^21."""
    from deequ_tpu.analyzers import spill

    keys = jax.ShapeDtypeStruct((ROWS,), jnp.uint64, sharding=one_chip)
    n_sentinel = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    spill._finalize_fn().lower(keys, n_sentinel).compile()


def test_f64_spill_collector_tpu_branch(one_chip, monkeypatch):
    """An f64 grouping key on the TPU branch: canonical u64 bits are
    packed on the host (the chip has no f64 bitcast) and the collector
    update appends them inside the fused scan."""
    from deequ_tpu import Dataset
    from deequ_tpu.analyzers import spill
    from deequ_tpu.analyzers.grouping import FrequencyPlan
    from deequ_tpu.data.table import ColumnRequest
    from deequ_tpu.engine import AnalysisEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(0)
    ds = Dataset.from_pydict({"v": rng.normal(size=ROWS)})
    spec = spill.single_collector_spec(
        ds, FrequencyPlan(("v",), None, False), AnalysisEngine()
    )
    assert ColumnRequest("v", "u64bits") in spec.requests
    batch = next(iter(ds.device_batches(spec.requests, ROWS)))
    state = spec.ops.init()
    compiled = (
        jax.jit(spec.ops.update)
        .lower(_shapes(state, one_chip), _shapes(batch, one_chip))
        .compile()
    )
    assert compiled.memory_analysis() is not None


def test_dp4_mesh_step_returns_its_carry(topo, monkeypatch):
    """chip_smoke's suite as one fused step on a 4-chip ``dp`` mesh: the
    TPU compiler returns the carry with the shardings the init carry is
    given, so step 2 reuses step 1's program (the retrace found on 4
    chips in PR 21). The step is captured from a small run on 4 CPU
    devices and recompiled for the described chips."""
    from collections import OrderedDict

    from jax.sharding import Mesh, NamedSharding

    import chip_smoke
    from deequ_tpu import Dataset
    from deequ_tpu.analyzers import spill
    from deequ_tpu.engine import AnalysisEngine, scan

    monkeypatch.setattr(spill, "_FORCE_HOST_F64_BITS", True)  # TPU branch
    monkeypatch.setattr(scan, "_PLAN_CACHE", OrderedDict())
    calls = []
    lookup = scan._plan_cache_lookup

    def capturing(key, make_entry):
        entry, hit = lookup(key, make_entry)
        fn = entry.fn
        entry.fn = lambda *args: calls.append((fn, args)) or fn(*args)
        return entry, hit

    monkeypatch.setattr(scan, "_plan_cache_lookup", capturing)
    table = chip_smoke.build_table(20_000, chip_smoke.COLS, seed=0)
    cpu_mesh = Mesh(np.array(jax.devices("cpu")[:4]), ("dp",))
    chip_smoke.phase_verify(
        Dataset.from_arrow(table), chip_smoke.Reference(table), "dp4",
        AnalysisEngine(mesh=cpu_mesh),
    )
    fn, args = calls[0]

    chips = Mesh(np.array(topo.devices[:4]), ("dp",))

    def described(x):
        sharding = getattr(x, "sharding", None)
        if isinstance(sharding, NamedSharding):  # committed to the mesh
            sharding = NamedSharding(chips, sharding.spec)
        else:  # uncommitted: placed by the compiler
            sharding = None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    specs = jax.tree_util.tree_map(described, args)
    compiled = fn.lower(*specs).compile()
    carry_in = jax.tree_util.tree_leaves(specs[0])
    carry_out = jax.tree_util.tree_leaves(compiled.output_shardings[0])
    assert len(carry_in) == len(carry_out) > 40
    for given, got in zip(carry_in, carry_out):
        assert given.sharding is not None
        assert given.sharding.is_equivalent_to(got, len(given.shape))
    assert "all-reduce" in compiled.as_text()
