"""HyperLogLog primitives for the device pass.

Reference: ``analyzers/catalyst/StatefulHyperloglogPlus`` (SURVEY.md
§2.3): HLL++ registers as packed words updated per row inside Tungsten;
merge = word-wise max. TPU design (per SURVEY's table): registers are an
int32[m] device vector; the per-batch update is hash -> leading-zero
count -> register max (a scatter-max; on a TPU a banded one-hot
matmul on the MXU, sketches/hll_mxu.py); the merge is an elementwise
max (a ``lax.max`` all-reduce across the mesh / across persisted
states).

Hashing is built from 32-bit lanes ONLY — the TPU has no native 64-bit
integer path (XLA's x64 rewriter refuses u64 bitcasts), and 32-bit
murmur-style mixing maps perfectly onto the VPU:

- integral columns split the raw int64 payload into (hi u32, lo u32) —
  exact for the full 64-bit range (IDs, epoch nanos); floating columns
  canonicalize to a (float32, float32 residual) pair, stable across
  f32/f64 storage of equal values;
- the word pair mixes through murmur3's 32-bit finalizer into two
  independent 32-bit hashes: h1 supplies the register index (top
  P bits), h2 supplies the leading-zero rank;
- strings hash host-side with one 64-bit string hash
  (data/strings.hash64), split into its two u32 words: once per
  dictionary entry into device lookup tables gathered by code, or once
  per row of a hashed-string lane column, which ships the u64 itself
  (:func:`lane_hash_pair`). Either way a string lands in the same
  register.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

P = 14  # precision: m = 2^14 registers => ~0.8% relative error
M = 1 << P

_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)


def fmix32(h: jnp.ndarray) -> jnp.ndarray:
    """murmur3 32-bit finalizer (avalanche); h: uint32 array."""
    h = h ^ (h >> 16)
    h = h * _C1
    h = h ^ (h >> 13)
    h = h * _C2
    h = h ^ (h >> 16)
    return h


def hash_pair_numeric(
    values: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Produce two independent u32 hashes per value, dispatching on the
    column dtype:

    - **integral/boolean** columns hash the RAW 64-bit payload as two
      u32 words (hi/lo via shifts) — exact for the full int64 range.
      Float canonicalization here would collide catastrophically above
      2^53 (snowflake IDs, epoch nanos): the reference's HLL++ hashes
      the raw long, so must we.
    - **floating** columns canonicalize to (float32 hi, float32
      residual) — exact for floats and stable across f32/f64 storage of
      equal values.
    """
    if jnp.issubdtype(values.dtype, jnp.floating):
        # -0.0 -> +0.0 via where, NOT `+ 0.0`: XLA's algebraic
        # simplifier elides add(x, 0) inside larger graphs (observed
        # inside lax.cond branches, r5), which would make the hash of
        # -0.0 depend on compilation context
        as_f64 = values.astype(jnp.float64)
        as_f64 = jnp.where(as_f64 == 0.0, 0.0, as_f64)
        hi = as_f64.astype(jnp.float32)
        lo = (as_f64 - hi.astype(jnp.float64)).astype(jnp.float32)
        lo = jnp.where(lo == 0.0, jnp.float32(0.0), lo)
        hi_bits = jax.lax.bitcast_convert_type(hi, jnp.uint32)
        lo_bits = jax.lax.bitcast_convert_type(lo, jnp.uint32)
    else:
        as_i64 = values.astype(jnp.int64)
        lo_bits = (as_i64 & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
        hi_bits = (
            (as_i64 >> jnp.int64(32)) & jnp.int64(0xFFFFFFFF)
        ).astype(jnp.uint32)
    h1 = fmix32(lo_bits ^ fmix32(hi_bits ^ _GOLDEN))
    h2 = fmix32(hi_bits ^ fmix32(lo_bits ^ _C1))
    return h1, h2


def dictionary_hash_pairs(
    dictionary,
) -> Tuple[np.ndarray, np.ndarray]:
    """Stable (u32, u32) hash per dictionary entry (host-side, once, in
    vectorized form): the low and high words of the string hash every
    path shares (data/strings.hash64). Null entries hash to (0, 0)."""
    from deequ_tpu.data import strings

    arr = strings.dictionary_values(dictionary)
    if len(arr) == 0:
        return np.zeros(1, dtype=np.uint32), np.zeros(1, dtype=np.uint32)
    return strings.hash_pairs(arr)


def lane_hash_pair(h: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Device: a hashed-string lane's u64 row hashes -> (h1, h2), the
    same low and high words ``dictionary_hash_pairs`` gives the entry."""
    lo = (h & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (h >> jnp.uint64(32)).astype(jnp.uint32)
    return lo, hi


def _index_and_rank(h1, h2, mask):
    """THE one (register index, rho rank) derivation — the single and
    column-stacked update paths must share it: divergence here would put
    equal values in different registers, and a max-merge of states from
    the two paths would then double-count (the v1/v2 hazard documented
    in analyzers/states.py STATE_FORMAT_VERSIONS)."""
    idx = (h1 >> np.uint32(32 - P)).astype(jnp.int32)
    rho = jnp.minimum(jax.lax.clz(h2) + 1, 33).astype(jnp.int32)
    return jnp.where(mask, idx, 0), jnp.where(mask, rho, 0)


REGISTER_DTYPE = jnp.int8  # rho <= 33 fits i8: 4x fewer wire bytes than
# i32 when states cross to the host (every build runs in i32 — narrow
# scatters lower poorly — and the result narrows after)


def register_build_path() -> str:
    """Which register build a freshly traced plan bakes in: ``mxu``
    (the banded one-hot matmul, on a TPU) or ``xla`` (the scatter-max,
    every other backend). Rides the engine plan-cache key so another
    backend retraces instead of aliasing a stale compile. Both give the
    same registers."""
    from deequ_tpu.sketches import hll_mxu

    return "mxu" if hll_mxu.available() else "xla"


# (mesh, axis) of the mesh whose rows a plan is being traced for: XLA
# cannot partition a Mosaic kernel, so on a mesh each device builds the
# registers of its own rows and a max all-reduce merges them (the engine
# sets it around its row-sharded updates; None off a mesh)
_ROW_MESH = contextvars.ContextVar("hll_row_mesh", default=None)


@contextlib.contextmanager
def rows_sharded_over(mesh, axis: str):
    """Trace register builds inside as row-sharded over ``mesh``'s
    ``axis`` (``mesh`` None: one device)."""
    token = _ROW_MESH.set(None if mesh is None else (mesh, axis))
    try:
        yield
    finally:
        _ROW_MESH.reset(token)


def _per_device(kernel, idx, rho):
    """Run a kernel build per device over the rows of the mesh the plan
    is traced for, merged with a max; B pads with (0, 0) no-op rows to
    a multiple of the axis size."""
    from jax.sharding import PartitionSpec

    mesh, axis = _ROW_MESH.get()
    n = mesh.shape[axis]
    pad = -idx.shape[1] % n
    if pad:
        idx = jnp.pad(idx, ((0, 0), (0, pad)))
        rho = jnp.pad(rho, ((0, 0), (0, pad)))
    rows = PartitionSpec(None, axis)
    return jax.shard_map(
        lambda i, r: jax.lax.pmax(kernel(i, r), axis),
        mesh=mesh.abstract_mesh,
        in_specs=(rows, rows),
        out_specs=PartitionSpec(),
        check_vma=False,
    )(idx, rho)


def _build_registers(idx: jnp.ndarray, rho: jnp.ndarray) -> jnp.ndarray:
    """(C, B) register indices and ranks -> (C, M) int8 registers, the
    max rank per register, through the build this backend runs."""
    from deequ_tpu.sketches import hll_mxu
    from deequ_tpu.telemetry import get_telemetry

    path = register_build_path()
    get_telemetry().metrics.counter(f"hll.register_builds.{path}").inc()
    if path == "mxu":
        kernel = functools.partial(hll_mxu.registers, m=M)
        regs = (
            kernel(idx, rho)
            if _ROW_MESH.get() is None
            else _per_device(kernel, idx, rho)
        )
    else:  # one scatter-max into a flat (C*M,) file, col*M + idx
        n_cols = idx.shape[0]
        col_ids = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 0)
        regs = (
            jnp.zeros(n_cols * M, dtype=jnp.int32)
            .at[(col_ids * M + idx).ravel()]
            .max(rho.ravel())
            .reshape(n_cols, M)
        )
    return regs.astype(REGISTER_DTYPE)


def registers_from_hash_pair(
    h1: jnp.ndarray, h2: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """One batch of hash pairs -> int8[M] register vector.

    rho comes from h2's leading zeros (1..33) — supporting max register
    rank 33, ample for cardinalities far beyond 2^40."""
    idx, rho = _index_and_rank(h1, h2, mask)
    return _build_registers(idx[None, :], rho[None, :])[0]


def registers_from_hash_pair_stacked(
    h1: jnp.ndarray, h2: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """Column-stacked variant: (C, B) hash pairs -> (C, M) registers in
    ONE build for the whole group (a (C, G) grid on the kernels, one
    flat scatter on XLA) — bit-identical either way."""
    idx, rho = _index_and_rank(h1, h2, mask)
    return _build_registers(idx, rho)


# dict sizes up to this use the presence path (measured on v5e: the
# compare-reduce beats the per-row gather+scatter at every D tested up
# to 4096 — 261ms -> ~0ms at D=64, 261ms -> 57ms at D=4096 for a
# (4, 2^21) block; crossover extrapolates to D ~ 16k. docs/PERF.md.)
PRESENCE_DICT_CAP = 4096

# D-axis tile for the presence compare-reduce (bounds the (C, TILE, B)
# intermediate if a backend fails to fuse it; see
# registers_from_code_presence)
_PRESENCE_D_TILE = 256


def registers_from_code_presence(
    codes: jnp.ndarray,  # (C, B) int codes, -1 = null
    mask: jnp.ndarray,  # (C, B) validity (row mask pre-ANDed)
    lut1: jnp.ndarray,  # (C, D) u32 per-dictionary-entry hashes
    lut2: jnp.ndarray,
) -> jnp.ndarray:
    """Registers for dict-encoded columns WITHOUT touching rows with a
    scatter: a register's value is the max rho over the DISTINCT values
    present, so scattering each dictionary entry once, masked by
    whether its code occurs in the batch, yields bit-identical
    registers to scattering every row (max over duplicates ==
    single occurrence). Presence is a (C, D, B)->(C, D) compare-reduce
    the VPU eats at full rate, vs one serialized scatter element per
    ROW (~145M elem/s measured) on the per-row path. Null codes (-1)
    match no dictionary slot and vanish."""
    present = tiled_code_presence(codes, mask, lut1.shape[1], count=False)
    return registers_from_hash_pair_stacked(lut1, lut2, present)


def tiled_code_presence(
    codes: jnp.ndarray,  # (C, B) int codes, -1 = null
    mask: jnp.ndarray,  # (C, B) validity
    D: int,
    count: bool,
) -> jnp.ndarray:
    """(C, D) per-dictionary-slot presence (``count=False``, bool) or
    occurrence counts (``count=True``, i32) via the compare-reduce.

    The D axis is chunked so the (C, TILE, B) intermediate stays
    bounded even on a backend where XLA does NOT fuse the compare into
    the reduce (at the D=4096 cap with B=2^21 an unfused full-D
    intermediate would be tens of GB — r4 advisory). TILE=256 keeps
    the worst case ~2 GB/column-block and measured the same as the
    unchunked form (the reduce dominates either way). Shared by the
    HLL presence path here and DataType's count path
    (analyzers/datatype.py) so the tiling can never diverge."""
    codes_i32 = codes.astype(jnp.int32)
    tile = min(D, _PRESENCE_D_TILE)
    parts = []
    for d0 in range(0, D, tile):
        d = jnp.arange(d0, min(d0 + tile, D), dtype=jnp.int32)
        hits = (codes_i32[:, None, :] == d[None, :, None]) & mask[:, None, :]
        parts.append(
            hits.sum(axis=2, dtype=jnp.int32) if count else hits.any(axis=2)
        )
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


_Q = 32  # h2 supplies 32 bits => register ranks 0..Q+1


def _sigma(x: float) -> float:
    """Ertl's σ series (linear-counting correction term)."""
    if x == 1.0:
        return float("inf")
    y = 1.0
    z = x
    while True:
        x = x * x
        z_prev = z
        z = z + x * y
        y = y + y
        if z == z_prev:
            return z


def _tau(x: float) -> float:
    """Ertl's τ series (saturated-register correction term)."""
    if x == 0.0 or x == 1.0:
        return 0.0
    y = 1.0
    z = 1.0 - x
    while True:
        x = np.sqrt(x)
        z_prev = z
        y = 0.5 * y
        z = z - (1.0 - x) ** 2 * y
        if z == z_prev:
            return z / 3.0


def estimate(registers: np.ndarray) -> float:
    """Ertl's improved raw estimator ("New cardinality estimation
    algorithms for HyperLogLog sketches", Ertl 2017, Alg. 6): unbiased
    across the whole range with NO empirical bias tables and no
    linear-counting/raw switchover — strictly better than the original
    HLL estimator's biased transition region (~2.5m..5m), which is what
    the reference corrects with HLL++'s lookup tables."""
    registers = np.asarray(registers)
    m = float(M)
    counts = np.bincount(
        registers.astype(np.int64), minlength=_Q + 2
    ).astype(np.float64)
    z = m * _tau(1.0 - counts[_Q + 1] / m)
    for k in range(_Q, 0, -1):
        z = 0.5 * (z + counts[k])
    z = z + m * _sigma(counts[0] / m)
    alpha_inf = 1.0 / (2.0 * np.log(2.0))
    return float(alpha_inf * m * m / z)
