"""HyperLogLog primitives for the device pass.

Reference: ``analyzers/catalyst/StatefulHyperloglogPlus`` (SURVEY.md
§2.3): HLL++ registers as packed words updated per row inside Tungsten;
merge = word-wise max. TPU design (per SURVEY's table): registers are an
int32[m] device vector; the per-batch update is hash -> leading-zero
count -> scatter-max; the merge is an elementwise max (a ``lax.max``
all-reduce across the mesh / across persisted states).

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
- strings hash host-side ONCE per dictionary entry (blake2b-8, split
  into two u32 words) into device lookup tables gathered by code.
"""

from __future__ import annotations

import hashlib
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
    dictionary: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray]:
    """Stable (u32, u32) hash per dictionary entry (host-side, once)."""
    n = max(len(dictionary), 1)
    h1 = np.zeros(n, dtype=np.uint32)
    h2 = np.zeros(n, dtype=np.uint32)
    for i, value in enumerate(dictionary):
        if value is None:
            continue
        digest = hashlib.blake2b(
            str(value).encode("utf-8"), digest_size=8
        ).digest()
        words = np.frombuffer(digest, dtype=np.uint32)
        h1[i], h2[i] = words[0], words[1]
    return h1, h2


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
# i32 when states cross to the host (the scatter itself runs in i32 —
# narrow scatters lower poorly — and the result narrows after)


def registers_from_hash_pair(
    h1: jnp.ndarray, h2: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """One batch of hash pairs -> int8[M] register vector (scatter-max).

    rho comes from h2's leading zeros (1..33) — supporting max register
    rank 33, ample for cardinalities far beyond 2^40."""
    idx, rho = _index_and_rank(h1, h2, mask)
    from deequ_tpu.sketches import pallas_scatter

    pallas = pallas_scatter.scatter_max(idx[None, :], rho[None, :], M)
    if pallas is not None:
        return pallas[0].astype(REGISTER_DTYPE)
    return (
        jnp.zeros(M, dtype=jnp.int32)
        .at[idx]
        .max(rho)
        .astype(REGISTER_DTYPE)
    )


def registers_from_hash_pair_stacked(
    h1: jnp.ndarray, h2: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """Column-stacked variant: (C, B) hash pairs -> (C, M) registers via
    ONE scatter-max into a flat (C*M,) vector (per-column register
    blocks indexed by col*M + idx). Behind ``config.pallas_scatter``
    the unroll-16 SMEM kernel takes over with a (C, G) grid (a flat
    C*M register file exceeds SMEM) — bit-identical either way."""
    idx, rho = _index_and_rank(h1, h2, mask)
    from deequ_tpu.sketches import pallas_scatter

    pallas = pallas_scatter.scatter_max(idx, rho, M)
    if pallas is not None:
        return pallas.astype(REGISTER_DTYPE)
    n_cols = idx.shape[0]
    col_ids = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 0)
    flat = (col_ids * M + idx).ravel()
    return (
        jnp.zeros(n_cols * M, dtype=jnp.int32)
        .at[flat]
        .max(rho.ravel())
        .reshape(n_cols, M)
        .astype(REGISTER_DTYPE)
    )


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


# ------------------------------------------------------------------
# adaptive sorted-dedup update for numeric columns (r5)
# ------------------------------------------------------------------

# Registers only see DISTINCT values (register = max over duplicates),
# so a column whose per-batch distinct count U fits a static dictionary
# can sort the batch, compact the uniques, and scatter U elements
# instead of B. Measured on v5e (docs/PERF.md r5 table): sort 3.6 ms +
# compaction 2.9 ms vs 15.2 ms for the full per-row scatter at
# B = 2^21 — 2.3x for mid-cardinality columns (TPC-DS quantities,
# cent-denominated prices). High-cardinality columns keep the full
# scatter: the path is gated per GROUP by a linear-counting estimate
# from the CARRIED registers, so batch 1 (empty state) and any
# high-cardinality history never pay the sort.
DEDUP_DICT_CAP = 16384

# zeros > gate  <=>  linear-counting estimate -M*ln(zeros/M) < ~12k
# (margin below DEDUP_DICT_CAP so the inner exact U <= D check rarely
# has to fall back mid-branch)
_DEDUP_ZEROS_GATE = int(M * np.exp(-0.75))


def dedup_gate(registers: jnp.ndarray) -> jnp.ndarray:
    """(..., M) carried registers -> (...,) bool: the state's linear-
    counting estimate says this column is mid-cardinality. All-zero
    registers (first batch / empty column) gate FALSE: with no
    history the full scatter is the safe choice."""
    zeros = jnp.sum(registers == 0, axis=-1)
    return (zeros < M) & (zeros > _DEDUP_ZEROS_GATE)


def _dedup_supported(dtype) -> bool:
    """Sorted dedup needs a total order and a free sentinel: any real
    float or integer dtype qualifies (bool is NOT an integer subtype,
    so two-value boolean columns keep the plain scatter)."""
    return jnp.issubdtype(dtype, jnp.floating) or jnp.issubdtype(
        dtype, jnp.integer
    )


def dedup_column_registers(
    xc: jnp.ndarray,  # (B,) values
    maskc: jnp.ndarray,  # (B,) validity
) -> jnp.ndarray:
    """(M,) batch registers for ONE column via sort + unique
    compaction. Bit-identical to the per-row scatter: the dictionary
    entries are the batch's own values, hashed by the SAME
    hash_pair_numeric, and max over duplicates == single occurrence.

    Sentinel discipline: masked slots sort as ``sentval`` (+inf for
    floats, iinfo.max for ints), which excludes them from the unique
    run — a REAL sentinel-valued element (or NaN, floats only) is
    re-added as a flagged extra dictionary slot. Exotic NaN payloads
    collapse to the canonical NaN here (the per-row path hashes raw
    payload bits); both orderings count NaN as one value on canonical
    data, and states from the two paths still max-merge safely.

    A column whose ACTUAL U exceeds the cap falls back to its own full
    scatter inside the branch (correctness never depends on the
    caller's gate estimate)."""
    (B,) = xc.shape
    floating = jnp.issubdtype(xc.dtype, jnp.floating)
    D = min(DEDUP_DICT_CAP, B)
    if floating:
        sentval = jnp.asarray(jnp.inf, xc.dtype)
        nan_mask = jnp.isnan(xc)
        keys = jnp.where(maskc & ~nan_mask, xc, sentval)
        sent_flag = jnp.any((xc == sentval) & maskc)
        nan_flag = jnp.any(nan_mask & maskc)
        nan_entry = jnp.asarray(jnp.nan, xc.dtype)
    else:
        sentval = jnp.asarray(jnp.iinfo(xc.dtype).max, xc.dtype)
        keys = jnp.where(maskc, xc, sentval)
        sent_flag = jnp.any((xc == sentval) & maskc)
        nan_flag = jnp.asarray(False)
        nan_entry = sentval  # dead slot (flag stays False)

    s = jnp.sort(keys)
    uniq = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), s[1:] != s[:-1]]
    )
    real_u = uniq & (s < sentval)  # NaN compares False too
    U = jnp.sum(real_u).astype(jnp.int32)

    def dict_path():
        targets = jnp.arange(1, D + 1, dtype=jnp.int32)
        slot = jnp.arange(D, dtype=jnp.int32)
        ranks = jnp.cumsum(real_u.astype(jnp.int32))
        pos = jnp.searchsorted(ranks, targets)
        entries = s[jnp.clip(pos, 0, B - 1)]
        full = jnp.concatenate(
            [entries, jnp.stack([sentval, nan_entry])]
        )
        valid = jnp.concatenate(
            [slot < U, jnp.stack([sent_flag, nan_flag])]
        )
        h1, h2 = hash_pair_numeric(full)
        return registers_from_hash_pair(h1, h2, valid)

    def scatter_path():
        return _scatter_column(xc, maskc)

    return jax.lax.cond(U <= D, dict_path, scatter_path)


def dedup_column_registers_from_sorted(
    s: jnp.ndarray,  # (B,) PRE-SORTED keys: invalid/non-finite -> +inf
    xc: jnp.ndarray,  # (B,) raw values (flag probes + fallback scatter)
    maskc: jnp.ndarray,  # (B,) validity
) -> jnp.ndarray:
    """(M,) batch registers from an ALREADY-SORTED key array — the
    KLL group's masked f32 sort (engine/vectorize._kll_sorted_stack),
    which maps nulls AND every non-finite value to the +inf sentinel.
    The three non-finite values (+inf, -inf, NaN) are therefore absent
    from the unique run and re-enter as flagged extra dictionary
    slots, probed from the raw column. Bit-identity caveats match
    dedup_column_registers (canonical-NaN collapse).

    INTEGER columns may ride the same f32 pool when the planner has
    proven their range fits the 24-bit mantissa (f32 cast exact):
    dictionary entries cast BACK to the raw dtype before hashing, so
    they take hash_pair_numeric's integral path bit-identically to the
    per-row scatter; the non-finite extras are impossible for int data
    (their flags are always False) and their cast garbage is masked."""
    (B,) = s.shape
    D = min(DEDUP_DICT_CAP, B)
    sentval = jnp.asarray(jnp.inf, s.dtype)
    uniq = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), s[1:] != s[:-1]]
    )
    real_u = uniq & (s < sentval)
    U = jnp.sum(real_u).astype(jnp.int32)
    integral = not jnp.issubdtype(xc.dtype, jnp.floating)
    if integral:
        false = jnp.asarray(False)
        pos_inf = neg_inf = nan_flag = false
    else:
        pos_inf = jnp.any((xc == jnp.inf) & maskc)
        neg_inf = jnp.any((xc == -jnp.inf) & maskc)
        nan_flag = jnp.any(jnp.isnan(xc) & maskc)

    def dict_path():
        targets = jnp.arange(1, D + 1, dtype=jnp.int32)
        slot = jnp.arange(D, dtype=jnp.int32)
        ranks = jnp.cumsum(real_u.astype(jnp.int32))
        pos = jnp.searchsorted(ranks, targets)
        entries = s[jnp.clip(pos, 0, B - 1)]
        extras = jnp.asarray([jnp.inf, -jnp.inf, jnp.nan], s.dtype)
        full = jnp.concatenate([entries, extras]).astype(xc.dtype)
        valid = jnp.concatenate(
            [slot < U, jnp.stack([pos_inf, neg_inf, nan_flag])]
        )
        h1, h2 = hash_pair_numeric(full)
        return registers_from_hash_pair(h1, h2, valid)

    def scatter_path():
        return _scatter_column(xc, maskc)

    return jax.lax.cond(U <= D, dict_path, scatter_path)


def gated_column_registers_from_sorted(
    s: jnp.ndarray,  # (B,) shared-pool sorted f32 keys for this column
    xc: jnp.ndarray,  # (B,) raw values
    maskc: jnp.ndarray,  # (B,) validity
    prev_registers: jnp.ndarray,  # (M,) carried state for this column
) -> jnp.ndarray:
    """Runtime-widened sorted-dedup dispatch for ONE column the planner
    could NOT statically qualify (the O(1) range probe failed, or the
    declared range was too wide to prove anything). The column still
    rides the shared KLL sort — already paid for — and takes the dict
    path only when BOTH runtime checks pass:

    - the carried-register linear-counting estimate says
      mid-cardinality (``dedup_gate``), and
    - for integer data, every valid value in THIS batch fits the f32
      24-bit mantissa, so the pool's f32 sort keys are exact and the
      dict entries round-trip to the raw dtype bit-identically.

    Correctness never depends on the gate being right: a mispredicted
    estimate (actual batch U > D) falls back to the scatter INSIDE
    dedup_column_registers_from_sorted, and a non-qualifying batch
    pays only the two cheap checks on top of its plain scatter."""
    gate = dedup_gate(prev_registers)
    if jnp.issubdtype(xc.dtype, jnp.floating):
        qualifies = gate
    else:
        lim = 1 << 24  # f32 mantissa: int casts are exact in ±2^24
        xi = xc.astype(jnp.int64)
        in_mantissa = jnp.all(
            jnp.where(maskc, (xi >= -lim) & (xi <= lim), True)
        )
        qualifies = gate & in_mantissa
    return jax.lax.cond(
        qualifies,
        lambda: dedup_column_registers_from_sorted(s, xc, maskc),
        lambda: _scatter_column(xc, maskc),
    )


def registers_from_sorted_dedup_stacked(
    x: jnp.ndarray,  # (C, B) values, one dtype
    masks: jnp.ndarray,  # (C, B) validity
) -> jnp.ndarray:
    """(C, M) batch registers, every column through the sorted-dedup
    builder (no gating) — the differential-test surface for
    dedup_column_registers."""
    return jnp.stack(
        [
            dedup_column_registers(x[c], masks[c])
            for c in range(x.shape[0])
        ]
    )


def numeric_registers_adaptive(
    x: jnp.ndarray,  # (C, B) values
    masks: jnp.ndarray,  # (C, B) validity
    prev_registers: jnp.ndarray,  # (C, M) carried state
) -> jnp.ndarray:
    """THE numeric register builder. Default: ONE stacked flat scatter
    for the whole group. When the carried state says ANY column is
    mid-cardinality, the group switches to per-column dispatch where
    each gated column pays ITS OWN sort + unique compaction (~8 ms vs
    ~15 ms scatter at B=2^21) and ungated columns keep a plain scatter
    — a high-cardinality column never pays for its mid-card neighbors
    (the r5 batched-sort-for-the-whole-group variant measured a net
    LOSS on mixed groups for exactly that reason). Both layouts
    scatter at the same per-element rate (PERF.md r4: banked splits ==
    stacked)."""
    if not _dedup_supported(x.dtype):
        h1, h2 = hash_pair_numeric(x)
        return registers_from_hash_pair_stacked(h1, h2, masks)
    C = x.shape[0]
    gate = dedup_gate(prev_registers)

    def scatter_all():
        h1, h2 = hash_pair_numeric(x)
        return registers_from_hash_pair_stacked(h1, h2, masks)

    def per_column():
        outs = []
        for c in range(C):
            outs.append(
                jax.lax.cond(
                    gate[c],
                    lambda c=c: dedup_column_registers(x[c], masks[c]),
                    lambda c=c: _scatter_column(x[c], masks[c]),
                )
            )
        return jnp.stack(outs)

    return jax.lax.cond(jnp.any(gate), per_column, scatter_all)


def _scatter_column(xc: jnp.ndarray, maskc: jnp.ndarray) -> jnp.ndarray:
    h1, h2 = hash_pair_numeric(xc)
    return registers_from_hash_pair(h1, h2, maskc)


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
