"""Device-side high-cardinality grouping: sort + segment counting.

Reference context: the reference's grouping analyzers run a cluster
shuffle (``groupBy().count()``, SURVEY.md §2.6); deequ_tpu's dense
scatter-add path (analyzers/grouping.py) covers key spaces that fit a
device count vector, and historically spilled anything larger to the
host CPU's Arrow ``group_by`` — the one remaining Spark-job-shaped hole
in the engine (SURVEY.md §7 hard part #1; VERDICT r2 missing #1).

This module closes it for the common shape — ONE high-cardinality
numeric grouping column (an id/key column under CountDistinct /
Uniqueness / Distinctness / Entropy / Histogram): the TPU-native
equivalent of the shuffle is a device **sort + segment-boundary count**.

The sort uses a SINGLE u64 key lane — TPU sort compile time scales
brutally with operand count (measured on v5e: 1-operand ~25s,
3-operand 60-135s, both nearly flat in array length), so instead of
carrying drop/null flags as extra sort keys:

- int keys are XOR-biased into u64 (order-preserving, reversible);
  rejected rows (padding, where-filter, nulls) map to the u64 sentinel
  ``0xFFFF...`` and their EXACT count is kept as a scalar — after
  counting, the sentinel-sharing segment is corrected by subtracting
  that scalar, so even an int64.max key stays exact;
- float32 keys are their RAW BITS (``bitcast f32->u32``, the one
  bitcast width TPUs lower) widened to u64 — bit-grouping matches
  Arrow's dictionary semantics exactly (-0.0 != +0.0; NaN payloads
  canonicalized so NaN == NaN) and can never reach the sentinel;
- float64 keys bitcast to u64 directly on backends whose X64 rewriter
  lowers 64-bit bitcasts (CPU); on TPU the rewriter refuses the
  bitcast (verified r4: "X64 element types ... rewriting is not
  implemented: bitcast-convert u64"), so f64 keys are packed into u64
  ON THE HOST (numpy bit view + the same NaN/-0.0 canonicalization)
  and the u64 keys ship instead of the values — one numpy pass,
  identical wire bytes, bit-identical groups to the CPU device path;
- joint key spaces past one u64 lane (> 2^62) sort on TWO u64 lanes
  via ``lax.sort(num_keys=2)`` — measured on v5e: ~32s one-time
  compile (vs ~15s single-lane, persistent-cached), warm cost within
  2x of single-lane at 4M rows; the mixed-radix digits split across
  the lanes, covering joints to 2^124;
- the null group (Histogram's ``include_nulls``) is a separate scalar
  count, re-inserted host-side — it never needs a key lane at all.

Sorting by bits rather than value order is fine: grouping only needs
EQUAL keys adjacent, and bit-equality is the grouping relation itself.

Count-shaped metrics then finalize from ON-DEVICE scalars (#groups,
#count==1, entropy, #rows) — a 10M-group state never leaves the
device; Histogram fetches only its top-K bins via ``lax.top_k``. The
full (keys, counts) arrays stay device-resident and are fetched lazily
only if something actually needs the values (persistence, incremental
merge).

No dictionary is built: unlike the dense path (host Arrow
dictionary_encode) the keys here are the column's own 64-bit values, so
a 1B-row id column never materializes a host-side distinct set at all.

Execution has two forms. The DEFAULT is the one-pass COLLECTOR form
(``single_collector_spec`` / ``joint_collector_spec``): key extraction
is packaged as a ``ScanOps`` whose update appends each batch's u64
keys into a preallocated device-resident buffer at a carried offset,
so spill plans ride the SAME shared fused scan as the scalar and
dense-grouping analyzers — a whole mixed suite costs one traversal of
the source, and the per-plan sort + segment-count finalizes are
dispatched async afterwards so they overlap on device. The older
per-plan form (``device_spill_frequencies`` /
``device_spill_joint_frequencies``, a full re-read of the source per
plan) remains as the ``one_pass_spill=False`` escape hatch, the
fallback when the shared scan fails, and the differential-test oracle;
both forms produce bit-identical metrics (same batches, same order,
same pow2 sentinel padding in front of the same sort).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deequ_tpu.analyzers.grouping import FrequenciesAndNumRows
from deequ_tpu.data.table import (
    ColumnRequest,
    Dataset,
    Kind,
    ROW_MASK,
    f64_canonical_u64_bits,
)

# an INTEGRAL column whose (max - min) spans less than this stays on
# the dense fused-scan path: its host dictionary is bounded by the
# range, which a single O(1)-memory min/max probe establishes
DENSE_DOMAIN_RANGE = 4096

_SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)
_BIAS = np.uint64(1) << np.uint64(63)
# test hook: force the host f64-bit packing path on CPU backends
# (where the device bitcast also works) so the mesh variant is
# exercisable under the virtual CPU mesh
_FORCE_HOST_F64_BITS = False


@functools.lru_cache(maxsize=None)
def _joint_chunk_key_fn(n_columns: int):
    """Jitted: one scan chunk's per-column codes + masks -> flat u64
    JOINT keys (code+1 digits in mixed radix ``sizes``, null -> slot 0,
    exactly the dense path's joint-code math) with sentinel for
    non-contributing rows. Multi-column plans exclude only rows where
    ALL grouping columns are null (the reference's
    atLeastOneNonNullGroupingColumn)."""

    def build(codes, masks, rows, sizes):
        any_non_null = jnp.zeros_like(rows)
        for m in masks:
            any_non_null = any_non_null | m
        contributes = rows & any_non_null
        keys = jnp.zeros(rows.shape, dtype=jnp.uint64)
        for j in range(n_columns):
            shifted = (codes[j].astype(jnp.int64) + 1).astype(jnp.uint64)
            keys = keys * sizes[j].astype(jnp.uint64) + shifted
        keys = jnp.where(contributes, keys, _SENTINEL)
        n_sentinel = jnp.sum(~contributes, dtype=jnp.int64)
        return keys.ravel(), n_sentinel

    return jax.jit(build)


def _finish_keys(keys, mask, rows, include_nulls: bool):
    """Traced: the ONE copy of the sentinel/null bookkeeping every key
    builder shares — ``keys`` are the already-canonicalized u64 key
    bits; non-contributing rows map to the sentinel, null rows are
    counted when the plan keeps a null group."""
    if include_nulls:
        null = rows & ~mask
        contributes = rows & mask
    else:
        null = jnp.zeros_like(rows)
        contributes = rows & mask
    keys = jnp.where(contributes, keys, _SENTINEL)
    return (
        keys.ravel(),
        jnp.sum(~contributes, dtype=jnp.int64),
        jnp.sum(null, dtype=jnp.int64),
    )


@functools.lru_cache(maxsize=None)
def _chunk_key_fn(key_kind: str, include_nulls: bool):
    """Jitted: one scan chunk -> (flat u64 keys with sentinel for
    non-contributing rows, #sentinel rows, #null rows kept).
    ``key_kind``: "int" | "f32" | "f64" (see module docstring)."""

    def build(values, mask, rows):
        if key_kind == "f32":
            x = values.astype(jnp.float32)
            bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
            # canonical NaN bits: Arrow dictionary_encode groups NaN==NaN
            bits = jnp.where(
                jnp.isnan(x), jnp.uint32(0x7FC00000), bits
            )
            # -0.0 groups with 0.0 (Spark key normalization; goldens
            # neg_zero) — mapped at the BIT level because XLA's
            # simplifier folds the `x + 0.0` formulation away
            bits = jnp.where(
                bits == jnp.uint32(0x80000000), jnp.uint32(0), bits
            )
            keys = bits.astype(jnp.uint64)
        elif key_kind == "f64":
            x = values.astype(jnp.float64)
            bits = jax.lax.bitcast_convert_type(x, jnp.uint64)
            bits = jnp.where(
                jnp.isnan(x),
                jnp.uint64(0x7FF8000000000000),
                bits,
            )
            keys = jnp.where(
                bits == jnp.uint64(0x8000000000000000),
                jnp.uint64(0),
                bits,
            )
        else:
            keys = values.astype(jnp.int64).astype(jnp.uint64) ^ _BIAS
        return _finish_keys(keys, mask, rows, include_nulls)

    return jax.jit(build)


@functools.lru_cache(maxsize=None)
def _joint_chunk_key2_fn(n1: int, n2: int):
    """Two-lane variant of _joint_chunk_key_fn for joint key spaces
    past one u64 lane: columns [0:n1] pack lane 1, [n1:n1+n2] lane 2
    (each lane's radix product < 2^62). Sentinel = both lanes max."""

    def build(codes, masks, rows, sizes1, sizes2):
        any_non_null = jnp.zeros_like(rows)
        for m in masks:
            any_non_null = any_non_null | m
        contributes = rows & any_non_null

        def radix(cs, szs):
            keys = jnp.zeros(rows.shape, dtype=jnp.uint64)
            for j in range(len(cs)):
                shifted = (cs[j].astype(jnp.int64) + 1).astype(jnp.uint64)
                keys = keys * szs[j].astype(jnp.uint64) + shifted
            return keys

        k1 = radix(codes[:n1], sizes1)
        k2 = radix(codes[n1:], sizes2)
        k1 = jnp.where(contributes, k1, _SENTINEL)
        k2 = jnp.where(contributes, k2, _SENTINEL)
        n_sentinel = jnp.sum(~contributes, dtype=jnp.int64)
        return k1.ravel(), k2.ravel(), n_sentinel

    return jax.jit(build)


# HOST twin of the f64 key canonicalization in _chunk_key_fn; now
# lives in data.table (it backs the "u64bits" column repr the one-pass
# collector requests), re-exported here for its historical callers
f64_canonical_bits = f64_canonical_u64_bits


@functools.lru_cache(maxsize=None)
def _finish_keys_jit(include_nulls: bool):
    """Cached jitted _finish_keys wrapper (a fresh per-call lambda
    would defeat jit's cache and recompile every invocation)."""
    return jax.jit(
        lambda b, m, r: _finish_keys(b, m, r, include_nulls)
    )


def host_f64_u64_keys(
    values: np.ndarray, mask: np.ndarray, rows: np.ndarray,
    include_nulls: bool,
):
    """f64_canonical_bits plus the sentinel bookkeeping of
    _chunk_key_fn — the single-device host packing path."""
    bits = f64_canonical_bits(values)
    if include_nulls:
        null = rows & ~mask
        contributes = rows & mask
    else:
        null = np.zeros_like(rows)
        contributes = rows & mask
    keys = np.where(contributes, bits, _SENTINEL)
    return (
        keys.ravel(),
        int(np.sum(~contributes)),
        int(np.sum(null)),
    )


def _segment_count_lanes(lanes, correction):
    """Traced: sort flat u64 key LANES lexicographically, count segment
    boundaries (a boundary wherever ANY lane changes), subtract
    ``correction`` sentinel-valued entries from the trailing segment.
    This is the ONE copy of the exactness-critical bookkeeping — the
    single-device finalize (1 or 2 lanes) and the per-shard half of
    the sharded shuffle all run it. Output arrays have length N+1
    (slot N absorbs non-boundary scatter writes); segments occupy
    [0, num_segments) and ``gmask`` marks those with a positive
    corrected count. Counts are i32 (a chip processes < 2^31 rows per
    state; merges widen). The sentinel is max on EVERY lane, so it
    still sorts last regardless of lane count."""
    n = lanes[0].shape[0]
    if len(lanes) == 1:
        sorted_lanes = (jnp.sort(lanes[0]),)
    else:
        sorted_lanes = jax.lax.sort(tuple(lanes), num_keys=len(lanes))
    changed = jnp.zeros(n - 1, dtype=bool)
    for k in sorted_lanes:
        changed = changed | (k[1:] != k[:-1])
    boundary = jnp.concatenate([jnp.ones(1, dtype=bool), changed])
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    num_segments = seg[-1] + 1
    counts = jnp.zeros(n + 1, dtype=jnp.int32).at[seg].add(1)
    # sentinel-valued entries all sort to the end and share the last
    # segment; the caller knows exactly how many don't belong
    has_sentinel = jnp.ones((), dtype=bool)
    for k in sorted_lanes:
        has_sentinel = has_sentinel & (k[-1] == _SENTINEL)
    counts = counts.at[seg[-1]].add(
        -jnp.where(has_sentinel, correction, 0).astype(jnp.int32)
    )
    scatter_idx = jnp.where(boundary, seg, n)
    group_lanes = tuple(
        jnp.zeros(n + 1, dtype=k.dtype).at[scatter_idx].set(k)
        for k in sorted_lanes
    )
    in_range = jnp.arange(n + 1, dtype=jnp.int32) < num_segments
    gmask = in_range & (counts > 0)
    return num_segments, counts, group_lanes, gmask


def _segment_count(keys, correction):
    """Single-lane wrapper over _segment_count_lanes (the sharded
    shuffle and the single-column finalize use this shape)."""
    num_segments, counts, group_lanes, gmask = _segment_count_lanes(
        (keys,), correction
    )
    return num_segments, counts, group_lanes[0], gmask


def _entropy_term(counts, gmask, total):
    """Traced: -sum(p log p) over masked groups against a GLOBAL total
    (partial term for psum in the sharded path; the whole sum in the
    single-device path)."""
    c = jnp.where(gmask, counts, 0).astype(jnp.float64)
    tot_f = jnp.maximum(total, 1).astype(jnp.float64)
    p = c / tot_f
    return -jnp.sum(jnp.where(c > 0, p * jnp.log(p), 0.0))


def _spill_scalars(num_segments, counts, gmask, total):
    """The on-device scalar summary every finalize shape shares."""
    return {
        "num_segments": num_segments.astype(jnp.int64),
        "num_groups": jnp.sum(gmask, dtype=jnp.int64),
        "total": total,
        "unique": jnp.sum((counts == 1) & gmask, dtype=jnp.int64),
        "entropy": _entropy_term(counts, gmask, total),
    }


@functools.lru_cache(maxsize=None)
def _finalize_fn():
    """Jitted: flat u64 keys + sentinel count -> per-group arrays and
    scalars (single-device path)."""

    def run(keys, n_sentinel):
        num_segments, counts, group_keys, gmask = _segment_count(
            keys, n_sentinel
        )
        total = (keys.shape[0] - n_sentinel).astype(jnp.int64)
        scalars = _spill_scalars(num_segments, counts, gmask, total)
        return scalars, group_keys, counts

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _finalize2_fn():
    """Jitted two-lane finalize (joint keys past one u64 lane)."""

    def run(hi, lo, n_sentinel):
        num_segments, counts, group_lanes, gmask = _segment_count_lanes(
            (hi, lo), n_sentinel
        )
        total = (hi.shape[0] - n_sentinel).astype(jnp.int64)
        scalars = _spill_scalars(num_segments, counts, gmask, total)
        return scalars, group_lanes[0], group_lanes[1], counts

    return jax.jit(run)


@functools.partial(jax.jit, static_argnums=(3,))
def _topk_fn(counts, group_keys, num_segments, k):
    # equal-count ties at the k-boundary resolve in ascending
    # PACKED-KEY order here (segments are key-sorted) vs first-seen
    # order on the dense/Arrow path — a documented divergence; see
    # FrequenciesAndNumRows.top_groups (ADVICE r3)
    in_range = (
        jnp.arange(counts.shape[0], dtype=jnp.int32) < num_segments
    )
    tc, ti = jax.lax.top_k(jnp.where(in_range, counts, -1), k)
    return tc, jnp.take(group_keys, ti)


def _pack_top_pairs(pairs, k: int, null_rows: int):
    """Shared top-k tail: merge in the null bin (a host scalar) and
    pack (keys, counts) arrays."""
    if null_rows > 0:
        pairs = list(pairs) + [(None, np.int64(null_rows))]
        pairs.sort(key=lambda kv: -kv[1])
        pairs = pairs[:k]
    if not pairs:
        return np.zeros(0, dtype=object), np.zeros(0, dtype=np.int64)
    keys_out = np.empty(len(pairs), dtype=object)
    keys_out[:] = [p[0] for p in pairs]
    return keys_out, np.asarray([p[1] for p in pairs], dtype=np.int64)


def _count_data_pass() -> None:
    """Every full traversal of the source bumps ``engine.data_passes``
    (run_scan counts its own) — the deferred re-scan paths below each
    cost one; the collector form costs zero beyond the shared scan."""
    from deequ_tpu.telemetry import get_telemetry

    get_telemetry().counter("engine.data_passes").inc()


class SpillOverflow(Exception):
    """A sharded spill bucket exceeded its static capacity; the caller
    falls back to the host Arrow path (exactness over speed)."""


def _fmix64(x):
    """murmur3 64-bit finalizer: avalanches sequential ids into uniform
    bucket assignments (a plain ``key % ndev`` would send stride-ndev
    id ranges all to one shard)."""
    x = x ^ (x >> np.uint64(33))
    x = x * np.uint64(0xFF51AFD7ED558CCD)
    x = x ^ (x >> np.uint64(33))
    x = x * np.uint64(0xC4CEB9FE1A85EC53)
    x = x ^ (x >> np.uint64(33))
    return x


def _fmix64_int(x: int) -> int:
    """Host-side _fmix64 over Python ints (no numpy overflow warnings);
    used for trace-time constants like the sentinel's bucket."""
    m = (1 << 64) - 1
    x &= m
    x ^= x >> 33
    x = (x * 0xFF51AFD7ED558CCD) & m
    x ^= x >> 33
    x = (x * 0xC4CEB9FE1A85EC53) & m
    x ^= x >> 33
    return x


@functools.lru_cache(maxsize=None)
def _sharded_spill_fn(mesh, axis: str, cap: int):
    """Jitted shard_map: the TPU shuffle (SURVEY.md §2.6, §7 hard part
    #1). Each shard hash-buckets its local u64 keys, ``all_to_all``
    re-shards them so EQUAL keys land on the same device, then each
    device runs the SAME sort + segment-count as the single-device path
    (_segment_count) over its disjoint key range; scalars psum into
    global metrics. Per-device memory is O(rows/ndev): group arrays
    come back SHARDED (out_specs P(axis)), never replicated.

    Sentinel-valued rows (dropped rows AND any legit int64.max keys —
    indistinguishable by value) never enter the shuffle at all: their
    global count minus the known dropped count is exactly the
    int64.max group's count, reconstructed analytically. The only
    sentinel-valued entries a shard receives are therefore all_to_all
    PADDING, whose count derives from the communicated per-bucket
    counts. A bucket overflow (static ``cap`` exceeded) is reported as
    a scalar; the host falls back to the Arrow path rather than
    dropping rows."""
    import jax
    from jax.sharding import PartitionSpec as P

    ndev = mesh.shape[axis]

    def per_shard(keys, n_sentinel_global, n_null_global):
        is_sent = keys == _SENTINEL
        sv_local = jnp.sum(is_sent, dtype=jnp.int64)
        bucket = (_fmix64(keys) % np.uint64(ndev)).astype(jnp.int32)
        # sentinel-valued rows are excluded from the shuffle (their
        # count is bookkept in scalars); bucket ndev scatters to drop
        bucket = jnp.where(is_sent, ndev, bucket)
        (recv,), padding_received, overflow = _bucketed_all_to_all(
            axis, ndev, cap, bucket, (keys,)
        )

        # the shared exactness-critical bookkeeping (spill.py's one copy)
        num_segments, counts, group_keys, gmask = _segment_count(
            recv, padding_received.astype(jnp.int64)
        )

        # the analytic int64.max group: sentinel-VALUED rows globally,
        # minus the known dropped-row count
        legit_max = (
            jax.lax.psum(sv_local, axis) - n_sentinel_global
        )
        scalars = _sharded_scalar_block(
            axis, num_segments, counts, gmask, legit_max
        )
        return (
            scalars,
            group_keys,  # sharded out: (ndev*(L+1),) global
            counts,
            num_segments.astype(jnp.int32)[None],  # (ndev,) global
            overflow,
            n_null_global,
        )

    sharded = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(axis), P(), P()),
        out_specs=(P(), P(axis), P(axis), P(axis), P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded)


def _bucketed_all_to_all(axis: str, ndev: int, cap: int, bucket, lanes):
    """The shuffle core every lane-width shares: stable-sort the local
    rows by bucket, pack per-destination (ndev, cap) send buffers for
    EACH key lane with one shared position layout, all_to_all them,
    and derive the received-padding count from the communicated
    per-bucket real counts. bucket == ndev drops the row."""
    import jax

    m = bucket.shape[0]
    order = jnp.argsort(bucket, stable=True)
    sorted_bucket = bucket[order]
    bcounts = jnp.zeros(ndev, jnp.int32).at[bucket].add(1, mode="drop")
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(bcounts)[:-1]]
    )
    pos = jnp.arange(m, dtype=jnp.int32) - offsets[
        jnp.clip(sorted_bucket, 0, ndev - 1)
    ]
    in_cap = (pos < cap) & (sorted_bucket < ndev)
    recv_lanes = []
    for lane in lanes:
        send = (
            jnp.full((ndev, cap), _SENTINEL, dtype=lane.dtype)
            .at[
                jnp.where(in_cap, sorted_bucket, ndev),
                jnp.clip(pos, 0, cap - 1),
            ]
            .set(lane[order], mode="drop")
        )
        recv_lanes.append(
            jax.lax.all_to_all(
                send, axis, split_axis=0, concat_axis=0
            ).ravel()
        )
    overflow = jax.lax.psum(
        jnp.sum(jnp.maximum(bcounts - cap, 0)), axis
    )
    # real (non-padding) entry counts per (sender, my bucket)
    sent_real = jnp.minimum(bcounts, cap)  # (ndev,) what I sent
    recv_real = jax.lax.all_to_all(
        sent_real[:, None], axis, split_axis=0, concat_axis=0
    )  # (ndev, 1): shard s's real count for MY bucket
    padding_received = ndev * cap - jnp.sum(recv_real)
    return recv_lanes, padding_received, overflow


def _sharded_scalar_block(axis, num_segments, counts, gmask, legit_max):
    """The psum'd scalar summary every sharded spill shape shares
    (single-lane with its analytic int64.max group; two-lane joints
    pass legit_max = 0, as joint codes can never reach the sentinel)."""
    import jax

    local_total = jnp.sum(jnp.where(gmask, counts, 0), dtype=jnp.int64)
    total = jax.lax.psum(local_total, axis) + legit_max
    num_groups = (
        jax.lax.psum(jnp.sum(gmask, dtype=jnp.int64), axis)
        + (legit_max > 0).astype(jnp.int64)
    )
    unique = (
        jax.lax.psum(
            jnp.sum((counts == 1) & gmask, dtype=jnp.int64), axis
        )
        + (legit_max == 1).astype(jnp.int64)
    )
    pm = legit_max.astype(jnp.float64) / jnp.maximum(total, 1).astype(
        jnp.float64
    )
    entropy = jax.lax.psum(
        _entropy_term(counts, gmask, total), axis
    ) + jnp.where(
        legit_max > 0, -pm * jnp.log(jnp.maximum(pm, 1e-300)), 0.0
    )
    return {
        # replicated upper bound; per-shard true values ride the
        # sharded num_segments vector (sliced at fetch time)
        "num_segments": jax.lax.pmax(num_segments, axis).astype(
            jnp.int64
        ),
        "num_groups": num_groups,
        "total": total,
        "unique": unique,
        "entropy": entropy,
        "legit_max": legit_max,
    }


@functools.lru_cache(maxsize=None)
def _sharded_spill2_fn(mesh, axis: str, cap: int):
    """Two-lane variant of _sharded_spill_fn for joint key spaces past
    one u64 lane (> 2^62): the bucket hashes BOTH lanes so equal
    (hi, lo) pairs land on one device, both lanes ride the shared
    send-buffer layout, and the per-shard count is the same two-lane
    sort (_segment_count_lanes) the single-device path uses. Joint
    codes never reach the sentinel, so legit_max degenerates to 0."""
    import jax
    from jax.sharding import PartitionSpec as P

    ndev = mesh.shape[axis]

    def per_shard(k1, k2):
        # no sentinel scalar: joint codes can never reach the
        # sentinel, so there is no analytic max-group to reconstruct
        is_sent = k1 == _SENTINEL
        bucket = (
            _fmix64(k1 ^ _fmix64(k2)) % np.uint64(ndev)
        ).astype(jnp.int32)
        bucket = jnp.where(is_sent, ndev, bucket)
        (r1, r2), padding_received, overflow = _bucketed_all_to_all(
            axis, ndev, cap, bucket, (k1, k2)
        )
        num_segments, counts, group_lanes, gmask = _segment_count_lanes(
            (r1, r2), padding_received.astype(jnp.int64)
        )
        scalars = _sharded_scalar_block(
            axis, num_segments, counts, gmask, jnp.int64(0)
        )
        return (
            scalars,
            group_lanes[0],
            group_lanes[1],
            counts,
            num_segments.astype(jnp.int32)[None],  # (ndev,) global
            overflow,
        )

    sharded = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(), P(axis), P(axis), P(axis), P(axis), P()),
        check_vma=False,
    )
    return jax.jit(sharded)


class DeviceFrequencies(FrequenciesAndNumRows):
    """FrequenciesAndNumRows whose groups live ON DEVICE.

    Count metrics read precomputed scalars; ``keys``/``counts`` fetch
    and decode lazily (only persistence, incremental merge, and
    MutualInformation ever need the values). The null group, if any, is
    a host scalar appended on access."""

    def __init__(
        self,
        columns: Tuple[str, ...],
        values_dtype: np.dtype,
        scalars: Dict[str, object],
        group_keys,
        counts,
        null_rows: int,
        include_nulls: bool,
        joint=None,  # (dictionaries, sizes): multi-column joint codes
    ):
        self.columns = tuple(columns)
        self._values_dtype = np.dtype(values_dtype)
        self._is_float = self._values_dtype.kind == "f"
        self._joint = joint
        # base-class lazy-decode slots (joint mode feeds _lazy after
        # fetch and inherits keys/non_null_group_mask, incl. caching)
        self._keys = None
        self._lazy = None
        self._num_segments = int(scalars["num_segments"])
        self._value_groups = int(scalars["num_groups"])
        self._unique = int(scalars["unique"])
        self._entropy = float(scalars["entropy"])
        self._null_rows = int(null_rows) if include_nulls else 0
        self._include_nulls = include_nulls
        self.num_rows = int(scalars["total"]) + self._null_rows
        # sharded path only: analytically-reconstructed int64.max group
        self._legit_max = int(scalars.get("legit_max", 0))
        self._dev = (group_keys, counts)
        self._keys_host: Optional[np.ndarray] = None
        self._counts_host: Optional[np.ndarray] = None

    # -- FrequenciesAndNumRows surface ---------------------------------

    @property
    def _has_null_group(self) -> bool:
        return self._null_rows > 0

    @property
    def num_groups(self) -> int:
        return self._value_groups + (1 if self._has_null_group else 0)

    def _fetch(self) -> None:
        if self._counts_host is None:
            from deequ_tpu.engine.pack import packed_device_get

            gk, c = packed_device_get(self._dev)
            s = self._num_segments
            raw_keys = np.asarray(gk)[:s]
            raw_counts = np.asarray(c)[:s]
            live = raw_counts > 0  # drops a zeroed sentinel segment
            self._keys_host = raw_keys[live]
            self._counts_host = raw_counts[live].astype(np.int64)
        self._set_joint_lazy()

    def _set_joint_lazy(self) -> None:
        """Arm the base class's cached joint decode over the fetched
        keys (shared by the single-device and sharded fetches)."""
        if self._joint is not None and self._lazy is None:
            dictionaries, sizes = self._joint
            self._lazy = (
                self._keys_host.astype(np.int64),
                list(dictionaries),
                list(sizes),
            )

    def _decode_keys(self, raw: np.ndarray) -> np.ndarray:
        """(K,) raw u64 keys -> (K,) object values in the column's OWN
        dtype — a float32 column's keys must decode to np.float32, or
        Histogram labels and persisted keys would diverge from the
        dense dictionary path (str(np.float64(1.1)) !=
        str(np.float32(1.1))). Float keys are raw bits; ints unbias."""
        if self._values_dtype == np.float32:
            vals = raw.astype(np.uint32).view(np.float32)
        elif self._values_dtype == np.float64:
            vals = raw.view(np.float64)
        elif self._is_float:  # f16 materialized as f32 on the wire
            vals = raw.astype(np.uint32).view(np.float32).astype(
                self._values_dtype
            )
        else:
            vals = (raw ^ _BIAS).view(np.int64)
        return vals.astype(object)

    @property
    def counts(self) -> np.ndarray:
        self._fetch()
        if self._has_null_group:
            return np.concatenate(
                [self._counts_host, [np.int64(self._null_rows)]]
            )
        return self._counts_host

    @property
    def keys(self) -> np.ndarray:
        self._fetch()
        if self._joint is not None:
            # inherit the base class's cached lazy decode (ONE radix
            # walk however many times merge/persistence read .keys)
            return FrequenciesAndNumRows.keys.fget(self)
        n = self.num_groups
        out = np.empty((n, 1), dtype=object)
        out[: len(self._keys_host), 0] = self._decode_keys(self._keys_host)
        if self._has_null_group:
            out[-1, 0] = None
        return out

    def non_null_group_mask(self) -> np.ndarray:
        if self._joint is not None:
            self._fetch()
            return FrequenciesAndNumRows.non_null_group_mask(self)
        mask = np.ones(self.num_groups, dtype=bool)
        if self._has_null_group:
            mask[-1] = False
        return mask

    # -- fast paths (no device->host group transfer) -------------------

    def count_unique_groups(self) -> int:
        return self._unique + (1 if self._null_rows == 1 else 0)

    def entropy_nats(self) -> float:
        from deequ_tpu.analyzers.base import EmptyStateException

        if self._joint is not None:
            # joint plans can hold PARTIALLY-null groups, which entropy
            # excludes — the on-device scalar summed all groups, so fall
            # back to the host fold over the fetched distribution
            return FrequenciesAndNumRows.entropy_nats(self)
        if self.num_rows - self._null_rows == 0:
            raise EmptyStateException("Entropy over empty distribution.")
        return self._entropy

    def top_groups(self, k: int) -> Tuple[np.ndarray, np.ndarray]:
        if self._joint is not None:  # multi-column: host decode path
            return FrequenciesAndNumRows.top_groups(self, k)
        gk, c = self._dev
        kk = min(k, self._num_segments)
        pairs = []
        if kk > 0:
            from deequ_tpu.engine.pack import packed_device_get

            tc, tkeys = packed_device_get(
                _topk_fn(c, gk, np.int32(self._num_segments), kk)
            )
            tc = np.asarray(tc)
            live = tc > 0  # zeroed sentinel segment never bins
            decoded = self._decode_keys(np.asarray(tkeys)[live])
            pairs = list(zip(decoded, tc[live].astype(np.int64)))
        return _pack_top_pairs(
            pairs, k, self._null_rows if self._has_null_group else 0
        )


class TwoLaneDeviceFrequencies(DeviceFrequencies):
    """DeviceFrequencies for joint keys on TWO u64 lanes (joint space
    past 2^62): group identity is the (hi, lo) pair; decoding walks
    each lane's own mixed radix over its own column slice."""

    def __init__(
        self,
        columns,
        scalars,
        group_hi,
        group_lo,
        counts,
        dictionaries,
        sizes,
        split: int,
    ):
        super().__init__(
            columns,
            np.dtype(np.int64),
            scalars,
            (group_hi, group_lo),
            counts,
            0,
            False,
            joint=(list(dictionaries), list(sizes)),
        )
        self._split = split
        self._keys_host2: Optional[np.ndarray] = None

    def _fetch(self) -> None:
        if self._counts_host is None:
            from deequ_tpu.engine.pack import packed_device_get

            # one packed fetch for all three arrays
            gh, gl, c = packed_device_get(
                (self._dev[0][0], self._dev[0][1], self._dev[1])
            )
            s = self._num_segments
            raw_hi = np.asarray(gh)[:s]
            raw_lo = np.asarray(gl)[:s]
            raw_counts = np.asarray(c)[:s]
            live = raw_counts > 0
            self._keys_host = raw_hi[live]
            self._keys_host2 = raw_lo[live]
            self._counts_host = raw_counts[live].astype(np.int64)

    @property
    def keys(self) -> np.ndarray:
        self._fetch()
        if self._keys is None:
            from deequ_tpu.analyzers.grouping import _decode_joint_codes

            dictionaries, sizes = self._joint
            split = self._split
            left = _decode_joint_codes(
                split,
                self._keys_host.astype(np.int64),
                dictionaries[:split],
                sizes[:split],
            )
            right = _decode_joint_codes(
                len(self.columns) - split,
                self._keys_host2.astype(np.int64),
                dictionaries[split:],
                sizes[split:],
            )
            self._keys = np.hstack([left, right])
        return self._keys

    def non_null_group_mask(self) -> np.ndarray:
        self._fetch()
        mask = np.ones(len(self._keys_host), dtype=bool)
        for lane, lane_sizes in (
            (self._keys_host, self._joint[1][: self._split]),
            (self._keys_host2, self._joint[1][self._split:]),
        ):
            remaining = lane.astype(np.int64).copy()
            for j in range(len(lane_sizes) - 1, -1, -1):
                slot = remaining % lane_sizes[j]
                remaining = remaining // lane_sizes[j]
                mask &= slot > 0
        return mask

    # entropy_nats / top_groups: the inherited DeviceFrequencies
    # methods already take the joint (host-fold) branch for any
    # instance with _joint set, which this class always has


class ShardedTwoLaneDeviceFrequencies(TwoLaneDeviceFrequencies):
    """TwoLaneDeviceFrequencies whose groups live SHARDED across a
    mesh (joint key spaces > 2^62 under a mesh): both key lanes fetch
    per shard, sliced at each shard's true segment count."""

    def _fetch(self) -> None:
        if self._counts_host is None:
            (gh_flat, gl_flat), gc_flat = self._dev[0], self._dev[1]
            gh = np.asarray(gh_flat)
            gl = np.asarray(gl_flat)
            gc = np.asarray(gc_flat)
            segs = np.asarray(self._segs)
            ndev = len(segs)
            gh = gh.reshape(ndev, -1)
            gl = gl.reshape(ndev, -1)
            gc = gc.reshape(ndev, -1)
            hi_parts, lo_parts, count_parts = [], [], []
            for shard in range(ndev):
                s = int(segs[shard])
                live = gc[shard][:s] > 0
                hi_parts.append(gh[shard][:s][live])
                lo_parts.append(gl[shard][:s][live])
                count_parts.append(gc[shard][:s][live])
            self._keys_host = np.concatenate(hi_parts)
            self._keys_host2 = np.concatenate(lo_parts)
            self._counts_host = np.concatenate(count_parts).astype(
                np.int64
            )


def _sharded_spill_joint2_frequencies(
    dataset: Dataset, plan, engine, dictionaries, sizes, split, pred
) -> "ShardedTwoLaneDeviceFrequencies":
    """Meshed TWO-LANE joint spill (joint key spaces > 2^62 under a
    mesh — docs/COVERAGE.md known-gap, VERDICT r4 next #4): the same
    hash-bucket all_to_all shuffle with BOTH lanes riding the shared
    send layout, then the per-shard two-lane sort + segment count."""
    columns = list(plan.columns)
    needed = {
        r
        for c in columns
        for r in (ColumnRequest(c, "codes"), ColumnRequest(c, "mask"))
    }
    if pred is not None:
        needed.update(pred.requests)

    key2_fn = _joint_chunk_key2_fn(split, len(columns) - split)
    sizes1 = jnp.asarray(np.asarray(sizes[:split], dtype=np.int64))
    sizes2 = jnp.asarray(np.asarray(sizes[split:], dtype=np.int64))

    def build(batch):
        rows = batch[ROW_MASK]
        if pred is not None:
            rows = rows & pred.complies(batch)
        return key2_fn(
            tuple(batch[f"{c}::codes"] for c in columns),
            tuple(batch[f"{c}::mask"] for c in columns),
            rows,
            sizes1,
            sizes2,
        )

    scalars, g_hi, g_lo, g_counts, segs_host = _sharded_shuffle2(
        dataset, engine, needed, build, label=f"joint2 {columns!r}"
    )
    state = ShardedTwoLaneDeviceFrequencies(
        plan.columns,
        scalars,
        g_hi,
        g_lo,
        g_counts,
        list(dictionaries),
        list(sizes),
        split,
    )
    state._segs = segs_host
    return state


def split_joint_lanes(sizes) -> Optional[int]:
    """First-fit split index: columns [0:i] on lane 1, [i:] on lane 2,
    each lane's radix product < 2^62. None when even two lanes cannot
    hold the joint space (or a single column's radix already overflows
    a lane — impossible for dictionaries bounded by row count)."""
    cap = 2**62
    prod = 1
    i = 0
    for s in sizes:
        if prod * s >= cap:
            break
        prod *= s
        i += 1
    if i == 0:
        return None
    prod2 = 1
    for s in sizes[i:]:
        prod2 *= s
        if prod2 >= cap:
            return None
    return i


class ShardedDeviceFrequencies(DeviceFrequencies):
    """DeviceFrequencies whose groups live SHARDED across a mesh: each
    device holds the (keys, counts, num_segments) of its disjoint hash
    range (nothing is replicated); fetching is a filtered concatenation
    plus the analytically-reconstructed int64.max group, if any."""

    def _fetch(self) -> None:
        if self._counts_host is None:
            gk_flat, gc_flat, segs = (
                np.asarray(x) for x in self._dev
            )
            ndev = len(segs)
            gk = gk_flat.reshape(ndev, -1)
            gc = gc_flat.reshape(ndev, -1)
            keys_parts, count_parts = [], []
            for shard in range(ndev):
                s = int(segs[shard])
                raw_k = gk[shard][:s]
                raw_c = gc[shard][:s]
                live = raw_c > 0
                keys_parts.append(raw_k[live])
                count_parts.append(raw_c[live])
            if self._legit_max > 0:
                keys_parts.append(np.array([_SENTINEL], dtype=np.uint64))
                count_parts.append(
                    np.array([self._legit_max], dtype=np.int64)
                )
            self._keys_host = np.concatenate(keys_parts)
            self._counts_host = np.concatenate(count_parts).astype(
                np.int64
            )
        self._set_joint_lazy()

    def top_groups(self, k: int):
        if self._joint is not None:  # multi-column: host decode path
            return FrequenciesAndNumRows.top_groups(self, k)
        # host-side top-k over the fetched union (a per-shard device
        # top_k + gather would cut the fetch further; at histogram's
        # k<=1000 the union fetch is the simpler exact path)
        self._fetch()
        order = np.argsort(-self._counts_host, kind="stable")[:k]
        pairs = list(
            zip(
                self._decode_keys(self._keys_host[order]),
                self._counts_host[order],
            )
        )
        return _pack_top_pairs(
            pairs, k, self._null_rows if self._has_null_group else 0
        )


def device_spill_eligible(dataset: Dataset, plan, engine=None) -> bool:
    """True when a frequency plan should run the device sort path:
    a single INTEGRAL/FRACTIONAL grouping column whose flat sort fits
    the device budget. Strings keep the dense/Arrow path (their keys
    are dictionary codes); booleans and timestamps keep it too so
    decoded key VALUES (True/False, datetime64) stay merge-compatible
    with dense-path states; uint64 can't widen to the i64 key lane.

    Note the asymmetry with the dense path: dense must first build a
    host-side dictionary (an Arrow hash pass over every row) just to
    LEARN the cardinality; the sort path needs no dictionary at all,
    so for FRACTIONAL and unbounded-domain integer columns it wins
    even at low cardinality. Bounded-domain integers are the
    exception (the DENSE_DOMAIN_RANGE gate below): a single O(1)
    min/max probe bounds their dictionary up front, and the dense
    fused scan then beats one device sort per column."""
    from deequ_tpu import config

    opts = config.options()
    if not opts.device_spill_grouping:
        return False
    if not opts.device_cache_bytes:
        return False  # chunked device path needs the resident cache
    if opts.engine == "cpu":
        return False  # honor the engine-selection flag's placement
    if dataset.num_rows >= 2**31:
        return False  # i32 segment counts; the dense path widens, we gate
    if len(plan.columns) != 1:
        return False
    column = plan.columns[0]
    kind = dataset.schema.kind_of(column)
    if kind not in (Kind.INTEGRAL, Kind.FRACTIONAL):
        return False
    try:
        dt = dataset.request_dtype(ColumnRequest(column, "values"))
    except Exception:  # noqa: BLE001 — odd column: use the host path
        return False
    if dt.kind == "u" and dt.itemsize == 8:
        return False
    if kind == Kind.INTEGRAL:
        # bounded-domain integers (TPC-DS quantity-style): one O(1)-
        # memory min/max probe (free from parquet row-group stats)
        # detects them, the host dictionary is then bounded by the
        # range, and ALL such columns ride the shared fused dense scan
        # — while the sort path costs a sequential device sort per
        # column (r5: 5 qty columns = 2.75 s/run steady + a one-time
        # ~60 s sort-plan compile vs milliseconds dense)
        rng = dataset.integral_range(column)
        if rng is not None and (rng[1] - rng[0]) < DENSE_DOMAIN_RANGE:
            return False
    # f64 keys: CPU-class backends bitcast on device; elsewhere (TPU)
    # the canonical u64 bits pack on the HOST (f64_canonical_bits —
    # the X64 rewriter cannot lower the f64 bitcast, measured r4) and
    # the same device sort runs, single-device and meshed alike
    # headroom gate: the pass pins values+mask chunks in the cache
    # (~9 B/row) AND allocates sort transients outside cache accounting
    # (u64 keys + sorted copy + group keys + counts ~ 30 B/row, pow2
    # padded); 64 B/row keeps the whole pass clear of HBM even when the
    # budget is sized close to the device memory
    return dataset.num_rows * 64 <= opts.device_cache_bytes


def joint_spill_config_ok(dataset: Dataset, plan, engine=None) -> bool:
    """The SIZE-INDEPENDENT gates of the joint spill — callers must
    check these BEFORE probing full per-column cardinalities: the
    probe can stream a whole distinct set into host memory, which must
    never happen for a plan the config would reject anyway."""
    from deequ_tpu import config

    opts = config.options()
    if not opts.device_spill_grouping or not opts.device_cache_bytes:
        return False
    if opts.engine == "cpu":
        return False
    if plan.include_nulls:
        # the joint kernel drops all-null rows; include_nulls plans
        # (Histogram's null bin) keep the dense/Arrow paths
        return False
    if dataset.num_rows >= 2**31:
        return False
    return dataset.num_rows * 64 <= opts.device_cache_bytes


def joint_spill_eligible(
    dataset: Dataset, plan, sizes, engine=None
) -> bool:
    """Multi-column variant: config gates pass AND the joint
    mixed-radix key space fits the sort lanes (one u64 lane below
    2^62; past that, TWO lanes cover up to ~2^124 provided the digits
    split across lanes — single-device AND meshed since r5, via
    _sharded_spill_joint2_frequencies)."""
    if not joint_spill_config_ok(dataset, plan, engine):
        return False
    return split_joint_lanes(tuple(sizes)) is not None


def joint_fits_one_lane(sizes) -> bool:
    """True when the mixed-radix joint space fits ONE u64 sort lane
    (< 2^62): the shape the sharded shuffle can re-use unchanged.
    Defined via split_joint_lanes so there is exactly one copy of the
    lane-capacity rule."""
    return split_joint_lanes(tuple(sizes)) == len(tuple(sizes))


def _stage_mesh_columns(dataset, engine, needed, extra_arrays=None):
    """Mesh staging every sharded spill shares: pow2/mesh-multiple
    padding (so the per-shard sort's expensive-to-compile program is
    shared across datasets whose row counts round the same way) and
    column placement. Returns (flat, mesh, axis, ndev, cap)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh, axis = engine.mesh, engine.dp_axis
    ndev = mesh.shape[axis]
    n = dataset.num_rows
    _count_data_pass()  # materializes every needed column: one pass
    pow2 = 1 << max(1, int(max(n, 1) - 1).bit_length())
    padded = max(1, -(-pow2 // ndev)) * ndev
    sharding = NamedSharding(mesh, P(axis))

    def pad_to(host: np.ndarray) -> np.ndarray:
        if len(host) < padded:
            host = np.concatenate(
                [host, np.zeros(padded - len(host), dtype=host.dtype)]
            )
        return host

    flat = {
        r.key: jax.device_put(pad_to(dataset.materialize(r)), sharding)
        for r in needed
    }
    for key, host in (extra_arrays or {}).items():
        # caller-prepared arrays (e.g. host-packed f64 key bits) stage
        # like any column
        flat[key] = jax.device_put(pad_to(host), sharding)
    rows_host = np.zeros(padded, dtype=bool)
    rows_host[:n] = True
    flat[ROW_MASK] = jax.device_put(rows_host, sharding)

    m_local = padded // ndev
    # pow2 capacity (shared compiles); 4x the uniform expectation is
    # comfortable headroom for hashed buckets — dropped rows never
    # enter the shuffle, so nulls/filters cannot skew a bucket
    cap = 1 << max(8, ((4 * m_local) // ndev - 1).bit_length())
    return flat, mesh, axis, ndev, cap


def _sharded_shuffle(
    dataset, engine, needed, build, label: str, extra_arrays=None
):
    """Shared single-lane mesh-spill scaffolding: staging, the
    bucketed all_to_all shuffle, and the overflow check.
    ``build(flat)`` -> (keys, n_sentinel, n_null).

    Returns (scalars, g_keys, g_counts, segs_host, n_null_host);
    raises SpillOverflow when a hash bucket exceeds its static
    capacity (the caller falls back to Arrow)."""
    import jax

    from deequ_tpu.engine.pack import packed_device_get

    flat, mesh, axis, ndev, cap = _stage_mesh_columns(
        dataset, engine, needed, extra_arrays
    )
    keys, n_sentinel, n_null = jax.jit(build)(flat)
    out = _sharded_spill_fn(mesh, axis, cap)(keys, n_sentinel, n_null)
    scalars, g_keys, g_counts, g_segs, overflow, n_null_global = out
    scalars, overflow_host, n_null_host, segs_host = packed_device_get(
        (scalars, overflow, n_null_global, np.asarray(g_segs))
    )
    if int(overflow_host) > 0:
        raise SpillOverflow(
            f"hash bucket exceeded capacity {cap} on {label}"
        )
    return scalars, g_keys, g_counts, segs_host, int(n_null_host)


def _sharded_shuffle2(dataset, engine, needed, build, label: str):
    """Two-lane twin of _sharded_shuffle: ``build(flat)`` ->
    (k1, k2, n_sentinel). Returns (scalars, g_hi, g_lo, g_counts,
    segs_host)."""
    import jax

    from deequ_tpu.engine.pack import packed_device_get

    flat, mesh, axis, ndev, cap = _stage_mesh_columns(
        dataset, engine, needed
    )
    k1, k2, _ = jax.jit(build)(flat)
    out = _sharded_spill2_fn(mesh, axis, cap)(k1, k2)
    scalars, g_hi, g_lo, g_counts, g_segs, overflow = out
    scalars, overflow_host, segs_host = packed_device_get(
        (scalars, overflow, np.asarray(g_segs))
    )
    if int(overflow_host) > 0:
        raise SpillOverflow(
            f"hash bucket exceeded capacity {cap} on {label}"
        )
    return scalars, g_hi, g_lo, g_counts, segs_host


def _sharded_spill_joint_frequencies(
    dataset: Dataset, plan, engine, dictionaries, sizes, pred
) -> "ShardedDeviceFrequencies":
    """Meshed multi-column joint spill (SURVEY §2.6, closing the
    'meshed multi-column spills use the host path' gap): the joint
    mixed-radix codes pack into ONE u64 lane (< 2^62 — two-lane joints
    stay single-device), after which the bucketed all_to_all shuffle,
    per-shard sort + segment count, and scalar psums are EXACTLY the
    single-column sharded machinery (_sharded_shuffle) — joint keys
    can never collide with the sentinel, so the analytic int64.max
    group reconstruction degenerates to zero."""
    columns = list(plan.columns)
    needed = {
        r
        for c in columns
        for r in (ColumnRequest(c, "codes"), ColumnRequest(c, "mask"))
    }
    if pred is not None:
        needed.update(pred.requests)

    key_fn = _joint_chunk_key_fn(len(columns))
    sizes_dev = jnp.asarray(np.asarray(sizes, dtype=np.int64))

    def build(batch):
        rows = batch[ROW_MASK]
        if pred is not None:
            rows = rows & pred.complies(batch)
        keys, n_sentinel = key_fn(
            tuple(batch[f"{c}::codes"] for c in columns),
            tuple(batch[f"{c}::mask"] for c in columns),
            rows,
            sizes_dev,
        )
        return keys, n_sentinel, jnp.int64(0)  # no null group (gated)

    scalars, g_keys, g_counts, segs_host, _ = _sharded_shuffle(
        dataset, engine, needed, build, label=f"joint {columns!r}"
    )
    state = ShardedDeviceFrequencies(
        plan.columns,
        np.dtype(np.int64),
        scalars,
        g_keys,
        g_counts,
        0,
        False,
        joint=(list(dictionaries), list(sizes)),
    )
    state._dev = (g_keys, g_counts, segs_host)
    return state


def device_spill_joint_frequencies(
    dataset: Dataset, plan, engine, dictionaries, sizes
) -> "DeviceFrequencies":
    """Multi-column high-cardinality frequencies on device: joint codes
    (the dense path's mixed-radix math) packed into ONE u64 sort lane —
    covers joint key spaces past the dense scatter budget but within
    2^62 (e.g. two 100k-cardinality columns under Uniqueness)."""
    from deequ_tpu import config
    from deequ_tpu.engine.scan import CHUNK_BATCHES

    columns = list(plan.columns)
    requests = [ColumnRequest(c, "codes") for c in columns] + [
        ColumnRequest(c, "mask") for c in columns
    ]
    pred = None
    if plan.where is not None:
        from deequ_tpu.sql.predicate import compile_predicate

        pred = compile_predicate(plan.where, dataset)
        requests += list(pred.requests)

    if engine is not None and getattr(engine, "mesh", None) is not None:
        if joint_fits_one_lane(sizes):
            return _sharded_spill_joint_frequencies(
                dataset, plan, engine, dictionaries, sizes, pred
            )
        split_at = split_joint_lanes(tuple(sizes))
        if split_at is None:
            raise SpillOverflow("joint key space exceeds two u64 lanes")
        # r5: joint spaces past one u64 lane ride the same shuffle on
        # TWO lanes (lax.sort num_keys=2 per shard)
        return _sharded_spill_joint2_frequencies(
            dataset, plan, engine, dictionaries, sizes, split_at, pred
        )

    batch_size = engine._resolve_batch_size(dataset.num_rows)
    nb = dataset.num_batches(batch_size)
    chunk_batches = min(CHUNK_BATCHES, nb)
    _count_data_pass()  # deferred re-scan: one traversal per plan
    split = split_joint_lanes(tuple(sizes))
    if split is None:  # planner should have gated; double-check
        raise SpillOverflow("joint key space exceeds two u64 lanes")
    two_lane = split < len(columns)
    if two_lane:
        key2_fn = _joint_chunk_key2_fn(split, len(columns) - split)
        sizes1 = jnp.asarray(np.asarray(sizes[:split], dtype=np.int64))
        sizes2 = jnp.asarray(np.asarray(sizes[split:], dtype=np.int64))
    else:
        key_fn = _joint_chunk_key_fn(len(columns))
        sizes_dev = jnp.asarray(np.asarray(sizes, dtype=np.int64))

    keys_parts = []
    keys2_parts = []
    n_sentinel = jnp.int64(0)
    for chunk in dataset.device_scan_chunks(
        requests,
        batch_size,
        chunk_batches=chunk_batches,
        budget_bytes=config.options().device_cache_bytes,
    ):
        rows = chunk[ROW_MASK]
        if pred is not None:
            flat = {k: v.reshape(-1) for k, v in chunk.items()}
            rows = rows & pred.complies(flat).reshape(rows.shape)
        codes = tuple(chunk[f"{c}::codes"] for c in columns)
        masks = tuple(chunk[f"{c}::mask"] for c in columns)
        if two_lane:
            k1, k2, ns = key2_fn(codes, masks, rows, sizes1, sizes2)
            keys_parts.append(k1)
            keys2_parts.append(k2)
        else:
            k, ns = key_fn(codes, masks, rows, sizes_dev)
            keys_parts.append(k)
        n_sentinel = n_sentinel + ns

    def _joined(parts):
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    keys = _joined(keys_parts)
    n = keys.shape[0]
    padded = 1 << max(1, int(n - 1).bit_length()) if n > 1 else 1
    pad = padded - n
    if pad:
        keys = jnp.concatenate(
            [keys, jnp.full(pad, _SENTINEL, dtype=keys.dtype)]
        )
        n_sentinel = n_sentinel + pad

    from deequ_tpu.engine.pack import packed_device_get

    if two_lane:
        keys2 = _joined(keys2_parts)
        if pad:
            keys2 = jnp.concatenate(
                [keys2, jnp.full(pad, _SENTINEL, dtype=keys2.dtype)]
            )
        scalars, group_hi, group_lo, counts = _finalize2_fn()(
            keys, keys2, n_sentinel
        )
        scalars = packed_device_get(scalars)
        return TwoLaneDeviceFrequencies(
            plan.columns,
            scalars,
            group_hi,
            group_lo,
            counts,
            list(dictionaries),
            list(sizes),
            split,
        )

    scalars, group_keys, counts = _finalize_fn()(keys, n_sentinel)
    scalars = packed_device_get(scalars)
    return DeviceFrequencies(
        plan.columns,
        np.dtype(np.int64),
        scalars,
        group_keys,
        counts,
        0,
        False,
        joint=(list(dictionaries), list(sizes)),
    )


def device_spill_frequencies(
    dataset: Dataset, plan, engine
) -> "DeviceFrequencies":
    """One high-cardinality frequency pass fully on device (sharded
    across the engine's mesh when one is set)."""
    from deequ_tpu import config
    from deequ_tpu.engine.scan import CHUNK_BATCHES
    from deequ_tpu.sql.predicate import compile_predicate

    column = plan.columns[0]
    values_dtype = dataset.request_dtype(ColumnRequest(column, "values"))
    if values_dtype.kind != "f":
        key_kind = "int"
    elif np.dtype(values_dtype).itemsize == 8:
        key_kind = "f64"
    else:
        key_kind = "f32"
    requests = [
        ColumnRequest(column, "values"),
        ColumnRequest(column, "mask"),
    ]
    pred = None
    if plan.where is not None:
        pred = compile_predicate(plan.where, dataset)
        requests += list(pred.requests)

    import jax as _jax

    host_f64 = key_kind == "f64" and _jax.default_backend() != "cpu"

    if engine is not None and getattr(engine, "mesh", None) is not None:
        # f64 on non-CPU meshes rides host-packed bits inside the
        # sharded build — see _sharded_spill_frequencies
        return _sharded_spill_frequencies(
            dataset, plan, engine, column, values_dtype, key_kind, pred
        )

    batch_size = engine._resolve_batch_size(dataset.num_rows)
    nb = dataset.num_batches(batch_size)
    chunk_batches = min(CHUNK_BATCHES, nb)
    _count_data_pass()  # deferred re-scan: one traversal per plan

    if host_f64:
        # u64 keys packed on the HOST (host_f64_u64_keys; the TPU X64
        # rewriter cannot lower the f64->u64 bitcast — measured r4),
        # shipped instead of the values: same wire bytes, and the
        # device sort/segment path below is shared untouched
        parts, n_sent, n_nul = [], 0, 0
        for batch in dataset.device_batches(requests, batch_size):
            rows = np.asarray(batch[ROW_MASK], dtype=bool)
            if pred is not None:
                rows = rows & np.asarray(pred.complies(batch), dtype=bool)
            k, ns, nn = host_f64_u64_keys(
                batch[f"{column}::values"],
                np.asarray(batch[f"{column}::mask"], dtype=bool),
                rows,
                bool(plan.include_nulls),
            )
            parts.append(k)
            n_sent += ns
            n_nul += nn
        host_keys = (
            np.concatenate(parts) if len(parts) > 1 else parts[0]
        )
        from deequ_tpu.data.table import add_transfer_bytes

        add_transfer_bytes(host_keys.nbytes)
        keys = _jax.device_put(host_keys)
        n_sentinel = jnp.int64(n_sent)
        n_null = jnp.int64(n_nul)
    else:
        key_fn = _chunk_key_fn(key_kind, bool(plan.include_nulls))

        keys_parts = []
        n_sentinel = jnp.int64(0)
        n_null = jnp.int64(0)
        for chunk in dataset.device_scan_chunks(
            requests,
            batch_size,
            chunk_batches=chunk_batches,
            budget_bytes=config.options().device_cache_bytes,
        ):
            rows = chunk[ROW_MASK]
            if pred is not None:
                flat = {k: v.reshape(-1) for k, v in chunk.items()}
                rows = rows & pred.complies(flat).reshape(rows.shape)
            k, ns, nn = key_fn(
                chunk[f"{column}::values"], chunk[f"{column}::mask"], rows
            )
            keys_parts.append(k)
            n_sentinel = n_sentinel + ns
            n_null = n_null + nn

        keys = (
            jnp.concatenate(keys_parts)
            if len(keys_parts) > 1
            else keys_parts[0]
        )
    # pad to pow2 so the (expensive-to-compile) sort program is shared
    # across datasets whose row counts round the same way
    n = keys.shape[0]
    padded = 1 << max(1, int(n - 1).bit_length()) if n > 1 else 1
    if padded != n:
        keys = jnp.concatenate(
            [keys, jnp.full(padded - n, _SENTINEL, dtype=keys.dtype)]
        )
        n_sentinel = n_sentinel + (padded - n)

    scalars, group_keys, counts = _finalize_fn()(keys, n_sentinel)
    from deequ_tpu.engine.pack import packed_device_get

    fetched = packed_device_get((scalars, n_null))
    scalars, n_null_host = fetched
    return DeviceFrequencies(
        plan.columns,
        values_dtype,
        scalars,
        group_keys,
        counts,
        int(n_null_host),
        bool(plan.include_nulls),
    )


def _sharded_spill_frequencies(
    dataset: Dataset,
    plan,
    engine,
    column: str,
    values_dtype: np.dtype,
    key_kind: str,
    pred,
) -> "ShardedDeviceFrequencies":
    """Mesh variant: build the global u64 key vector (row-sharded over
    the dp axis), then run the hash-bucket all_to_all re-shard + local
    sort (see _sharded_spill_fn). Raises SpillOverflow when a bucket
    exceeds its static capacity; the caller falls back to Arrow."""
    import jax as _jax

    needed = {ColumnRequest(column, "values"), ColumnRequest(column, "mask")}
    if pred is not None:
        needed.update(pred.requests)
    include_nulls = bool(plan.include_nulls)
    host_bits = key_kind == "f64" and (
        _jax.default_backend() != "cpu" or _FORCE_HOST_F64_BITS
    )
    extra = None
    if host_bits:
        # the TPU X64 rewriter can't lower the f64 bitcast, so the
        # canonical u64 bits pack on the HOST and stage like a column;
        # the jitted build only applies mask/sentinel bookkeeping
        if pred is None or ColumnRequest(column, "values") not in set(
            pred.requests
        ):  # the predicate may still need the raw values
            needed.discard(ColumnRequest(column, "values"))
        extra = {
            "__f64bits__": f64_canonical_bits(
                dataset.materialize(ColumnRequest(column, "values"))
            )
        }
    key_fn = (
        None if host_bits else _chunk_key_fn(key_kind, include_nulls)
    )

    def build(batch):
        rows = batch[ROW_MASK]
        if pred is not None:
            rows = rows & pred.complies(batch)
        if host_bits:  # bits pre-canonicalized on the host; shared
            # sentinel/null bookkeeping (_finish_keys, the one copy)
            return _finish_keys(
                batch["__f64bits__"],
                batch[f"{column}::mask"],
                rows,
                include_nulls,
            )
        return key_fn(
            batch[f"{column}::values"], batch[f"{column}::mask"], rows
        )

    scalars, g_keys, g_counts, segs_host, n_null_host = _sharded_shuffle(
        dataset, engine, needed, build, label=repr(column),
        extra_arrays=extra,
    )
    state = ShardedDeviceFrequencies(
        plan.columns,
        values_dtype,
        scalars,
        g_keys,
        g_counts,
        n_null_host,
        bool(plan.include_nulls),
    )
    state._dev = (g_keys, g_counts, segs_host)
    return state


# --------------------------------------------------------------------------
# one-pass collectors: spill key extraction riding the SHARED fused scan
# --------------------------------------------------------------------------


class CollectorSpec:
    """One spill plan's ride on the shared fused scan.

    ``requests`` + ``ops`` slot into ``engine.run_scan`` next to the
    scalar/dense ops; the ops' state is the device-resident key buffer
    (``ScanOps.device_result`` keeps it out of the epilogue fetch).
    After the scan, ``dispatch(final_state)`` launches this plan's
    sort + segment-count finalize ASYNC and returns
    ``(pending, build)``: the caller dispatches EVERY plan first —
    overlapping the per-plan sorts on device — then fetches all
    pendings in one packed transfer and calls ``build(fetched)`` to
    construct the FrequenciesAndNumRows state. ``build`` may raise
    :class:`SpillOverflow` (sharded hash bucket past capacity); the
    planner attaches ``overflow_fallback`` (host Arrow) and
    ``scan_fallback`` (the deferred per-plan re-scan, for when the
    shared scan itself fails) plus ``on_success`` telemetry."""

    def __init__(self, plan, requests, ops, path, dispatch):
        self.plan = plan
        self.requests = list(requests)
        self.ops = ops
        self.path = path  # telemetry label ("device-sort"[-joint])
        self._dispatch = dispatch
        # wired by the planner (grouping.plan_frequency_passes)
        self.on_success = lambda: None
        self.overflow_fallback = None
        self.scan_fallback = None

    def dispatch(self, state):
        return self._dispatch(state)


def _pow2_len(n: int) -> int:
    """The key-vector padding rule the deferred path uses (pad to pow2
    so the expensive-to-compile sort program is shared across datasets
    whose row counts round the same way) — collector buffers MUST use
    the identical rule for bit-identical finalize inputs."""
    return 1 << max(1, int(n - 1).bit_length()) if n > 1 else 1


def _collector_geometry(dataset: Dataset, engine):
    """(mesh, axis, ndev, local_cap): buffer geometry for a collector.

    ``local_cap`` is the pow2-padded per-device key capacity derived
    from the shared scan's exact row feed (``engine.scan_row_capacity``
    — every batch row including the zero-padded tail lands in the
    buffer; padding rows key to the sentinel like any dropped row).
    Single-device this equals the deferred path's padded key length
    exactly; under a mesh it matches _stage_mesh_columns' per-shard
    ``m_local`` whenever the default batch geometry is in effect."""
    capacity = engine.scan_row_capacity(dataset)
    mesh = getattr(engine, "mesh", None)
    if mesh is None:
        return None, None, 1, _pow2_len(capacity)
    axis = engine.dp_axis
    ndev = mesh.shape[axis]
    # batch_size is rounded to an ndev multiple, so this divides evenly
    return mesh, axis, ndev, _pow2_len(max(1, capacity // ndev))


def _mesh_bucket_cap(m_local: int, ndev: int) -> int:
    """The sharded shuffle's per-(sender, bucket) capacity — the SAME
    formula as _stage_mesh_columns so compiled shuffle programs are
    shared between the collector and deferred forms."""
    return 1 << max(8, ((4 * m_local) // ndev - 1).bit_length())


def _collector_ops(batch_keys, mesh, axis, ndev, local_cap, n_lanes,
                   cache_token):
    """Build the collector ``ScanOps``: state is ``(buffers, offset,
    n_sentinel, n_null)`` where each buffer is a sentinel-filled u64
    key lane — flat ``(local_cap,)`` single-device, or
    ``(ndev, local_cap)`` sharded ``P(axis, None)`` under a mesh so
    each shard appends its own rows and the dynamic write offset lives
    on the replicated dim. ``batch_keys(batch, consts)`` -> (lanes
    tuple, n_sentinel, n_null) per batch; every batch appends exactly
    its row count, so the final offset is statically full — unwritten
    pow2-padding slots stay sentinel and are added to the correction
    at dispatch time, exactly like the deferred path's explicit pad."""
    from deequ_tpu.analyzers.base import ScanOps

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P(axis, None))

        def make_buffer():
            return jax.device_put(
                jnp.full((ndev, local_cap), _SENTINEL, dtype=jnp.uint64),
                sharding,
            )
    else:

        def make_buffer():
            return jnp.full(local_cap, _SENTINEL, dtype=jnp.uint64)

    def init():
        return (
            tuple(make_buffer() for _ in range(n_lanes)),
            jnp.int64(0),  # rows written (per shard under a mesh)
            jnp.int64(0),  # sentinel (non-contributing) rows so far
            jnp.int64(0),  # null rows kept (include_nulls plans)
        )

    def update(state, batch, consts=None):
        buffers, offset, ns, nn = state
        lanes, s, null = batch_keys(batch, consts)
        if mesh is not None:
            written = lanes[0].shape[0] // ndev
            new_buffers = tuple(
                jax.lax.dynamic_update_slice(
                    buf,
                    lane.reshape(ndev, written),
                    (jnp.int32(0), offset.astype(jnp.int32)),
                )
                for buf, lane in zip(buffers, lanes)
            )
        else:
            written = lanes[0].shape[0]
            new_buffers = tuple(
                jax.lax.dynamic_update_slice(
                    buf, lane, (offset.astype(jnp.int32),)
                )
                for buf, lane in zip(buffers, lanes)
            )
        return (new_buffers, offset + written, ns + s, nn + null)

    def merge(a, b):
        raise NotImplementedError(
            "collector states accumulate through ONE shared scan; "
            "they never merge across scans"
        )

    return ScanOps(
        init, update, merge, cache_token=cache_token, device_result=True
    )


def single_collector_spec(
    dataset: Dataset, plan, engine
) -> "CollectorSpec":
    """The one-pass twin of device_spill_frequencies: a CollectorSpec
    whose ops accumulate the single grouping column's u64 keys through
    the shared scan, and whose dispatch runs the identical finalize
    (single-device sort or sharded shuffle) over the buffer."""
    import jax as _jax
    from deequ_tpu.sql.predicate import compile_predicate

    column = plan.columns[0]
    values_dtype = dataset.request_dtype(ColumnRequest(column, "values"))
    if values_dtype.kind != "f":
        key_kind = "int"
    elif np.dtype(values_dtype).itemsize == 8:
        key_kind = "f64"
    else:
        key_kind = "f32"
    include_nulls = bool(plan.include_nulls)
    # f64 on backends whose X64 rewriter can't lower the bitcast (TPU):
    # the canonical u64 bits pack on the HOST as the "u64bits" column
    # repr and ride the normal batch pipeline — still one pass
    host_bits = key_kind == "f64" and (
        _jax.default_backend() != "cpu" or _FORCE_HOST_F64_BITS
    )
    value_req = ColumnRequest(column, "u64bits" if host_bits else "values")
    requests = [value_req, ColumnRequest(column, "mask")]
    pred = None
    if plan.where is not None:
        pred = compile_predicate(plan.where, dataset)
        requests += list(pred.requests)

    mesh, axis, ndev, local_cap = _collector_geometry(dataset, engine)
    key_fn = None if host_bits else _chunk_key_fn(key_kind, include_nulls)

    def batch_keys(batch, _consts):
        rows = batch[ROW_MASK]
        if pred is not None:
            rows = rows & pred.complies(batch)
        if host_bits:
            k, s, null = _finish_keys(
                batch[value_req.key], batch[f"{column}::mask"], rows,
                include_nulls,
            )
        else:
            k, s, null = key_fn(
                batch[value_req.key], batch[f"{column}::mask"], rows
            )
        return (k,), s, null

    token = None
    if pred is None or getattr(pred, "dataset_independent", False):
        token = (
            "spill-collector", (column,), key_kind, host_bits,
            include_nulls, plan.where, local_cap, ndev,
        )
    ops = _collector_ops(
        batch_keys, mesh, axis, ndev, local_cap, 1, token
    )

    if mesh is None:

        def dispatch(state):
            (buf,), off, ns, nn = state
            # unwritten pow2 tail slots hold the sentinel from init
            ns_total = ns + (jnp.int64(local_cap) - off)
            scalars, group_keys, counts = _finalize_fn()(buf, ns_total)

            def build(fetched):
                scalars_h, n_null_h = fetched
                return DeviceFrequencies(
                    plan.columns, values_dtype, scalars_h, group_keys,
                    counts, int(n_null_h), include_nulls,
                )

            return (scalars, nn), build

    else:
        cap = _mesh_bucket_cap(local_cap, ndev)

        def dispatch(state):
            (buf,), off, ns, nn = state
            # per-shard unwritten slots x ndev shards
            ns_total = ns + (jnp.int64(ndev * local_cap) - off * ndev)
            out = _sharded_spill_fn(mesh, axis, cap)(
                buf.reshape(-1), ns_total, nn
            )
            scalars, g_keys, g_counts, g_segs, overflow, n_null_g = out

            def build(fetched):
                scalars_h, overflow_h, n_null_h, segs_h = fetched
                if int(overflow_h) > 0:
                    raise SpillOverflow(
                        f"hash bucket exceeded capacity {cap} on "
                        f"{column!r}"
                    )
                st = ShardedDeviceFrequencies(
                    plan.columns, values_dtype, scalars_h, g_keys,
                    g_counts, int(n_null_h), include_nulls,
                )
                st._dev = (g_keys, g_counts, segs_h)
                return st

            return (scalars, overflow, n_null_g, g_segs), build

    return CollectorSpec(plan, requests, ops, "device-sort", dispatch)


def joint_collector_spec(
    dataset: Dataset, plan, engine, dictionaries, sizes
) -> "CollectorSpec":
    """The one-pass twin of device_spill_joint_frequencies: joint
    mixed-radix codes on one u64 lane (or two past 2^62) accumulate
    through the shared scan; dispatch runs the matching finalize."""
    from deequ_tpu.sql.predicate import compile_predicate

    columns = list(plan.columns)
    split = split_joint_lanes(tuple(sizes))
    if split is None:  # eligibility should have gated; double-check
        raise SpillOverflow("joint key space exceeds two u64 lanes")
    two_lane = split < len(columns)
    requests = [ColumnRequest(c, "codes") for c in columns] + [
        ColumnRequest(c, "mask") for c in columns
    ]
    pred = None
    if plan.where is not None:
        pred = compile_predicate(plan.where, dataset)
        requests += list(pred.requests)

    mesh, axis, ndev, local_cap = _collector_geometry(dataset, engine)

    # per-column radix sizes ride ScanOps.consts (runtime inputs, like
    # the dense ops' LUTs) so compiled plans stay shareable
    if two_lane:
        consts = {
            "sizes1": np.asarray(sizes[:split], dtype=np.int64),
            "sizes2": np.asarray(sizes[split:], dtype=np.int64),
        }
        key2_fn = _joint_chunk_key2_fn(split, len(columns) - split)
    else:
        consts = {"sizes": np.asarray(sizes, dtype=np.int64)}
        key_fn = _joint_chunk_key_fn(len(columns))

    def batch_keys(batch, c):
        rows = batch[ROW_MASK]
        if pred is not None:
            rows = rows & pred.complies(batch)
        codes = tuple(batch[f"{col}::codes"] for col in columns)
        masks = tuple(batch[f"{col}::mask"] for col in columns)
        if two_lane:
            k1, k2, s = key2_fn(
                codes, masks, rows, c["sizes1"], c["sizes2"]
            )
            return (k1, k2), s, jnp.int64(0)
        k, s = key_fn(codes, masks, rows, c["sizes"])
        return (k,), s, jnp.int64(0)  # no null group (gated)

    token = None
    if pred is None or getattr(pred, "dataset_independent", False):
        token = (
            "spill-collector-joint", tuple(columns), two_lane, split,
            plan.where, local_cap, ndev,
        )
    ops = _collector_ops(
        batch_keys, mesh, axis, ndev, local_cap,
        2 if two_lane else 1, token,
    )
    ops.consts = consts
    joint = (list(dictionaries), list(sizes))

    if mesh is None:
        if two_lane:

            def dispatch(state):
                (b1, b2), off, ns, _nn = state
                ns_total = ns + (jnp.int64(local_cap) - off)
                scalars, g_hi, g_lo, counts = _finalize2_fn()(
                    b1, b2, ns_total
                )

                def build(fetched):
                    return TwoLaneDeviceFrequencies(
                        plan.columns, fetched, g_hi, g_lo, counts,
                        joint[0], joint[1], split,
                    )

                return scalars, build

        else:

            def dispatch(state):
                (buf,), off, ns, _nn = state
                ns_total = ns + (jnp.int64(local_cap) - off)
                scalars, group_keys, counts = _finalize_fn()(
                    buf, ns_total
                )

                def build(fetched):
                    return DeviceFrequencies(
                        plan.columns, np.dtype(np.int64), fetched,
                        group_keys, counts, 0, False, joint=joint,
                    )

                return scalars, build

    else:
        cap = _mesh_bucket_cap(local_cap, ndev)
        if two_lane:

            def dispatch(state):
                (b1, b2), _off, _ns, _nn = state
                # the 2-lane shuffle drops sentinel rows itself; no
                # correction scalar enters (matching _sharded_shuffle2)
                out = _sharded_spill2_fn(mesh, axis, cap)(
                    b1.reshape(-1), b2.reshape(-1)
                )
                scalars, g_hi, g_lo, g_counts, g_segs, overflow = out

                def build(fetched):
                    scalars_h, overflow_h, segs_h = fetched
                    if int(overflow_h) > 0:
                        raise SpillOverflow(
                            f"hash bucket exceeded capacity {cap} on "
                            f"joint2 {columns!r}"
                        )
                    st = ShardedTwoLaneDeviceFrequencies(
                        plan.columns, scalars_h, g_hi, g_lo, g_counts,
                        joint[0], joint[1], split,
                    )
                    st._segs = segs_h
                    return st

                return (scalars, overflow, g_segs), build

        else:

            def dispatch(state):
                (buf,), off, ns, _nn = state
                ns_total = ns + (
                    jnp.int64(ndev * local_cap) - off * ndev
                )
                out = _sharded_spill_fn(mesh, axis, cap)(
                    buf.reshape(-1), ns_total, jnp.int64(0)
                )
                scalars, g_keys, g_counts, g_segs, overflow, _nng = out

                def build(fetched):
                    scalars_h, overflow_h, segs_h = fetched
                    if int(overflow_h) > 0:
                        raise SpillOverflow(
                            f"hash bucket exceeded capacity {cap} on "
                            f"joint {columns!r}"
                        )
                    st = ShardedDeviceFrequencies(
                        plan.columns, np.dtype(np.int64), scalars_h,
                        g_keys, g_counts, 0, False, joint=joint,
                    )
                    st._dev = (g_keys, g_counts, segs_h)
                    return st

                return (scalars, overflow, g_segs), build

    return CollectorSpec(
        plan, requests, ops, "device-sort-joint", dispatch
    )


# --------------------------------------------------------------------------
# cross-host (multi-process) spill — docs/MULTIHOST.md steps 1-4
# --------------------------------------------------------------------------


class MultihostDeviceFrequencies(ShardedDeviceFrequencies):
    """ShardedDeviceFrequencies whose shards span PROCESSES: count
    metrics read the replicated psum scalars (fetchable on every
    host); Histogram's top-k merges per-shard candidates gathered
    across processes; the full (keys, counts) union is gathered only
    if something actually reads ``.keys``/``.counts`` (persistence).

    COLLECTIVE CONTRACT: ``top_groups`` / ``.keys`` / ``.counts``
    issue ``process_allgather`` collectives lazily — EVERY process
    must reach them together (SPMD), exactly like the call that built
    this state. Reading them from one process only (e.g. inside an
    ``if process_index() == 0:`` block) strands the peers in the
    collective. The scalar count metrics (CountDistinct/Uniqueness/
    Distinctness/Entropy) are replicated and safe to read anywhere."""

    def _local_live_pairs(self):
        """(keys, counts) concatenated over THIS process's shards."""
        g_keys, g_counts, g_segs = self._dev
        segs_by_dev = {
            s.device: int(np.asarray(s.data)[0])
            for s in g_segs.addressable_shards
        }
        counts_by_dev = {
            s.device: np.asarray(s.data)
            for s in g_counts.addressable_shards
        }
        keys_parts, count_parts = [], []
        for s in g_keys.addressable_shards:
            seg = segs_by_dev[s.device]
            raw_k = np.asarray(s.data)[:seg]
            raw_c = counts_by_dev[s.device][:seg]
            live = raw_c > 0
            keys_parts.append(raw_k[live])
            count_parts.append(raw_c[live].astype(np.int64))
        if not keys_parts:
            return (
                np.zeros(0, np.uint64),
                np.zeros(0, np.int64),
            )
        return (
            np.concatenate(keys_parts),
            np.concatenate(count_parts),
        )

    @staticmethod
    def _allgather_varlen(keys: np.ndarray, counts: np.ndarray):
        """Gather variable-length (keys, counts) from every process:
        sizes first, pad to the max, one fixed-shape allgather."""
        import jax.numpy as jnp
        from jax.experimental import multihost_utils

        n = len(keys)
        sizes = np.asarray(
            multihost_utils.process_allgather(
                jnp.asarray([n], dtype=jnp.int64)
            )
        ).reshape(-1)
        cap = int(sizes.max()) if len(sizes) else 0
        if cap == 0:
            return np.zeros(0, np.uint64), np.zeros(0, np.int64)
        pk = np.zeros(cap, np.uint64)
        pk[:n] = keys
        pc = np.zeros(cap, np.int64)
        pc[:n] = counts
        gk = np.asarray(
            multihost_utils.process_allgather(
                jnp.asarray(pk.view(np.int64))
            )
        ).reshape(-1, cap)
        gc = np.asarray(
            multihost_utils.process_allgather(jnp.asarray(pc))
        ).reshape(-1, cap)
        out_k, out_c = [], []
        for p, sz in enumerate(sizes):
            out_k.append(gk[p, : int(sz)].view(np.uint64))
            out_c.append(gc[p, : int(sz)])
        return np.concatenate(out_k), np.concatenate(out_c)

    def _fetch(self) -> None:
        if self._counts_host is None:
            keys, counts = self._allgather_varlen(
                *self._local_live_pairs()
            )
            if self._legit_max > 0:
                keys = np.concatenate(
                    [keys, np.array([_SENTINEL], dtype=np.uint64)]
                )
                counts = np.concatenate(
                    [counts, np.array([self._legit_max], np.int64)]
                )
            self._keys_host = keys
            self._counts_host = counts
        self._set_joint_lazy()

    def top_groups(self, k: int):
        # per-process top-k candidates (shards own disjoint key
        # ranges, so the global top-k is within the union of
        # per-process top-k when each contributes k candidates)
        keys, counts = self._local_live_pairs()
        if len(counts) > k:
            order = np.argsort(-counts, kind="stable")[:k]
            keys, counts = keys[order], counts[order]
        g_keys, g_counts = self._allgather_varlen(keys, counts)
        if self._legit_max > 0:
            g_keys = np.concatenate(
                [g_keys, np.array([_SENTINEL], dtype=np.uint64)]
            )
            g_counts = np.concatenate(
                [g_counts, np.array([self._legit_max], np.int64)]
            )
        order = np.argsort(-g_counts, kind="stable")[:k]
        pairs = list(
            zip(self._decode_keys(g_keys[order]), g_counts[order])
        )
        return _pack_top_pairs(
            pairs, k, self._null_rows if self._has_null_group else 0
        )


def multihost_spill_frequencies(
    dataset: Dataset, plan, mesh, axis: str = "dp"
) -> "MultihostDeviceFrequencies":
    """High-cardinality frequencies across PROCESSES (docs/MULTIHOST.md
    'High-cardinality grouping across hosts', steps 1-4): every process
    holds ITS OWN shard-table; u64 keys build locally, assemble into
    one globally-sharded array (``make_array_from_process_local_data``),
    and the SAME bucketed ``all_to_all`` shuffle + per-shard sort +
    segment count (_sharded_spill_fn) runs SPMD across hosts — equal
    keys land on one device wherever their rows lived, key ranges end
    up disjoint, and the count metrics psum into replicated scalars no
    host ever re-merges. The 10M-group state never crosses hosts;
    Histogram fetches only per-shard top-k candidates.

    ``where`` predicates evaluate PER ROW on each host's own shard
    (compiled against that shard's dictionaries) before the key build,
    so any supported predicate works — the shuffle only ever sees the
    surviving keys. Scope: single grouping column. Raises
    SpillOverflow exactly like the single-host path when a hash bucket
    exceeds its static capacity."""
    import jax
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    column = plan.columns[0]
    values_dtype = dataset.request_dtype(ColumnRequest(column, "values"))
    if values_dtype.kind != "f":
        key_kind = "int"
    elif np.dtype(values_dtype).itemsize == 8:
        key_kind = "f64"
    else:
        key_kind = "f32"
    host_bits = key_kind == "f64" and (
        jax.default_backend() != "cpu" or _FORCE_HOST_F64_BITS
    )

    pred = None
    pred_error: Optional[BaseException] = None
    if plan.where is not None:
        from deequ_tpu.sql.predicate import compile_predicate

        # compile BEFORE any collective — and make the outcome
        # UNIFORM: plan-budget checks depend on each shard's own
        # dictionaries, so one host can fail where another succeeds;
        # raising on only one host would strand its peers in the next
        # allgather forever (review finding). The first collective is
        # therefore a success-flag exchange every host participates in.
        try:
            pred = compile_predicate(plan.where, dataset)
        except Exception as exc:  # noqa: BLE001 — exchanged below
            pred_error = exc
    ok_flags = np.asarray(
        multihost_utils.process_allgather(
            jax.numpy.asarray(
                [0 if pred_error is not None else 1],
                dtype=jax.numpy.int32,
            )
        )
    ).reshape(-1)
    if not ok_flags.all():
        bad = [int(i) for i in np.nonzero(ok_flags == 0)[0]]
        raise ValueError(
            f"where-predicate compilation failed on host(s) {bad}"
            + (f": {pred_error!r}" if pred_error is not None else "")
        )

    ndev = mesh.shape[axis]
    local_devices = [
        d for d in mesh.devices.flat
        if d.process_index == jax.process_index()
    ]
    n_local_dev = len(local_devices)
    n_local = dataset.num_rows

    # globally agreed per-device capacity: every process computes the
    # same pow2 from the allgathered (rows, devices) pairs
    shape_info = np.asarray(
        multihost_utils.process_allgather(
            jax.numpy.asarray([n_local, n_local_dev], dtype=jax.numpy.int64)
        )
    ).reshape(-1, 2)
    per_dev_needed = int(
        max(-(-int(r) // max(int(d), 1)) for r, d in shape_info)
    )
    per_dev = 1 << max(1, (max(per_dev_needed, 1) - 1).bit_length())
    padded_local = per_dev * n_local_dev

    def pad_to(host: np.ndarray) -> np.ndarray:
        if len(host) < padded_local:
            host = np.concatenate(
                [host, np.zeros(padded_local - len(host), host.dtype)]
            )
        return host

    _count_data_pass()  # materializes the shard's columns: one pass
    values = pad_to(dataset.materialize(ColumnRequest(column, "values")))
    mask = pad_to(dataset.materialize(ColumnRequest(column, "mask")))
    rows = np.zeros(padded_local, dtype=bool)
    rows[:n_local] = True
    if pred is not None:
        batch = {
            r.key: pad_to(
                np.asarray(dataset.materialize(r))
            )
            for r in pred.requests
        }
        # one-shot eager eval (like the host_f64 key path): a fresh
        # jit wrapper here would recompile per call (review finding)
        complies = np.asarray(
            jax.device_get(pred.complies(batch)), dtype=bool
        )
        rows = rows & complies

    if host_bits:
        bits = pad_to(f64_canonical_bits(values[:n_local]))
        keys_local, n_sent_l, n_null_l = _finish_keys_jit(
            plan.include_nulls
        )(bits, mask, rows)
    else:
        keys_local, n_sent_l, n_null_l = _chunk_key_fn(
            key_kind, plan.include_nulls
        )(values, mask, rows)

    # global scalar bookkeeping: one tiny allgather
    sums = np.asarray(
        multihost_utils.process_allgather(
            jax.numpy.asarray(
                [int(n_sent_l), int(n_null_l)], dtype=jax.numpy.int64
            )
        )
    ).reshape(-1, 2)
    n_sent = int(sums[:, 0].sum())
    n_null = int(sums[:, 1].sum())

    sharding = NamedSharding(mesh, P(axis))
    g_keys = jax.make_array_from_process_local_data(
        sharding, np.asarray(keys_local)
    )
    cap = 1 << max(8, ((4 * per_dev) // ndev - 1).bit_length())
    out = _sharded_spill_fn(mesh, axis, cap)(
        g_keys,
        jax.numpy.int64(n_sent),
        jax.numpy.int64(n_null),
    )
    scalars, gk, gc, g_segs, overflow, _ = out
    host_scalars = {
        k: np.asarray(jax.device_get(v)) for k, v in scalars.items()
    }
    if int(np.asarray(jax.device_get(overflow))) > 0:
        raise SpillOverflow(
            f"hash bucket exceeded capacity {cap} on {column!r} "
            "(multihost)"
        )
    state = MultihostDeviceFrequencies(
        plan.columns,
        values_dtype,
        host_scalars,
        gk,
        gc,
        n_null,
        bool(plan.include_nulls),
    )
    state._dev = (gk, gc, g_segs)
    return state
