"""Vectorizing scan planner: stack same-kind columns into (C, B) ops.

The fused scan (engine/scan.py) makes N analyzers cost ONE data pass,
but each analyzer still lowers its own masked reduction: a 50-column
profile emits hundreds of HLO reduce/scatter ops, which bloats XLA
compile time (the dominant cold-start cost for big plans) and leaves
per-kernel overhead on the table.

This planner groups analyzers of the same FAMILY over columns of the
same device dtype and the same ``where`` filter into one stacked op:

- ``stats``        — Mean/Sum/Minimum/Maximum/StandardDeviation (values)
                     and MinLength/MaxLength (lengths): one (C, B)
                     masked reduction per needed statistic, Welford/Chan
                     vectorized over the column axis;
- ``completeness`` — Completeness: one (C, B) mask count;
- ``hll``          — ApproxCountDistinct: hashes computed on the stacked
                     block, registers updated with ONE scatter-max into
                     a (C*M,) vector;
- ``datatype``     — DataType over string columns: stacked code->bucket
                     LUT gather + one scatter-add.

Group states hold (C,)-shaped leaves; after the scan each member
analyzer's ordinary state (states.py types) is SLICED back out, so
metric finalization, state persistence, and incremental merge are
unchanged. Numerics mirror the scalar paths in analyzers/basic.py
exactly (same masked-neutral elements, same dtype widenings, same
Welford/Chan batch merge) — only the reduction batching differs.

Reference analog: deequ fuses analyzers into one ``df.agg`` but leaves
per-expression evaluation to Tungsten (SURVEY.md §2.2); stacking is the
TPU-shaped version of that fusion, feeding the VPU 8x32-lane grid full
columns-by-rows tiles instead of one row stream per expression.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deequ_tpu.analyzers import states as S
from deequ_tpu.analyzers.base import ScanOps, pad_pow2
from deequ_tpu.analyzers.basic import (
    _compile_where,
    _row_mask,
    _acc_float,
)
from deequ_tpu.data.table import LANE_HASH, ColumnRequest, Dataset, Kind
from deequ_tpu.sketches import hll

_F64 = jnp.float64


@dataclass
class ScanUnit:
    """One engine slot: either a single analyzer's ops or a vectorized
    group. ``extract(state, member_index)`` slices a member's ordinary
    state out of a group state (None for singles)."""

    members: List[Any]  # analyzers, in column order
    ops: ScanOps
    requests: List[ColumnRequest]
    extract: Optional[Callable[[Any, int], Any]] = None

    # engine adapter: run_scan asks analyzers for device_requests
    def device_requests(self, dataset: Dataset) -> List[ColumnRequest]:
        return self.requests


def _index_members(members: Sequence[Any]) -> Tuple[List[str], List[int]]:
    """Dedup member columns preserving order; returns (columns,
    member->column-index map)."""
    columns: List[str] = []
    col_index: Dict[str, int] = {}
    for a in members:
        if a.column not in col_index:
            col_index[a.column] = len(columns)
            columns.append(a.column)
    return columns, [col_index[a.column] for a in members]


def _stack_luts(luts: List[np.ndarray], fill=0) -> np.ndarray:
    """Stack per-column LUTs into one (C, L) const, padding every LUT to
    the group max and then to a power of two (see base.pad_pow2)."""
    width = max(len(lut) for lut in luts)
    return np.stack(
        [
            pad_pow2(
                np.pad(lut, (0, width - len(lut)), constant_values=fill),
                fill,
            )
            for lut in luts
        ]
    )


def _delta_cap_of(dataset: Dataset, columns: List[str]) -> Optional[int]:
    """The group's delta-LUT capacity when EVERY member column ships
    one-pass dictionary deltas (data/parquet.py dict_delta_capacity),
    else None — the builders then keep the consts-LUT form. Consulting
    the dataset COMMITS the columns to delta mode, so the decision here
    and the dataset's device_batches behavior can never diverge."""
    cap_fn = getattr(dataset, "dict_delta_capacity", None)
    if cap_fn is None:
        return None
    caps = [cap_fn(c) for c in columns]
    if not caps or any(cap is None for cap in caps):
        return None
    return int(max(caps))


def _set_lut_row(lut, i: int, row: np.ndarray):
    """Overwrite row ``i`` of a (C, L) LUT state leaf, host numpy or
    device array alike (host_delta runs outside jit: numpy before the
    first dispatch, a committed device array after)."""
    if isinstance(lut, np.ndarray):
        out = lut.copy()
        out[i, :] = row
        return out
    # lint-ok: sync-discipline: converts the HOST numpy mirror row for
    # a .at[].set update; no device fetch happens here
    return lut.at[i].set(np.asarray(row))


def _delta_overflow(column: str, needed: int, cap: int) -> RuntimeError:
    return RuntimeError(
        f"dictionary for column {column!r} grew to {needed} values, "
        f"past dict_delta_capacity={cap}: raise "
        "DEEQU_TPU_DICT_DELTA_CAPACITY or set dict_deltas=False to "
        "fall back to the pre-pass consts path"
    )


# --------------------------------------------------------------------------
# shared per-batch prologue (cross-unit stack/sort memoization)
# --------------------------------------------------------------------------
#
# Every unit in a fused step receives the SAME batch dict object — the
# engine (scan.py fused_update / the resident scan body) builds it once
# per trace and hands it to each op in turn — so the first unit to need
# a stacked (C, B) block (or the KLL group's masked f32 sort, or the
# where-filter row mask) stores the traced value back into the dict
# under a reserved key and later units reuse it. Relying on XLA HLO CSE
# for this worked for the sort (two structurally identical subgraphs)
# but NOT for the per-family stacks and row masks, whose operand sets
# differ across groups; at 40 columns the repeated prologue work was a
# measured slice of the 2.4x in-engine KLL overhead (docs/PERF.md "KLL
# unit decomposition"). The reserved prefix can never collide with wire
# keys ("col::repr", "__buf_*", "__row_width__"), and the memo entries
# live only for the duration of one trace (the dict dies with it).

_SHARED_PREFIX = "__shared__:"


def _shared_stack(batch, columns, suffix):
    """Memoized ``jnp.stack([batch[f"{c}::{suffix}"] ...])``: one stack
    per (column tuple, repr) per fused step, shared across units."""
    key = _SHARED_PREFIX + suffix + ":" + "\x1f".join(columns)
    out = batch.get(key)
    if out is None:
        out = jnp.stack([batch[f"{c}::{suffix}"] for c in columns])
        batch[key] = out
    return out


def _shared_rows(batch, where_fn, where):
    """Memoized ``_row_mask``: one row-validity vector per (batch,
    where-expression) — every group with the same filter reuses it."""
    key = _SHARED_PREFIX + "rows:" + repr(where)
    out = batch.get(key)
    if out is None:
        out = _row_mask(batch, where_fn)
        batch[key] = out
    return out


def _where_ok_for_token(where: Optional[str], dataset: Dataset) -> bool:
    if where is None:
        return True
    from deequ_tpu.sql.predicate import compile_predicate

    return compile_predicate(where, dataset).dataset_independent


def _group_token(
    family: str,
    dataset: Dataset,
    columns: Sequence[str],
    where: Optional[str],
    extra: Tuple = (),
) -> Optional[tuple]:
    if not _where_ok_for_token(where, dataset):
        return None
    kinds = tuple((c, dataset.kind_token(c)) for c in columns)
    return ("vec", family, kinds, where, extra)


# --------------------------------------------------------------------------
# stats family
# --------------------------------------------------------------------------

_STATS_NEED = {
    "Mean": ("sum",),
    "Sum": ("sum",),
    "Minimum": ("min",),
    "Maximum": ("max",),
    "MinLength": ("min",),
    "MaxLength": ("max",),
    "StandardDeviation": ("sum", "welford"),
}


def _build_stats_group(
    dataset: Dataset,
    members: List[Any],
    repr_name: str,
    where: Optional[str],
) -> ScanUnit:
    """members: stats analyzers sharing (repr, value dtype, where)."""
    columns, member_cols = _index_members(members)
    needs = set()
    for a in members:
        needs.update(_STATS_NEED[type(a).__name__])
    where_fn, where_reqs = _compile_where(where, dataset)
    requests = [
        r
        for c in columns
        for r in (ColumnRequest(c, repr_name), ColumnRequest(c, "mask"))
    ] + where_reqs
    C = len(columns)
    acc = _acc_float()
    is_float = np.issubdtype(
        dataset.request_dtype(ColumnRequest(columns[0], repr_name)),
        np.floating,
    )

    def init():
        state = {"n": np.zeros(C, dtype=np.int64)}
        if "sum" in needs:
            state["sum"] = np.zeros(C, dtype=np.dtype(acc))
        if "min" in needs:  # NaN = nan_largest_min identity (states.py)
            state["min"] = np.full(C, np.nan, dtype=np.float64)
        if "max" in needs:
            state["max"] = np.full(C, -np.inf, dtype=np.float64)
        if "welford" in needs:
            state["w"] = S.StandardDeviationState(
                np.zeros(C), np.zeros(C), np.zeros(C)
            )
        return state

    def update(state, batch):
        x = _shared_stack(batch, columns, repr_name)
        masks = _shared_stack(batch, columns, "mask")
        masks = masks & _shared_rows(batch, where_fn, where)[None, :]
        new = dict(state)
        n_b = jnp.sum(masks, axis=1, dtype=jnp.int32).astype(jnp.int64)
        new["n"] = state["n"] + n_b
        sum_b = None
        if "sum" in needs:
            # mirrors basic._msum: float columns reduce in native dtype
            # (scalar cast to acc after); integrals widen per element to
            # f64 for exactness regardless of the accumulation knob
            if is_float:
                sum_b = jnp.sum(
                    jnp.where(masks, x, jnp.zeros((), x.dtype)), axis=1
                ).astype(acc)
            else:
                sum_b = jnp.sum(
                    jnp.where(masks, x, 0).astype(_F64), axis=1
                ).astype(acc)
            new["sum"] = state["sum"] + sum_b
        if "min" in needs:  # mirrors basic._mmin (NaN-largest ordering)
            from deequ_tpu.analyzers.basic import _mmin

            new["min"] = S.nan_largest_min(
                state["min"], _mmin(x, masks, axis=1)
            )
        if "max" in needs:  # mirrors basic._mmax
            neutral = (
                jnp.array(-jnp.inf, x.dtype)
                if is_float
                else jnp.array(jnp.iinfo(x.dtype).min, x.dtype)
            )
            new["max"] = jnp.maximum(
                state["max"],
                jnp.max(jnp.where(masks, x, neutral), axis=1).astype(_F64),
            )
        if "welford" in needs:
            # mirrors StandardDeviation.make_ops batch-state + Chan
            # merge, vectorized over the column axis
            xw = x if is_float else x.astype(_F64)
            nb_f = n_b.astype(_F64)
            safe_nb = jnp.maximum(nb_f, 1.0)
            mean_b = sum_b.astype(_F64) / safe_nb
            dx = jnp.where(
                masks, xw - mean_b.astype(xw.dtype)[:, None], 0
            )
            m2_b = jnp.sum(dx * dx, axis=1).astype(_F64)
            batch_state = S.StandardDeviationState(
                nb_f,
                jnp.where(nb_f > 0, mean_b, 0.0),
                jnp.where(nb_f > 0, m2_b, 0.0),
            )
            new["w"] = S.StandardDeviationState.merge(
                state["w"], batch_state
            )
        return new

    def merge(a, b):
        out = {"n": a["n"] + b["n"]}
        if "sum" in needs:
            out["sum"] = a["sum"] + b["sum"]
        if "min" in needs:
            out["min"] = S.nan_largest_min(a["min"], b["min"])
        if "max" in needs:
            out["max"] = jnp.maximum(a["max"], b["max"])
        if "welford" in needs:
            out["w"] = S.StandardDeviationState.merge(a["w"], b["w"])
        return out

    def extract(state, member_idx: int):
        i = member_cols[member_idx]
        a = members[member_idx]
        name = type(a).__name__
        n = state["n"][i]
        if name in ("Mean",):
            return S.MeanState(state["sum"][i], n)
        if name in ("Sum",):
            return S.SumState(state["sum"][i], n)
        if name in ("Minimum", "MinLength"):
            return S.MinState(state["min"][i], n)
        if name in ("Maximum", "MaxLength"):
            return S.MaxState(state["max"][i], n)
        w = state["w"]
        return S.StandardDeviationState(w.n[i], w.avg[i], w.m2[i])

    token = _group_token(
        "stats",
        dataset,
        columns,
        where,
        extra=(repr_name, tuple(sorted(needs)), "f" if is_float else "i"),
    )
    return ScanUnit(
        members,
        ScanOps(init, update, merge, cache_token=token),
        requests,
        extract,
    )


# --------------------------------------------------------------------------
# completeness family
# --------------------------------------------------------------------------


def _build_completeness_group(
    dataset: Dataset, members: List[Any], where: Optional[str]
) -> ScanUnit:
    columns, member_cols = _index_members(members)
    where_fn, where_reqs = _compile_where(where, dataset)
    requests = [ColumnRequest(c, "mask") for c in columns] + where_reqs
    C = len(columns)

    def init():
        return {
            "matches": np.zeros(C, dtype=np.int64),
            "rows": np.int64(0),
        }

    def update(state, batch):
        rows = _shared_rows(batch, where_fn, where)
        masks = _shared_stack(batch, columns, "mask")
        valid = masks & rows[None, :]
        return {
            "matches": state["matches"]
            + jnp.sum(valid, axis=1, dtype=jnp.int32).astype(jnp.int64),
            "rows": state["rows"]
            + jnp.sum(rows, dtype=jnp.int32).astype(jnp.int64),
        }

    def merge(a, b):
        return {
            "matches": a["matches"] + b["matches"],
            "rows": a["rows"] + b["rows"],
        }

    def extract(state, member_idx: int):
        return S.NumMatchesAndCount(
            state["matches"][member_cols[member_idx]], state["rows"]
        )

    token = _group_token("completeness", dataset, columns, where)
    return ScanUnit(
        members,
        ScanOps(init, update, merge, cache_token=token),
        requests,
        extract,
    )


# --------------------------------------------------------------------------
# hll family
# --------------------------------------------------------------------------


def _build_hll_group(
    dataset: Dataset,
    members: List[Any],
    value_repr: str,  # "values" (numeric) | "codes" | "hash" (string)
    where: Optional[str],
) -> ScanUnit:
    columns, member_cols = _index_members(members)
    where_fn, where_reqs = _compile_where(where, dataset)
    requests = [
        r
        for c in columns
        for r in (ColumnRequest(c, value_repr), ColumnRequest(c, "mask"))
    ] + where_reqs
    C = len(columns)

    consts = None
    host_delta = None
    delta_cap = None
    if value_repr == "codes":
        # one-pass dictionary deltas: when every member column ships
        # deltas, the hash LUTs move from consts into STATE at a fixed
        # (C, cap) shape and host_delta folds each delta's hash pairs
        # in as it arrives — no dictionary pre-pass at build time
        delta_cap = _delta_cap_of(dataset, columns)
        if delta_cap is None:
            luts1, luts2 = [], []
            for c in columns:
                h1, h2 = hll.dictionary_hash_pairs(dataset.dictionary(c))
                luts1.append(h1)
                luts2.append(h2)
            consts = {"h1": _stack_luts(luts1), "h2": _stack_luts(luts2)}

    def init():
        if delta_cap is not None:
            return {
                "registers": np.zeros((C, hll.M), dtype=np.int8),
                "h1": np.zeros((C, delta_cap), dtype=np.uint32),
                "h2": np.zeros((C, delta_cap), dtype=np.uint32),
            }
        return S.ApproxCountDistinctState(
            np.zeros((C, hll.M), dtype=np.int8)
        )

    def update(state, batch, consts_in=None):
        registers = (
            state["registers"] if delta_cap is not None else state.registers
        )
        masks = _shared_stack(batch, columns, "mask")
        masks = masks & _shared_rows(batch, where_fn, where)[None, :]
        if value_repr == "codes":
            codes = _shared_stack(batch, columns, "codes").astype(
                jnp.int32
            )
            if delta_cap is not None:
                lut1, lut2 = state["h1"], state["h2"]
            else:
                lut1, lut2 = consts_in["h1"], consts_in["h2"]
            if lut1.shape[1] <= hll.PRESENCE_DICT_CAP:
                # small dictionaries: presence compare-reduce + one
                # D-element scatter — bit-identical registers, no
                # per-row scatter (the measured throughput wall)
                regs = hll.registers_from_code_presence(
                    codes, masks, lut1, lut2
                )
            else:
                codes = jnp.clip(codes, 0, lut1.shape[1] - 1)
                h1 = jnp.take_along_axis(lut1, codes, axis=1)
                h2 = jnp.take_along_axis(lut2, codes, axis=1)
                regs = hll.registers_from_hash_pair_stacked(
                    h1, h2, masks
                )
        elif value_repr == LANE_HASH:
            # hashed-string lane columns: every row ships its hash
            h1, h2 = hll.lane_hash_pair(
                _shared_stack(batch, columns, LANE_HASH)
            )
            regs = hll.registers_from_hash_pair_stacked(h1, h2, masks)
        else:
            h1, h2 = hll.hash_pair_numeric(
                _shared_stack(batch, columns, "values")
            )
            regs = hll.registers_from_hash_pair_stacked(h1, h2, masks)
        new_regs = jnp.maximum(registers, regs)
        if delta_cap is not None:
            return {
                "registers": new_regs,
                "h1": state["h1"],
                "h2": state["h2"],
            }
        return S.ApproxCountDistinctState(new_regs)

    def extract(state, member_idx: int):
        regs = (
            state["registers"] if delta_cap is not None else state.registers
        )
        return S.ApproxCountDistinctState(regs[member_cols[member_idx]])

    if delta_cap is not None:
        col_index = {c: i for i, c in enumerate(columns)}
        # host mirrors of the hash LUT rows: deltas append into these,
        # then ONE row overwrite lands in the device state — the mirror
        # is what lets a delta be an append instead of a re-hash
        mirrors = {
            c: (
                np.zeros(delta_cap, dtype=np.uint32),
                np.zeros(delta_cap, dtype=np.uint32),
            )
            for c in columns
        }

        def merge(a, b):
            # registers are the real monoid; the LUT leaves follow the
            # same dictionary progression in every shard, so a
            # commutative maximum is an identity-preserving merge
            return {
                "registers": jnp.maximum(a["registers"], b["registers"]),
                "h1": jnp.maximum(a["h1"], b["h1"]),
                "h2": jnp.maximum(a["h2"], b["h2"]),
            }

        def host_delta(state, deltas):
            from deequ_tpu.analyzers.base import DELTA_PRIME

            if deltas is DELTA_PRIME:
                items = [(c, 0, dataset.dictionary(c)) for c in columns]
            else:
                items = [
                    (c, d["start"], d["values"])
                    for c, d in deltas.items()
                    if c in col_index
                ]
            if not items:
                return state
            h1s, h2s = state["h1"], state["h2"]
            for c, start, values in items:
                n = len(values)
                if start + n > delta_cap:
                    raise _delta_overflow(c, start + n, delta_cap)
                m1, m2 = mirrors[c]
                if start == 0:  # full (re)ship: reset the mirror
                    m1[:] = 0
                    m2[:] = 0
                p1, p2 = hll.dictionary_hash_pairs(
                    # lint-ok: sync-discipline: delta VALUES are host
                    # numpy strings off the parquet reader, not device
                    np.asarray(values, dtype=object)
                )
                m1[start:start + n] = p1
                m2[start:start + n] = p2
                h1s = _set_lut_row(h1s, col_index[c], m1)
                h2s = _set_lut_row(h2s, col_index[c], m2)
            return {"registers": state["registers"], "h1": h1s, "h2": h2s}

    else:
        merge = S.ApproxCountDistinctState.merge

    token = _group_token(
        "hll",
        dataset,
        columns,
        where,
        extra=(value_repr, delta_cap),
    )
    return ScanUnit(
        members,
        ScanOps(
            init,
            update,
            merge,
            consts=consts,
            cache_token=token,
            host_delta=host_delta,
        ),
        requests,
        extract,
    )


# --------------------------------------------------------------------------
# kll family (host-folded quantile sketches)
# --------------------------------------------------------------------------


def _kll_sorted_stack(batch, columns, where_fn, where=None):
    """THE one construction of the KLL group's masked f32 sort,
    memoized into the shared batch dict (keyed by columns + where), so
    KLL groups that differ only in their sketch parameters run one
    sort per step instead of trusting XLA's CSE to merge identical
    subgraphs. Returns (sorted_x (C, B), masks, x)."""
    key = (
        _SHARED_PREFIX
        + "kllsort:"
        + repr(where)
        + ":"
        + "\x1f".join(columns)
    )
    hit = batch.get(key)
    if hit is not None:
        return hit
    masks = _shared_stack(batch, columns, "mask")
    masks = masks & _shared_rows(batch, where_fn, where)[None, :]
    x = jnp.stack(
        [batch[f"{c}::values"].astype(jnp.float32) for c in columns]
    )
    masks = masks & jnp.isfinite(x)
    sorted_x = jnp.sort(jnp.where(masks, x, jnp.inf), axis=1)
    out = (sorted_x, masks, x)
    batch[key] = out
    return out


def _build_kll_group(
    dataset: Dataset, members: List[Any], where: Optional[str]
) -> ScanUnit:
    """KLLSketch/ApproxQuantile/ApproxQuantiles sharing (params, where):
    ONE batched (C, B) sort + strided sampling per scan step instead of
    C independent sorts; the host folds each column's samples into its
    compactor hierarchy. Analyzers over the SAME column share one
    sketch (kll_profiling runs KLLSketch + ApproxQuantiles per column —
    the sort and the sketch are computed once)."""
    from deequ_tpu.sketches.hll import fmix32
    from deequ_tpu.sketches.kll import KLLSketchState

    params = members[0].params
    columns, member_cols = _index_members(members)
    where_fn, where_reqs = _compile_where(where, dataset)
    requests = [
        r
        for c in columns
        for r in (ColumnRequest(c, "values"), ColumnRequest(c, "mask"))
    ] + where_reqs
    C = len(columns)
    k = params.sketch_size

    def init():
        # per-batch output slot (overwritten each batch, not a carry)
        return (
            np.zeros((C, k), dtype=np.float32),  # samples
            np.zeros((C, k), dtype=bool),  # sample validity
            np.zeros(C, dtype=np.int64),  # valid counts
            np.full(C, np.inf, dtype=np.float32),  # min
            np.full(C, -np.inf, dtype=np.float32),  # max
            np.zeros(C, dtype=np.int32),  # compaction level
        )

    def update(_state, batch):
        # mirrors analyzers/kll._make_kll_ops exactly, vectorized over
        # the column axis; the device kernel stays in f32/u32 lanes
        sorted_x, masks, x = _kll_sorted_stack(
            batch, columns, where_fn, where
        )
        B = x.shape[1]
        nv = jnp.sum(masks, axis=1, dtype=jnp.int64)
        q = ((nv + k - 1) // k).astype(jnp.uint32)
        level = jnp.where(
            q > 1, 32 - jax.lax.clz(jnp.maximum(q - 1, 1)), 0
        ).astype(jnp.int32)
        stride = jnp.int64(1) << level.astype(jnp.int64)
        bits = jax.lax.bitcast_convert_type(sorted_x[:, 0], jnp.uint32)
        seed = fmix32(nv.astype(jnp.uint32) ^ bits)
        offset = seed.astype(jnp.int64) & (stride - 1)
        idx = offset[:, None] + jnp.arange(k, dtype=jnp.int64)[None, :] * (
            stride[:, None]
        )
        valid = idx < nv[:, None]
        samples = jnp.take_along_axis(
            sorted_x, jnp.clip(idx, 0, B - 1), axis=1
        )
        mn = jnp.min(jnp.where(masks, x, jnp.inf), axis=1)
        mx = jnp.max(jnp.where(masks, x, -jnp.inf), axis=1)
        return (samples, valid, nv, mn, mx, level)

    def host_init():
        return [KLLSketchState(params) for _ in range(C)]

    def host_fold(accs, out):
        samples, valid, nv, mn, mx, level = out
        # one host-side conversion for the whole (C, k) block; valid
        # samples are finite by construction (the device kernel masks
        # non-finite values into the +inf sentinel and invalidates
        # those slots), so the per-column isfinite net is skipped
        samples = np.asarray(samples)  # sync-ok: host fold runs on
        valid = np.asarray(valid)  # sync-ok: already-fetched numpy
        # (the packed epilogue fetched the whole block)
        for i in range(C):
            accs[i].add_pre_compacted(
                samples[i][valid[i]],
                int(level[i]),
                int(nv[i]),
                float(mn[i]),
                float(mx[i]),
                assume_finite=True,
            )
        return accs

    def merge(a, b):  # per-column sketch merge (incremental/mesh path)
        return [KLLSketchState.merge(x, y) for x, y in zip(a, b)]

    def extract(accs, member_idx: int):
        return accs[member_cols[member_idx]]

    token = _group_token(
        "kll",
        dataset,
        columns,
        where,
        extra=(k, params.shrinking_factor),
    )
    return ScanUnit(
        members,
        ScanOps(
            init,
            update,
            merge,
            host_init=host_init,
            host_fold=host_fold,
            cache_token=token,
        ),
        requests,
        extract,
    )


# --------------------------------------------------------------------------
# datatype family (string columns only)
# --------------------------------------------------------------------------


def _build_datatype_group(
    dataset: Dataset, members: List[Any], where: Optional[str]
) -> ScanUnit:
    from deequ_tpu.analyzers.datatype import dictionary_buckets

    columns, member_cols = _index_members(members)
    where_fn, where_reqs = _compile_where(where, dataset)
    requests = [
        r
        for c in columns
        for r in (ColumnRequest(c, "codes"), ColumnRequest(c, "mask"))
    ] + where_reqs
    C = len(columns)

    consts = None
    host_delta = None
    # one-pass dictionary deltas: bucket LUT in STATE, classified
    # incrementally from each delta's values (see _build_hll_group)
    delta_cap = _delta_cap_of(dataset, columns)
    if delta_cap is None:
        luts = [dictionary_buckets(dataset.dictionary(c)) for c in columns]
        consts = {"lut": _stack_luts(luts, S.DataTypeHistogram.STRING)}

    def init():
        state = {"counts": np.zeros((C, 6), dtype=np.int64)}
        if delta_cap is not None:
            # padding classifies as STRING like the consts path; rows
            # beyond the shipped dictionary are never indexed by a
            # valid code, so the fill never reaches a count
            state["lut"] = np.full(
                (C, delta_cap),
                S.DataTypeHistogram.STRING,
                dtype=np.int32,
            )
        return state

    def update(state, batch, consts_in=None):
        from deequ_tpu.sketches.hll import PRESENCE_DICT_CAP

        table = (
            state["lut"] if delta_cap is not None else consts_in["lut"]
        )
        rows = _shared_rows(batch, where_fn, where)
        masks = _shared_stack(batch, columns, "mask")
        valid = masks & rows[None, :]
        codes = _shared_stack(batch, columns, "codes").astype(jnp.int32)
        if table.shape[1] <= PRESENCE_DICT_CAP:
            # shared single-source implementation — see
            # analyzers/datatype.py counts_from_code_presence
            from deequ_tpu.analyzers.datatype import (
                counts_from_code_presence,
            )

            counts = counts_from_code_presence(codes, valid, rows, table)
        else:
            codes = jnp.clip(codes, 0, table.shape[1] - 1)
            bucket = jnp.take_along_axis(table, codes, axis=1)
            bucket = jnp.where(valid, bucket, S.DataTypeHistogram.NULL)
            bucket = jnp.where(rows[None, :], bucket, 6)  # padding slot
            col_ids = jax.lax.broadcasted_iota(
                jnp.int32, bucket.shape, 0
            )
            flat = (col_ids * 8 + bucket).ravel()
            counts = (
                jnp.zeros(C * 8, dtype=jnp.int32)
                .at[flat]
                .add(1)
                .reshape(C, 8)[:, :6]
            )
        new_counts = state["counts"] + counts.astype(jnp.int64)
        if delta_cap is not None:
            return {"counts": new_counts, "lut": state["lut"]}
        return {"counts": new_counts}

    def merge(a, b):
        out = {"counts": a["counts"] + b["counts"]}
        if delta_cap is not None:
            # every shard follows the same dictionary progression, so
            # maximum preserves the (identical) LUTs
            out["lut"] = jnp.maximum(a["lut"], b["lut"])
        return out

    def extract(state, member_idx: int):
        return S.DataTypeHistogram(
            state["counts"][member_cols[member_idx]]
        )

    if delta_cap is not None:
        col_index = {c: i for i, c in enumerate(columns)}
        mirrors = {
            c: np.full(
                delta_cap, S.DataTypeHistogram.STRING, dtype=np.int32
            )
            for c in columns
        }

        def host_delta(state, deltas):
            from deequ_tpu.analyzers.base import DELTA_PRIME

            if deltas is DELTA_PRIME:
                items = [(c, 0, dataset.dictionary(c)) for c in columns]
            else:
                items = [
                    (c, d["start"], d["values"])
                    for c, d in deltas.items()
                    if c in col_index
                ]
            if not items:
                return state
            lut = state["lut"]
            for c, start, values in items:
                n = len(values)
                if start + n > delta_cap:
                    raise _delta_overflow(c, start + n, delta_cap)
                row = mirrors[c]
                if start == 0:  # full (re)ship: reset the mirror
                    row[:] = S.DataTypeHistogram.STRING
                if n:
                    row[start:start + n] = dictionary_buckets(values)[:n]
                lut = _set_lut_row(lut, col_index[c], row)
            return {"counts": state["counts"], "lut": lut}

    token = _group_token(
        "datatype", dataset, columns, where, extra=(delta_cap,)
    )
    return ScanUnit(
        members,
        ScanOps(
            init,
            update,
            merge,
            consts=consts,
            cache_token=token,
            host_delta=host_delta,
        ),
        requests,
        extract,
    )


# --------------------------------------------------------------------------
# planner
# --------------------------------------------------------------------------


def plan_scan_units(
    dataset: Dataset, analyzers: Sequence[Any]
) -> Tuple[List[ScanUnit], Dict[Any, BaseException]]:
    """Partition analyzers into vectorized groups + singles.

    Returns (units, plan_failures). Grouping keys include the device
    dtype of the stacked repr and the ``where`` expression; anything
    unrecognized, host-folded, or oddly-typed falls back to its own
    ``make_ops`` — behavior is identical either way.
    """
    from deequ_tpu.analyzers.basic import (
        Completeness,
        Maximum,
        MaxLength,
        Mean,
        Minimum,
        MinLength,
        StandardDeviation,
        Sum,
    )
    from deequ_tpu.analyzers.datatype import DataType
    from deequ_tpu.analyzers.hll import ApproxCountDistinct, hll_value_repr
    from deequ_tpu.analyzers.kll import (
        ApproxQuantile,
        ApproxQuantiles,
        KLLSketch,
    )

    groups: Dict[tuple, List[Any]] = {}
    singles: List[Any] = []
    failures: Dict[Any, BaseException] = {}

    def group_key(a) -> Optional[tuple]:
        t = type(a)
        try:
            if t in (Mean, Sum, Minimum, Maximum, StandardDeviation):
                dt = dataset.request_dtype(
                    ColumnRequest(a.column, "values")
                )
                return ("stats", "values", str(dt), a.where)
            if t in (MinLength, MaxLength):
                return ("stats", "lengths", "int32", a.where)
            if t is Completeness:
                return ("completeness", a.where)
            if t is ApproxCountDistinct:
                value_repr = hll_value_repr(dataset, a.column)
                dt = dataset.request_dtype(
                    ColumnRequest(a.column, value_repr)
                )
                return ("hll", value_repr, str(dt), a.where)
            if (
                t is DataType
                and dataset.schema.kind_of(a.column) == Kind.STRING
                and not dataset.string_lane(a.column)
            ):
                dt = dataset.request_dtype(ColumnRequest(a.column, "codes"))
                return ("datatype", str(dt), a.where)
            if t in (KLLSketch, ApproxQuantile, ApproxQuantiles):
                # values cast to f32 inside the kernel, so mixed input
                # dtypes stack fine; sketches keyed by params + where
                return (
                    "kll",
                    a.params.sketch_size,
                    a.params.shrinking_factor,
                    a.where,
                )
        except Exception:  # noqa: BLE001 — fall back to the single path
            return None
        return None

    for a in analyzers:
        key = group_key(a)
        if key is None:
            singles.append(a)
        else:
            groups.setdefault(key, []).append(a)

    units: List[ScanUnit] = []
    for key, members in groups.items():
        if len(members) == 1:
            singles.extend(members)
            continue
        try:
            if key[0] == "stats":
                units.append(
                    _build_stats_group(dataset, members, key[1], key[3])
                )
            elif key[0] == "completeness":
                units.append(
                    _build_completeness_group(dataset, members, key[1])
                )
            elif key[0] == "hll":
                units.append(
                    _build_hll_group(dataset, members, key[1], key[3])
                )
            elif key[0] == "kll":
                units.append(
                    _build_kll_group(dataset, members, key[3])
                )
            else:
                units.append(
                    _build_datatype_group(dataset, members, key[2])
                )
        except Exception:  # noqa: BLE001 — vectorization is an
            # optimization; degrade to the per-analyzer path
            singles.extend(members)

    from deequ_tpu.analyzers.base import CACHE_TOKEN_AUTO, make_cache_token

    for a in singles:
        try:
            ops = a.make_ops(dataset)
            if ops.cache_token is CACHE_TOKEN_AUTO:
                ops.cache_token = make_cache_token(
                    a,
                    dataset,
                    predicates=(
                        getattr(a, "where", None),
                        getattr(a, "predicate", None),
                    ),
                )
            units.append(
                ScanUnit([a], ops, a.device_requests(dataset), None)
            )
        except Exception as exc:  # noqa: BLE001
            failures[a] = exc
    if units:
        from deequ_tpu.telemetry import get_telemetry

        tm = get_telemetry()
        tm.counter("engine.vectorize.units").inc(len(units))
        tm.counter("engine.vectorize.stacked_members").inc(
            sum(len(u.members) for u in units if len(u.members) > 1)
        )
    return units, failures
