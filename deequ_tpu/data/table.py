"""Columnar dataset: Arrow ingest and device-batch materialization.

This is deequ_tpu's L0/L1 replacement for Spark DataFrames (SURVEY.md §1,
§7 stage 0). A :class:`Dataset` wraps a ``pyarrow.Table`` and materializes
*device representations* of columns on demand:

- ``values``   — numeric payload (nulls zero-filled; see mask); int64
                 columns narrow to i32 when every value fits (wire
                 bytes are the bottleneck)
- ``mask``     — validity bitmap as bool (True = non-null), AND row mask
- ``codes``    — dictionary codes for string/categorical columns —
                 i8/i16/i32 depending on dictionary size (widen before
                 any joint-code arithmetic!) — with the dictionary kept
                 host-side (strings never reach the TPU — SURVEY.md §7
                 hard part #3)
- ``lengths``  — utf8 lengths for string columns (MinLength/MaxLength)

Batches are fixed-size and zero-padded (padding rows carry
``__row_mask__ == False``) so that every batch has the same static shape
and the fused analyzer scan compiles exactly once.
"""

from __future__ import annotations

import enum
import functools
import hashlib
import weakref
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

ROW_MASK = "__row_mask__"

# Host-only dictionary-delta payloads riding a streamed batch dict
# (data/parquet.py produces them, the engine's streaming loop pops them
# before transfer and applies them to LUT-carrying op states): key
# ``DICT_DELTA_PREFIX + column`` -> {"start": int, "values": ndarray}.
# Never part of the wire layout, never device_put.
DICT_DELTA_PREFIX = "__dict_delta__:"

# -- host->device transfer accounting (monotonic; bench snapshots it) ----
# The tally lives on the telemetry registry now (counter
# "transfer.bytes" — always on, docs/OBSERVABILITY.md); these module
# functions remain as the stable accessors. Looked up per call, not
# cached: registry.reset() in tests would detach a cached instrument,
# and the lookup is per-BATCH, not per-row.
def _transfer_counter():
    from deequ_tpu.telemetry import get_telemetry

    return get_telemetry().counter("transfer.bytes")


def add_transfer_bytes(n: int) -> None:
    _transfer_counter().inc(int(n))


def transfer_bytes() -> int:
    """Total bytes shipped host->device by the data layer so far.
    Monotonic; callers snapshot around a run to decompose wall time into
    transfer vs compute."""
    return _transfer_counter().value


@functools.lru_cache(maxsize=None)
def _chunk_row_mask_fn(chunk_nb: int, batch_size: int):
    """Jitted builder of a (chunk_nb, batch_size) bool mask of in-bounds
    rows for the chunk starting at global row ``start`` — built ON
    device (iota fused into the comparison; no wire transfer). ``start``
    and ``n`` are runtime scalars so one compile serves every chunk."""
    import jax
    import jax.numpy as jnp

    def build(start, n):
        idx = jax.lax.broadcasted_iota(jnp.int64, (chunk_nb, batch_size), 0)
        off = jax.lax.broadcasted_iota(jnp.int64, (chunk_nb, batch_size), 1)
        return start + idx * batch_size + off < n

    # lint-ok: wire-discipline: resident-path device helper — the row
    # mask is BUILT on device (no wire transfer), not placed from host
    return jax.jit(build)


def _unpack_mask_bits(packed, batch_size: int):
    """Device: (chunk_nb, ceil(B/8)) uint8 little-endian packed bits ->
    (chunk_nb, B) bool. Validity masks cross the wire at 1 BIT/row
    (np.packbits host-side); this is the device-side expansion, fused by
    XLA into the consuming reductions' pass."""
    import jax.numpy as jnp

    bits = (packed[:, :, None] >> jnp.arange(8, dtype=jnp.uint8)) & jnp.uint8(1)
    return bits.reshape(packed.shape[0], -1)[:, :batch_size].astype(bool)


@functools.lru_cache(maxsize=None)
def _mask_unpack_fn(batch_size: int):
    import jax

    # lint-ok: wire-discipline: the device-side half of the 1-bit/row
    # mask wire itself; the engine composes it into the fused unpack
    return jax.jit(
        functools.partial(_unpack_mask_bits, batch_size=batch_size)
    )


@functools.lru_cache(maxsize=None)
def _lengths_gather_fn():
    """Device: utf8 lengths derived from dictionary codes via LUT gather
    — string columns whose codes already ship (DataType/Histogram/HLL)
    get MinLength/MaxLength inputs for FREE instead of 4 more bytes/row
    over the wire. ``lut[0]`` is the null slot (length 0); codes are -1
    for null, so gather at code+1."""
    import jax
    import jax.numpy as jnp

    def gather(codes, lut):
        idx = codes.astype(jnp.int32) + 1
        return jnp.take(lut, jnp.clip(idx, 0, lut.shape[0] - 1), axis=0)

    # lint-ok: wire-discipline: wire-FREE lengths — the LUT gather
    # replaces a 4-bytes/row transfer, it does not add one
    return jax.jit(gather)


def narrow_codes(codes: np.ndarray, dict_size: int) -> np.ndarray:
    """Wire narrowing for dictionary codes: small dictionaries ship i8
    or i16 instead of i32 (4x/2x fewer bytes over the bottleneck
    host->device link). Bounds leave headroom for the +1 null-slot
    shift in the grouping joint-code math; -1 (null) fits every width."""
    if dict_size < 127:
        return codes.astype(np.int8)
    if dict_size < 32767:
        return codes.astype(np.int16)
    return codes


def dictionary_to_numpy(dictionary: pa.Array) -> np.ndarray:
    """Dictionary values as numpy: object arrays for strings, NATIVE
    dtype otherwise — a to_pylist object array costs seconds at 10M
    distinct values. One definition for the in-memory and parquet paths."""
    if pa.types.is_string(dictionary.type) or pa.types.is_large_string(
        dictionary.type
    ):
        return np.asarray(dictionary.to_pylist(), dtype=object)
    return dictionary.to_numpy(zero_copy_only=False)


def dictionary_utf8_lengths(dictionary: pa.Array) -> np.ndarray:
    """utf8 lengths of dictionary entries (null -> 0), i32 — computed by
    Arrow's C++ kernel once per DISTINCT value, not per row."""
    lengths = pc.fill_null(
        pc.utf8_length(dictionary), pa.scalar(0, pa.int32())
    )
    if isinstance(lengths, pa.ChunkedArray):
        lengths = lengths.combine_chunks()
    return np.ascontiguousarray(
        lengths.to_numpy(zero_copy_only=False).astype(np.int32)
    )


def convert_basic_repr(col, kind: "Kind", repr_name: str) -> np.ndarray:
    """The ONE host->device conversion rule set for mask/values/lengths
    (codes need a dictionary and stay with their owner). Shared by the
    in-memory and parquet paths so fill/widening semantics cannot drift."""
    if repr_name == "mask":
        if col.null_count == 0:
            out = np.ones(len(col), dtype=bool)
        else:
            is_null = col.is_null()
            if isinstance(is_null, pa.ChunkedArray):
                is_null = is_null.combine_chunks()
            out = ~is_null.to_numpy(zero_copy_only=False)
        return np.ascontiguousarray(out.astype(bool))
    if repr_name == "values":
        if kind == Kind.STRING:
            raise TypeError(
                "string columns have no 'values' repr; request 'codes' "
                "or 'lengths' instead"
            )
        filled = col
        if kind == Kind.TIMESTAMP:
            if pa.types.is_date32(col.type):
                # Arrow has no chunked date32->int64 kernel; hop
                # through int32 (days since epoch, exact)
                filled = pc.cast(pc.cast(col, pa.int32()), pa.int64())
            else:
                filled = pc.cast(col, pa.int64())
            if col.null_count:
                filled = pc.fill_null(filled, pa.scalar(0, pa.int64()))
        elif col.null_count:
            zero = (
                pa.scalar(False)
                if kind == Kind.BOOLEAN
                else pa.scalar(0, type=col.type)
            )
            filled = pc.fill_null(col, zero)
        if isinstance(filled, pa.ChunkedArray):
            filled = filled.combine_chunks()
        out = filled.to_numpy(zero_copy_only=False)
        if kind == Kind.BOOLEAN:
            out = out.astype(np.int32)
        elif out.dtype == np.float16:
            out = out.astype(np.float32)
        elif out.dtype.kind not in "iuf":
            out = out.astype(np.float64)
        return np.ascontiguousarray(out)
    if repr_name == "lengths":
        lengths = pc.fill_null(pc.utf8_length(col), pa.scalar(0, pa.int32()))
        if isinstance(lengths, pa.ChunkedArray):
            lengths = lengths.combine_chunks()
        return np.ascontiguousarray(
            lengths.to_numpy(zero_copy_only=False).astype(np.int32)
        )
    if repr_name == "u64bits":
        return f64_canonical_u64_bits(convert_basic_repr(col, kind, "values"))
    raise ValueError(f"unknown column repr: {repr_name!r}")


def f64_canonical_u64_bits(values: np.ndarray) -> np.ndarray:
    """HOST twin of the f64 spill-key canonicalization in
    analyzers/spill.py's ``_chunk_key_fn``, for backends whose X64
    rewriter cannot lower the f64->u64 bitcast on device (TPU):
    canonical NaN bits, -0.0 remapped to 0 — bit-identical to the CPU
    device path's keys. Backs the "u64bits" column repr, so the packed
    bits ride the normal column pipeline (one pass over the source)
    instead of forcing a separate host re-read per spill plan."""
    bits = (
        np.ascontiguousarray(values, dtype=np.float64)
        .view(np.uint64)
        .copy()
    )
    x = np.asarray(values, dtype=np.float64)
    bits[np.isnan(x)] = np.uint64(0x7FF8000000000000)
    bits[bits == np.uint64(0x8000000000000000)] = np.uint64(0)
    return bits


def narrow_int64_values(out: np.ndarray) -> np.ndarray:
    """Wire narrowing: host->device bandwidth is the bottleneck; when
    every value of an int64 column fits i32, ship half the bytes. Safe:
    every consumer canonicalizes integrals (HLL hashes via int64,
    sums/min/max widen to f64), so i32 and i64 storage of equal values
    produce identical metrics and merge compatibly across datasets.
    MUST be decided once per column (callers), never per batch — mixed
    batch dtypes would force a recompile per dtype combination."""
    if out.dtype == np.int64 and len(out):
        lo, hi = out.min(), out.max()
        if lo >= -(2**31) and hi < 2**31:
            return out.astype(np.int32)
    return out


class Kind(enum.Enum):
    """Logical column kinds (maps Arrow types to analyzer preconditions)."""

    INTEGRAL = "Integral"
    FRACTIONAL = "Fractional"
    BOOLEAN = "Boolean"
    STRING = "String"
    TIMESTAMP = "Timestamp"
    UNKNOWN = "Unknown"

    @property
    def is_numeric(self) -> bool:
        return self in (Kind.INTEGRAL, Kind.FRACTIONAL, Kind.BOOLEAN)


def normalize_float_grouping_keys(arr):
    """Spark grouping-key normalization for float columns, shared by
    the dictionary/codes path (Dataset._materialize_codes) and the
    Arrow group_by fallback (analyzers.grouping._normalize_float_keys):

    - pre-encoded float dictionaries are flattened first (the
      dictionary itself may hold -0.0 AND 0.0, or several NaN
      payloads, as distinct entries);
    - every NaN payload maps to the one canonical NaN — Arrow's
      group_by/dictionary_encode treat DIFFERENT NaN bit patterns as
      distinct keys (verified empirically), while Spark and the device
      spill kernel (spill._chunk_key_fn) group all NaNs together;
    - -0.0 maps to 0.0 via +0.0 (identity for every other value).

    Non-float arrays pass through untouched. tests/goldens neg_zero /
    nan fixtures pin the behavior."""
    if pa.types.is_dictionary(arr.type) and pa.types.is_floating(
        arr.type.value_type
    ):
        arr = pc.cast(arr, arr.type.value_type)
    if not pa.types.is_floating(arr.type):
        return arr
    return pc.if_else(
        pc.is_nan(arr),
        pa.scalar(float("nan"), arr.type),
        pc.add(arr, pa.scalar(0.0, arr.type)),
    )


def _kind_of(arrow_type: pa.DataType) -> Kind:
    if pa.types.is_boolean(arrow_type):
        return Kind.BOOLEAN
    if pa.types.is_integer(arrow_type):
        return Kind.INTEGRAL
    if pa.types.is_floating(arrow_type) or pa.types.is_decimal(arrow_type):
        return Kind.FRACTIONAL
    if pa.types.is_string(arrow_type) or pa.types.is_large_string(arrow_type):
        return Kind.STRING
    if pa.types.is_dictionary(arrow_type):
        return _kind_of(arrow_type.value_type)
    if pa.types.is_timestamp(arrow_type) or pa.types.is_date(arrow_type):
        return Kind.TIMESTAMP
    return Kind.UNKNOWN


@dataclass(frozen=True)
class Field:
    name: str
    kind: Kind


@dataclass(frozen=True)
class Schema:
    fields: Tuple[Field, ...]

    @property
    def column_names(self) -> List[str]:
        return [f.name for f in self.fields]

    def has_column(self, name: str) -> bool:
        return any(f.name == name for f in self.fields)

    def kind_of(self, name: str) -> Kind:
        for f in self.fields:
            if f.name == name:
                return f.kind
        raise KeyError(name)

    def __len__(self) -> int:
        return len(self.fields)


@dataclass(frozen=True)
class ColumnRequest:
    """A device representation request: (column, repr)."""

    column: str
    # "values" | "mask" | "codes" | "lengths" | "u64bits" (host-packed
    # canonical f64 key bits for the one-pass spill collector)
    repr: str

    @property
    def key(self) -> str:
        return f"{self.column}::{self.repr}"


class Dataset:
    """In-memory columnar dataset over a ``pyarrow.Table``.

    Construction helpers accept Arrow tables, pandas DataFrames, or plain
    dicts of Python/numpy sequences. All device materializations are cached
    per (column, repr) as contiguous numpy arrays; batches are views plus a
    single zero-pad for the tail.
    """

    def __init__(self, table: pa.Table):
        self._table = table.combine_chunks()
        self._schema = Schema(
            tuple(
                Field(name, _kind_of(typ))
                for name, typ in zip(table.schema.names, table.schema.types)
            )
        )
        self._materialized: Dict[str, np.ndarray] = {}
        self._dictionaries: Dict[str, np.ndarray] = {}
        self._dict_lengths: Dict[str, np.ndarray] = {}
        # device-resident stacked batches, keyed (repr key, batch, sharding)
        self._device_cache: Dict = {}
        self._cache_key = id(self)
        weakref.finalize(self, Dataset._drop_cache_key, self._cache_key)

    # device-cache accounting is GLOBAL across Datasets (one chip, one
    # HBM): LRU registry of datasets holding device-resident columns
    _cache_registry: "OrderedDict[int, weakref.ref]" = OrderedDict()
    _cache_bytes_by_key: Dict[int, int] = {}

    @staticmethod
    def _drop_cache_key(key: int) -> None:
        Dataset._cache_registry.pop(key, None)
        Dataset._cache_bytes_by_key.pop(key, None)

    @staticmethod
    def global_device_cache_bytes() -> int:
        return sum(Dataset._cache_bytes_by_key.values())

    @property
    def _device_cache_bytes(self) -> int:
        return Dataset._cache_bytes_by_key.get(self._cache_key, 0)

    def _add_cache_bytes(self, nbytes: int) -> None:
        Dataset._cache_bytes_by_key[self._cache_key] = (
            self._device_cache_bytes + nbytes
        )

    def _touch_cache_registry(self) -> None:
        Dataset._cache_registry.pop(self._cache_key, None)
        Dataset._cache_registry[self._cache_key] = weakref.ref(self)

    # -- construction ---------------------------------------------------

    @staticmethod
    def from_arrow(table: pa.Table) -> "Dataset":
        return Dataset(table)

    @staticmethod
    def from_pandas(df) -> "Dataset":
        return Dataset(pa.Table.from_pandas(df, preserve_index=False))

    @staticmethod
    def from_pydict(data: Dict[str, Sequence]) -> "Dataset":
        return Dataset(pa.table(data))

    @staticmethod
    def from_parquet(source, read_batch_rows: int = 1 << 20) -> "Dataset":
        """Streaming parquet-backed dataset: batches are read and
        converted on the fly; whole columns are never materialized on
        the host unless the resident device cache opts in (see
        deequ_tpu.data.parquet)."""
        from deequ_tpu.data.parquet import ParquetDataset

        return ParquetDataset(source, read_batch_rows)

    # -- metadata -------------------------------------------------------

    @property
    def table(self) -> pa.Table:
        return self._table

    @property
    def num_rows(self) -> int:
        return self._table.num_rows

    @property
    def num_columns(self) -> int:
        return self._table.num_columns

    @property
    def schema(self) -> Schema:
        return self._schema

    def filter_rows(self, mask: np.ndarray) -> "Dataset":
        """Row subset (host-side); used by train/test splits and schema
        validation, not by the metric engine."""
        return Dataset(self._table.filter(pa.array(mask)))

    def select(self, columns: Sequence[str]) -> "Dataset":
        return Dataset(self._table.select(list(columns)))

    def record_batches(
        self, columns: Sequence[str], batch_rows: int = 1 << 20
    ) -> "Iterator[pa.RecordBatch]":
        """Column-pruned record batches (streamed from storage by
        parquet-backed datasets; zero-copy slices here)."""
        return iter(
            self._table.select(list(columns)).to_batches(batch_rows)
        )

    # -- dictionaries ---------------------------------------------------

    def dictionary(self, column: str) -> np.ndarray:
        """Host-side dictionary (unique values) for a column; codes index
        into this. Built once per column via Arrow's C++ kernels."""
        if column not in self._dictionaries:
            self._materialize_codes(column)
        return self._dictionaries[column]

    def _materialize_codes(self, column: str) -> None:
        arr = normalize_float_grouping_keys(self._table.column(column))
        if pa.types.is_dictionary(arr.type):
            dict_arr = arr.combine_chunks()
        else:
            dict_arr = pc.dictionary_encode(arr).combine_chunks()
        if isinstance(dict_arr, pa.ChunkedArray):
            dict_arr = dict_arr.combine_chunks()
        indices = dict_arr.indices
        codes = (
            pc.fill_null(indices, pa.scalar(-1, indices.type))
            .to_numpy(zero_copy_only=False)
            .astype(np.int32)
        )
        codes = narrow_codes(codes, len(dict_arr.dictionary))
        self._materialized[f"{column}::codes"] = np.ascontiguousarray(codes)
        self._dictionaries[column] = dictionary_to_numpy(dict_arr.dictionary)
        if self._schema.kind_of(column) == Kind.STRING:
            self._dict_lengths[column] = dictionary_utf8_lengths(
                dict_arr.dictionary
            )

    def dict_lengths(self, column: str) -> Optional[np.ndarray]:
        """Per-dictionary-entry utf8 lengths (i32) for a string column,
        or None when codes haven't been materialized. Used to derive
        the 'lengths' device repr from codes on device (see
        _lengths_gather_fn) instead of shipping 4 bytes/row."""
        if column not in self._dict_lengths and column in self._dictionaries:
            self._dict_lengths[column] = dictionary_utf8_lengths(
                pa.array(list(self._dictionaries[column]), pa.string())
            )
        return self._dict_lengths.get(column)

    # -- device materialization ----------------------------------------

    def materialize(self, req: ColumnRequest) -> np.ndarray:
        key = req.key
        if key in self._materialized:
            return self._materialized[key]
        if req.repr == "codes":
            self._materialize_codes(req.column)
            return self._materialized[key]
        col = self._table.column(req.column)
        kind = self._schema.kind_of(req.column)
        out = convert_basic_repr(col, kind, req.repr)
        if req.repr == "values" and kind == Kind.INTEGRAL:
            out = narrow_int64_values(out)  # whole column: one decision
        self._materialized[key] = out
        return out

    def request_dtype(self, req: ColumnRequest) -> np.dtype:
        """Dtype a device batch of this request will have (used by the
        vectorizing planner to group stackable columns). In-memory
        datasets answer from the (cached) materialization; streaming
        sources override with their pre-decided per-column dtypes."""
        if req.repr == "mask":
            return np.dtype(bool)
        if req.repr == "u64bits":
            return np.dtype(np.uint64)
        return np.dtype(self.materialize(req).dtype)

    # -- batching -------------------------------------------------------

    def device_batches(
        self,
        requests: Sequence[ColumnRequest],
        batch_size: Optional[int] = None,
        start_batch: int = 0,
    ) -> Iterator[Dict[str, np.ndarray]]:
        """Yield fixed-size batches (host numpy; the engine device_puts).

        Every batch has identical shapes: the tail batch is zero-padded
        and padding rows have ``__row_mask__ == False``; per-column masks
        are pre-ANDed with the row mask so updates need a single mask.

        ``start_batch`` skips the first N batches — the engine's
        resilience layer restarts the stream from a failing batch
        (retry) or a checkpoint cursor (resume); batch boundaries are
        identical for every start, so batch ``i`` of a restarted stream
        is bit-identical to batch ``i`` of a full one.
        """
        n = self.num_rows
        if batch_size is None:
            batch_size = n if n > 0 else 1
        batch_size = max(1, batch_size)
        keys = self._dedup_requests(requests)
        full: Dict[str, np.ndarray] = {
            k: self.materialize(r) for k, r in keys.items()
        }
        if n == 0:
            if start_batch > 0:
                return
            batch = {
                k: np.zeros((batch_size,), dtype=v.dtype)
                for k, v in full.items()
            }
            batch[ROW_MASK] = np.zeros((batch_size,), dtype=bool)
            yield batch
            return
        for start in range(start_batch * batch_size, n, batch_size):
            stop = min(start + batch_size, n)
            width = stop - start
            pad = batch_size - width
            batch = {}
            for k, v in full.items():
                sl = v[start:stop]
                if pad:
                    sl = np.concatenate(
                        [sl, np.zeros((pad,), dtype=v.dtype)]
                    )
                batch[k] = sl
            row_mask = np.ones((batch_size,), dtype=bool)
            if pad:
                row_mask[width:] = False
            batch[ROW_MASK] = row_mask
            if pad:
                for k in list(batch.keys()):
                    if k.endswith("::mask"):
                        batch[k] = batch[k] & row_mask
            yield batch

    # -- device-resident batching (the TPU fast path) -------------------

    def _is_all_valid(self, column: str) -> bool:
        return self._table.column(column).null_count == 0

    @staticmethod
    def _dedup_requests(
        requests: Sequence[ColumnRequest],
    ) -> Dict[str, ColumnRequest]:
        """Dedup requests and add a validity-mask request per column —
        the one canonical definition the byte estimate, the resident
        path, and the streaming path all share."""
        keys: Dict[str, ColumnRequest] = {}
        for r in requests:
            keys.setdefault(r.key, r)
            mask_req = ColumnRequest(r.column, "mask")
            keys.setdefault(mask_req.key, mask_req)
        return keys

    def _synthesize_mask(self, req: ColumnRequest) -> bool:
        if req.repr != "mask" or not self._is_all_valid(req.column):
            return False
        from deequ_tpu import config

        return config.options().synthesize_all_true_masks

    def _column_arrow_type(self, column: str) -> pa.DataType:
        """Storage-type hook (parquet sources answer from file schema)."""
        return self._table.column(column).type

    def _request_row_bytes(self, r: ColumnRequest) -> int:
        """Device bytes per row for one request (0 for synthesized);
        mirrors what materialize() actually produces, not the Arrow
        storage width (timestamps/dates widen to int64, f16 to f32;
        codes/int64 values may be wire-narrowed). Unmaterialized
        estimates are conservative upper bounds."""
        if r.repr == "mask":
            return 0 if self._synthesize_mask(r) else 1
        cached = self._materialized.get(r.key)
        if cached is not None:
            return cached.dtype.itemsize  # the true narrowed width
        if r.repr in ("codes", "lengths"):
            return 4
        if r.repr == "u64bits":
            return 8
        kind = self._schema.kind_of(r.column)
        if kind in (Kind.BOOLEAN, Kind.STRING):
            return 4
        if kind == Kind.TIMESTAMP:
            return 8
        try:
            width = max(1, self._column_arrow_type(r.column).bit_width // 8)
        except (ValueError, AttributeError):
            return 8
        return max(width, 4)  # f16 materializes as f32

    def dictionary_size_within(
        self, column: str, cap: int
    ) -> Optional[int]:
        """Distinct-value count if it is <= cap, else None WITHOUT
        necessarily building the full dictionary (parquet sources bail
        out of the streaming pre-pass once the cap is passed, so a
        spilling plan never materializes an unbounded value set)."""
        d = self.dictionary(column)
        return len(d) if len(d) <= cap else None

    def integral_range(
        self, column: str
    ) -> Optional[Tuple[int, int]]:
        """(min, max) of an INTEGRAL column in one vectorized Arrow
        pass — O(1) host memory, NO distinct set. Lets planners detect
        a bounded value domain (TPC-DS quantity-style columns) without
        the unbounded host dictionary the spill gate exists to avoid.
        None for non-integral columns or all-null data. Cached: the
        grouping planner asks once per (column, run)."""
        if self._schema.kind_of(column) != Kind.INTEGRAL:
            return None
        if not hasattr(self, "_integral_ranges"):
            self._integral_ranges: Dict[
                str, Optional[Tuple[int, int]]
            ] = {}
        if column not in self._integral_ranges:
            arr = self._table.column(column)
            if pa.types.is_dictionary(arr.type):
                self._integral_ranges[column] = None
            else:
                mm = pc.min_max(arr)
                lo, hi = mm["min"].as_py(), mm["max"].as_py()
                self._integral_ranges[column] = (
                    None
                    if lo is None or hi is None
                    else (int(lo), int(hi))
                )
        return self._integral_ranges[column]

    def _derived_length_codes(
        self, keys: Dict[str, ColumnRequest]
    ) -> List[ColumnRequest]:
        """Codes requests the derived-lengths path would ADD to the
        cache beyond the request set itself (a 'lengths' request served
        by LUT gather pins the column's codes chunks too) — the budget
        accounting must see them or eviction under-frees."""
        extra = []
        for r in keys.values():
            if r.repr != "lengths":
                continue
            try:
                if self._schema.kind_of(r.column) != Kind.STRING:
                    continue
            except KeyError:
                continue
            codes_key = f"{r.column}::codes"
            if codes_key in keys:
                continue
            if (
                codes_key in self._materialized
                or r.column in self._dictionaries
            ):
                extra.append(ColumnRequest(r.column, "codes"))
        return extra

    def estimated_device_bytes(
        self,
        requests: Sequence[ColumnRequest],
        batch_size: int,
        chunk_batches: int = 1,
        derive_lengths: bool = True,
    ) -> int:
        """Upper-bound device bytes for the resident scan path (padded
        to whole chunks; all-valid masks cost nothing — they alias the
        synthesized row mask; derived string lengths pin their codes
        chunks too). ``derive_lengths`` mirrors device_scan_chunks'
        ``sharding is None`` gate: under explicit sharding lengths ship
        directly, so the extra codes chunks must NOT be counted or
        meshed scans over-estimate and wrongly reject the resident
        path / over-evict (ADVICE r3)."""
        _, n_chunks = self._chunk_geometry(batch_size, chunk_batches)
        padded = n_chunks * chunk_batches * batch_size
        keys = self._dedup_requests(requests)
        per_row = 1  # synthesized row mask
        for r in keys.values():
            per_row += self._request_row_bytes(r)
        if derive_lengths:
            for r in self._derived_length_codes(keys):
                per_row += self._request_row_bytes(r)
        return padded * per_row

    def _chunk_geometry(
        self, batch_size: int, chunk_batches: int
    ) -> Tuple[int, int]:
        """(num_batches, num_chunks). The last chunk is padded with
        whole batches whose rows are all masked off (static chunk shape
        -> one compile serves every chunk)."""
        nb = self.num_batches(batch_size)
        return nb, max(1, -(-nb // chunk_batches))

    def _uncached_bytes(
        self,
        requests: Sequence[ColumnRequest],
        batch_size: int,
        chunk_batches: int,
        shard_key,
    ) -> int:
        """DEVICE (HBM) bytes this request set would ADD to the cache
        (keys already resident are free — the eviction test must not
        count them, or re-scans of a cached set would evict themselves).
        Masks count at their unpacked resident width (1 byte/row); wire
        bytes are tracked separately via add_transfer_bytes."""
        _, n_chunks = self._chunk_geometry(batch_size, chunk_batches)
        chunk_rows = chunk_batches * batch_size
        keys = self._dedup_requests(requests)
        counted = dict(keys)
        if shard_key is None:  # derived lengths only ride the
            # unsharded path (device_scan_chunks gates on sharding)
            for r in self._derived_length_codes(keys):
                counted.setdefault(r.key, r)
        total = 0
        for ci in range(n_chunks):
            if (
                ROW_MASK, batch_size, chunk_batches, ci, shard_key
            ) not in self._device_cache:
                total += chunk_rows
            for k, r in counted.items():
                if self._synthesize_mask(r):
                    continue
                if (
                    k, batch_size, chunk_batches, ci, shard_key
                ) in self._device_cache:
                    continue
                total += chunk_rows * self._request_row_bytes(r)
        return total

    def _ensure_cache_budget(self, needed: int, budget: int) -> None:
        """Evict device caches (other datasets first, LRU order, then
        this one) until ``needed`` more bytes fit in ``budget``."""
        if Dataset.global_device_cache_bytes() + needed <= budget:
            return
        for key in list(Dataset._cache_registry):
            if key == self._cache_key:
                continue
            ref = Dataset._cache_registry[key]
            ds = ref()
            if ds is not None:
                ds.clear_device_cache()
            else:
                Dataset._drop_cache_key(key)
            if Dataset.global_device_cache_bytes() + needed <= budget:
                return
        if Dataset.global_device_cache_bytes() + needed > budget:
            self.clear_device_cache()

    def _host_chunk(
        self, r: ColumnRequest, start_row: int, chunk_rows: int, batch_size: int
    ) -> np.ndarray:
        """(chunk_batches, batch_size) host array for one request's
        chunk: a slice of the materialized column, zero-padded (padding
        rows carry mask False exactly like the host batch path)."""
        full = self.materialize(r)
        n = len(full)
        stop = min(start_row + chunk_rows, n)
        sl = full[start_row:stop] if start_row < n else full[:0]
        if len(sl) < chunk_rows:
            sl = np.concatenate(
                [sl, np.zeros((chunk_rows - len(sl),), dtype=full.dtype)]
            )
        return sl.reshape(-1, batch_size)

    def device_scan_chunks(
        self,
        requests: Sequence[ColumnRequest],
        batch_size: int,
        chunk_batches: int = 1,
        sharding=None,
        budget_bytes: int = 0,
        start_chunk: int = 0,
    ) -> Iterator[Dict[str, "object"]]:
        """Device-resident stacked batches for the fused ``lax.scan``
        path, yielded chunk by chunk: each chunk is a dict of
        ``(chunk_batches, batch_size)`` jax arrays. ``start_chunk``
        skips the first N chunks (resilience-layer retry/resume; chunk
        geometry is independent of the start, so chunk ``i`` is
        identical whatever chunk the iteration began at).

        Chunking is what lets a FRESH-data run overlap transfer with
        compute: ``device_put`` and the per-chunk scan dispatch are both
        async, so while the device crunches chunk i, chunk i+1's bytes
        stream over the host->device link — wall becomes
        max(transfer, compute) instead of their sum. Every chunk is cached on device, so a re-scan replays from
        HBM with zero transfers.

        Wire-byte diet (host->device bytes are a cost of every fresh
        scan):
        - validity masks ship BIT-packed (np.packbits host-side, 8x
          fewer bytes) and are expanded on device;
        - masks of all-valid columns and the row mask are synthesized on
          device via iota — they never cross the wire;
        - string 'lengths' are derived on device from dictionary codes +
          a tiny length LUT whenever the codes ship anyway.

        When adding this request set would push the resident total past
        ``budget_bytes``, older cache entries are evicted first (the new
        set alone is known to fit — the engine checks before choosing
        this path).
        """
        import jax

        n = self.num_rows
        nb, n_chunks = self._chunk_geometry(batch_size, chunk_batches)
        chunk_rows = chunk_batches * batch_size

        # NamedSharding hashes by value, so equal shardings share entries
        shard_key = sharding

        if budget_bytes:
            self._ensure_cache_budget(
                self._uncached_bytes(
                    requests, batch_size, chunk_batches, shard_key
                ),
                budget_bytes,
            )
        self._touch_cache_registry()

        def put(host: np.ndarray):
            add_transfer_bytes(host.nbytes)
            if sharding is not None:
                # lint-ok: wire-discipline: the chunk-cache put IS the
                # resident wire (packed chunks, transfer accounted)
                return jax.device_put(host, sharding)
            # lint-ok: wire-discipline: resident wire put (see above)
            return jax.device_put(host)

        keys = self._dedup_requests(requests)
        # wire-free lengths: string columns whose codes ship anyway (or
        # are already materialized) gather lengths from a LUT on device.
        # Disabled under explicit sharding (LUT gather output placement
        # would need its own annotation; the mesh path ships lengths).
        derived_lengths: Dict[str, np.ndarray] = {}
        if sharding is None:
            for k, r in keys.items():
                if r.repr != "lengths":
                    continue
                if self._schema.kind_of(r.column) != Kind.STRING:
                    continue
                codes_key = f"{r.column}::codes"
                if codes_key in keys:
                    # codes ship anyway: materialize them NOW so the
                    # dictionary (and its length LUT) exists — without
                    # this the branch only fired when some earlier
                    # caller had happened to materialize codes first
                    self.materialize(ColumnRequest(r.column, "codes"))
                if (
                    codes_key in self._materialized
                    or r.column in self._dictionaries
                ):
                    lengths = self.dict_lengths(r.column)
                    if lengths is not None:
                        derived_lengths[r.column] = lengths

        lut_cache: Dict[str, object] = {}
        pack_masks = sharding is None

        for ci in range(start_chunk, n_chunks):
            start_row = ci * chunk_rows
            rm_key = (ROW_MASK, batch_size, chunk_batches, ci, shard_key)
            if rm_key not in self._device_cache:
                if sharding is not None:
                    idx = np.arange(
                        start_row,
                        start_row + chunk_rows,
                        dtype=np.int64,
                    )
                    row_mask = put((idx < n).reshape(-1, batch_size))
                else:
                    row_mask = _chunk_row_mask_fn(chunk_batches, batch_size)(
                        np.int64(start_row), np.int64(n)
                    )
                self._device_cache[rm_key] = row_mask
                self._add_cache_bytes(chunk_rows)
            row_mask = self._device_cache[rm_key]

            out: Dict[str, object] = {ROW_MASK: row_mask}
            for k, r in keys.items():
                if self._synthesize_mask(r):
                    out[k] = row_mask
                    continue
                ck = (k, batch_size, chunk_batches, ci, shard_key)
                if ck not in self._device_cache:
                    if r.repr == "lengths" and r.column in derived_lengths:
                        codes_req = ColumnRequest(r.column, "codes")
                        codes_ck = (
                            codes_req.key, batch_size, chunk_batches, ci,
                            shard_key,
                        )
                        if codes_ck not in self._device_cache:
                            codes_host = self._host_chunk(
                                codes_req, start_row, chunk_rows, batch_size
                            )
                            self._device_cache[codes_ck] = put(codes_host)
                            self._add_cache_bytes(codes_host.nbytes)
                        if r.column not in lut_cache:
                            lengths = derived_lengths[r.column]
                            lut = np.concatenate(
                                [np.zeros(1, np.int32), lengths]
                            )
                            lut_cache[r.column] = put(lut)
                        arr = _lengths_gather_fn()(
                            self._device_cache[codes_ck],
                            lut_cache[r.column],
                        )
                    elif r.repr == "mask" and pack_masks:
                        host = self._host_chunk(
                            r, start_row, chunk_rows, batch_size
                        )
                        packed = np.packbits(
                            host, axis=1, bitorder="little"
                        )
                        arr = _mask_unpack_fn(batch_size)(put(packed))
                    else:
                        host = self._host_chunk(
                            r, start_row, chunk_rows, batch_size
                        )
                        arr = put(host)
                    self._device_cache[ck] = arr
                    self._add_cache_bytes(
                        chunk_rows * self._request_row_bytes(r)
                    )
                out[k] = self._device_cache[ck]
            yield out

    def clear_device_cache(self) -> None:
        self._device_cache.clear()
        Dataset._drop_cache_key(self._cache_key)

    def num_batches(self, batch_size: Optional[int] = None) -> int:
        n = self.num_rows
        if n == 0:
            return 1
        if batch_size is None:
            return 1
        return -(-n // batch_size)

    def fingerprint(self) -> str:
        """Source identity for checkpoint invalidation (resuming a scan
        against a CHANGED source would silently fold two datasets into
        one metric). In-memory tables have no stable storage identity,
        so this is a WEAK fingerprint — schema + row count + a sample
        of the first column's bytes; parquet sources override with file
        paths/sizes/mtimes. docs/RESILIENCE.md documents the contract."""
        h = hashlib.sha1()
        h.update(
            repr(
                [(f.name, f.kind.value) for f in self._schema.fields]
            ).encode()
        )
        h.update(str(self.num_rows).encode())
        if self.num_rows and len(self._schema):
            first = self._schema.fields[0].name
            head = self._table.column(first).slice(
                0, min(self.num_rows, 1024)
            )
            h.update(repr(head.to_pylist()).encode())
        return f"mem-{h.hexdigest()[:20]}"
