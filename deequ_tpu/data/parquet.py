"""Streaming parquet ingest: scan-feed batches without materializing
whole columns.

Reference context: the reference delegates IO to Spark's parquet reader
feeding partitioned scans (SURVEY.md §7 stage 0, §5.7 "streamed
chunking over record batches"). Here :class:`ParquetDataset` exposes
the same Dataset contract over a (multi-file) parquet source:

- ``device_batches`` STREAMS: Arrow record batches are read column-
  pruned from the files, re-chunked to the engine's fixed batch size,
  converted to device representations per batch, and fed to the fused
  scan — host memory stays O(batch x requested columns), so a table
  far larger than RAM profiles fine.
- string columns: under ``config.dict_deltas`` (default) the global
  dictionary is built INCREMENTALLY inside the same pass — each batch
  absorbs its new uniques into a per-column accumulator, codes index
  against the accumulator, and only the DELTA (new uniques, appended
  in first-occurrence order) rides the batch as a host-only payload
  (table.DICT_DELTA_PREFIX) for delta-aware ops to fold into their
  LUT states. Incremental accumulation provably reproduces the exact
  dictionary (values AND order) of the legacy streaming pre-pass
  (``_collect_uniques``), independent of chunking — which is why
  delta codes and pre-pass codes are interchangeable and a stable
  dictionary costs zero bytes after batch 1. With the flag off, the
  legacy one-extra-pass pre-pass builds the dictionary up front.
- ``materialize`` (full column) still works — the resident fast path
  uses it when the request set fits the device cache budget — but the
  streaming path never calls it.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.dataset as pads

from deequ_tpu.data.table import (
    ColumnRequest,
    DICT_DELTA_PREFIX,
    Dataset,
    Field,
    Kind,
    ROW_MASK,
    Schema,
    _kind_of,
    convert_basic_repr,
    dictionary_to_numpy,
    is_lane_repr,
    lane_repr_dtype,
    narrow_codes,
)
from deequ_tpu.telemetry import get_telemetry


def _read_batches(scanner) -> Iterator[pa.RecordBatch]:
    """The scanner's record batches, each read (parquet page read and
    decompression) as the span ``ingest:read`` on the reading thread.
    The span closes before the batch is handed on."""
    tm = get_telemetry()
    batches = iter(scanner.to_batches())
    while True:
        with tm.span("ingest:read"):
            batch = next(batches, None)
        if batch is None:
            return
        yield batch


class _IncrementalDict:
    """One column's global dictionary, grown batch-by-batch inside the
    single data pass. ``absorb_and_encode`` appends a batch's new
    uniques (first-occurrence order — provably the same dictionary,
    values and order, that ``_collect_uniques`` builds over the same
    row stream, whatever the chunking) and returns the batch's int32
    codes against the grown accumulator, so a row's code is always
    valid against every dictionary state at or after its batch."""

    __slots__ = ("values", "n")

    def __init__(self) -> None:
        self.values: Optional[pa.Array] = None
        self.n = 0

    def absorb_and_encode(self, column: pa.Array) -> np.ndarray:
        if pa.types.is_dictionary(column.type):
            column = pc.cast(column, column.type.value_type)
        self.absorb(pc.drop_null(pc.unique(column)))
        return self.encode(column)

    # r10 split: the ordered ingest pool computes a batch's uniques on
    # a WORKER thread (pc.unique is order-free) and runs absorb+encode
    # on the consumer at ordered release, so accumulator growth — the
    # one order-dependent step — happens exactly as the single-thread
    # path would. absorb(unique(b0)); absorb(unique(b1)); ... yields
    # the identical first-occurrence dictionary whatever the chunking.

    def absorb(self, uniques: pa.Array) -> None:
        """Append a batch's new uniques in first-occurrence order."""
        if len(uniques):
            if self.values is None:
                self.values = uniques
            else:
                idx = pc.index_in(uniques, value_set=self.values)
                new = uniques.filter(pc.is_null(idx))
                if len(new):
                    self.values = pa.concat_arrays([self.values, new])
            self.n = len(self.values)

    def encode(self, column: pa.Array) -> np.ndarray:
        """int32 codes against the accumulator as absorbed so far
        (nulls and unseen values index to -1)."""
        if pa.types.is_dictionary(column.type):
            column = pc.cast(column, column.type.value_type)
        if self.values is None or self.n == 0:
            return np.full(len(column), -1, dtype=np.int32)
        idx = pc.index_in(column, value_set=self.values)
        idx = pc.fill_null(idx, pa.scalar(-1, idx.type))
        return np.ascontiguousarray(
            idx.to_numpy(zero_copy_only=False).astype(np.int32)
        )

    def slice_values(self, start: int) -> np.ndarray:
        """Accumulated values [start, n) as a host numpy array — one
        delta payload's ``values``."""
        return dictionary_to_numpy(
            self.values.slice(start, self.n - start)
        )


def _column_batch_to_reprs(
    column: pa.Array,
    kind: Kind,
    requests: List[str],
    value_set: Optional[pa.Array] = None,
    values_dtype: Optional[np.dtype] = None,
) -> Dict[str, np.ndarray]:
    """Convert one record-batch column into the requested device reprs.
    mask/values/lengths/u64bits share Dataset.materialize's conversion
    rules (table.convert_basic_repr); codes come from a vectorized
    ``pc.index_in`` against the dataset-global dictionary (Arrow treats
    NaN as equal to NaN, matching the in-memory dictionary_encode
    path; nulls index to -1). ``values_dtype`` applies the PER-COLUMN
    wire-narrowing decision (from parquet statistics) — narrowing per
    batch would make streamed batch dtypes unstable and recompile the
    fused scan per dtype combination."""
    out: Dict[str, np.ndarray] = {}
    for repr_name in requests:
        if repr_name == "codes":
            assert value_set is not None
            if pa.types.is_dictionary(column.type):
                column = pc.cast(column, column.type.value_type)
            idx = pc.index_in(column, value_set=value_set)
            idx = pc.fill_null(idx, pa.scalar(-1, idx.type))
            out["codes"] = np.ascontiguousarray(
                # lint-ok: wire-discipline: loop is over the REPRS of
                # one column, not batches; the width derives from the
                # run-stable global value_set, identical every batch
                narrow_codes(
                    idx.to_numpy(zero_copy_only=False).astype(np.int32),
                    len(value_set),
                )
            )
        else:
            arr = convert_basic_repr(column, kind, repr_name)
            if repr_name == "values" and values_dtype is not None:
                arr = arr.astype(values_dtype)
            out[repr_name] = arr
    return out


class ParquetDataset(Dataset):
    """A Dataset over parquet file(s)/directory, scanned lazily."""

    # r10: class-level opt-in for the ordered ingest pool — the engine
    # engages ``ingest_work_items`` only on classes that declare this
    # (a __getattr__-delegating wrapper must define its own planner)
    supports_parallel_ingest = True

    def __init__(self, source, read_batch_rows: int = 1 << 20):
        # no super().__init__: there is no in-memory table
        # a prebuilt pyarrow dataset (the shard_view planner's
        # row-group-restricted FileSystemDataset) passes through as-is
        self._source = (
            source
            if isinstance(source, pads.Dataset)
            else pads.dataset(source, format="parquet")
        )
        self._shard_tag = None
        self._read_batch_rows = read_batch_rows
        self._schema = Schema(
            tuple(
                Field(name, _kind_of(typ))
                for name, typ in zip(
                    self._source.schema.names, self._source.schema.types
                )
            )
        )
        self._num_rows = self._source.count_rows()
        self._materialized: Dict[str, np.ndarray] = {}
        self._dictionaries: Dict[str, np.ndarray] = {}
        # one-pass dictionary deltas: per-column incremental
        # accumulators (persist across device_batches calls so a
        # restart resumes the grown dictionary) and the set of columns
        # COMMITTED to delta mode by a plan-time dict_delta_capacity
        # consultation
        self._delta_dicts: Dict[str, _IncrementalDict] = {}
        self._delta_columns: set = set()
        self._value_sets: Dict[str, pa.Array] = {}
        self._null_counts: Dict[str, int] = {}
        self._device_cache: Dict = {}
        self._cache_key = id(self)
        import weakref

        weakref.finalize(self, Dataset._drop_cache_key, self._cache_key)

    # -- metadata -------------------------------------------------------

    @property
    def table(self) -> pa.Table:  # loads everything; avoid on big data
        return self._source.to_table()

    @property
    def num_rows(self) -> int:
        return self._num_rows

    @property
    def dictionary_traversal_batch(self) -> int:
        # a column's dictionary is a pre-pass over the files
        # (``_collect_uniques``), read in batches of this many rows
        return self._read_batch_rows

    @property
    def num_columns(self) -> int:
        return len(self._schema)

    def filter_rows(self, mask: np.ndarray) -> Dataset:
        return Dataset(self.table.filter(pa.array(mask)))

    def select(self, columns: Sequence[str]) -> Dataset:
        return Dataset(self._source.to_table(columns=list(columns)))

    def record_batches(
        self, columns: Sequence[str], batch_rows: int = 1 << 20
    ) -> Iterator[pa.RecordBatch]:
        scanner = self._source.scanner(
            columns=list(columns), batch_size=batch_rows
        )
        return _read_batches(scanner)

    def fingerprint(self) -> str:
        """STRONG source identity for checkpoint invalidation: the
        sorted file list plus per-file size and mtime (rewritten,
        appended or touched files all change it). Falls back to
        path-only identity for storage without stat support."""
        import hashlib
        import os

        h = hashlib.sha1()
        for path in sorted(self._source.files):
            h.update(path.encode())
            try:
                st = os.stat(path)
                h.update(f":{st.st_size}:{st.st_mtime_ns}".encode())
            except OSError:
                pass
        if self._shard_tag is not None:
            # two shard views of the SAME files must not share a
            # checkpoint identity (their row streams differ)
            h.update(
                f"shard:{self._shard_tag[0]}/{self._shard_tag[1]}".encode()
            )
        h.update(str(self._num_rows).encode())
        return f"parquet-{h.hexdigest()[:20]}"

    # -- statistics from parquet metadata -------------------------------

    def _column_null_count(self, column: str) -> int:
        if column not in self._null_counts:
            total = 0
            known = True
            for fragment in self._source.get_fragments():
                meta = fragment.metadata
                idx = self._source.schema.get_field_index(column)
                for rg in range(meta.num_row_groups):
                    stats = meta.row_group(rg).column(idx).statistics
                    if stats is None or stats.null_count is None:
                        known = False
                        break
                    total += stats.null_count
                if not known:
                    break
            # unknown stats -> conservatively "has nulls" (mask ships)
            self._null_counts[column] = total if known else 1
        return self._null_counts[column]

    def _is_all_valid(self, column: str) -> bool:
        return self._column_null_count(column) == 0

    def _values_dtype(self, column: str) -> Optional[np.dtype]:
        """Per-COLUMN wire-narrowing decision for int64 columns, from
        parquet row-group min/max statistics (one decision for the whole
        stream; see _column_batch_to_reprs). None = keep native."""
        if not hasattr(self, "_values_dtypes"):
            self._values_dtypes: Dict[str, Optional[np.dtype]] = {}
        if column in self._values_dtypes:
            return self._values_dtypes[column]
        decision: Optional[np.dtype] = None
        arrow_type = self._column_arrow_type(column)
        if (
            self._schema.kind_of(column) == Kind.INTEGRAL
            and pa.types.is_integer(arrow_type)
            and arrow_type.bit_width == 64
        ):
            rng = self._stats_min_max(column)
            if (
                rng is not None
                and rng[0] >= -(2**31)
                and rng[1] < 2**31
            ):
                decision = np.dtype(np.int32)
        self._values_dtypes[column] = decision
        return decision

    def _stats_min_max(self, column: str):
        """(min, max) folded over every fragment's row-group
        statistics, or None when any group lacks them — THE one stats
        walk (consumed by the wire-narrowing decision above and the
        integral-range probe below)."""
        lo, hi = None, None
        idx = self._source.schema.get_field_index(column)
        for fragment in self._source.get_fragments():
            meta = fragment.metadata
            for rg in range(meta.num_row_groups):
                stats = meta.row_group(rg).column(idx).statistics
                if (
                    stats is None
                    or not stats.has_min_max
                    or stats.min is None
                    or stats.max is None
                ):
                    return None
                lo = stats.min if lo is None else min(lo, stats.min)
                hi = stats.max if hi is None else max(hi, stats.max)
        return None if lo is None else (lo, hi)

    def _column_arrow_type(self, column: str) -> pa.DataType:
        idx = self._source.schema.get_field_index(column)
        return self._source.schema.types[idx]

    def request_dtype(self, req: ColumnRequest) -> np.dtype:
        """Batch dtype WITHOUT materializing the stream: run the one
        authoritative conversion (_column_batch_to_reprs) on a ZERO-ROW
        column of the file's type, so any future change to the
        conversion/narrowing rules is reflected here automatically."""
        if req.repr == "mask":
            return np.dtype(bool)
        if is_lane_repr(req.repr):
            return lane_repr_dtype(req.repr)
        if req.repr == "codes" and self._dict_delta_mode(req.column):
            # delta-mode codes are canonical int32 on every path (the
            # wire codec layer narrows them on the wire); crucially
            # this answers WITHOUT the dictionary pre-pass — plan
            # building must stay zero-pass
            return np.dtype(np.int32)
        kind = self._schema.kind_of(req.column)
        value_set = (
            self._dict_value_set(req.column)
            if req.repr == "codes"
            else None
        )
        values_dtype = (
            self._values_dtype(req.column)
            if req.repr == "values"
            else None
        )
        empty = pa.array([], type=self._column_arrow_type(req.column))
        out = _column_batch_to_reprs(
            empty, kind, [req.repr], value_set, values_dtype
        )
        return np.dtype(out[req.repr].dtype)

    # -- one-pass dictionary deltas --------------------------------------

    def _dict_delta_mode(self, column: str) -> bool:
        """True when this column's codes ship as incremental dictionary
        deltas inside the single data pass (docs/PERF.md "One-pass
        dictionary deltas") instead of via the legacy pre-pass. A
        column COMMITTED by ``dict_delta_capacity`` stays in delta mode
        for run-long consistency; otherwise the flag and the kind
        decide — except when an already-cached dictionary is too big
        for the delta LUT capacity, where the free consts path wins."""
        if column in self._delta_columns:
            return True
        from deequ_tpu import config

        opts = config.options()
        if not opts.dict_deltas:
            return False
        if self._schema.kind_of(column) != Kind.STRING:
            return False
        d = self._dictionaries.get(column)
        if d is not None and len(d) > opts.dict_delta_capacity:
            return False
        return True

    def dict_delta_capacity(self, column: str) -> Optional[int]:
        """Static delta-LUT capacity for delta-aware consumers at PLAN
        time (None: this column's codes will not ship deltas — build
        the consts-LUT form). Consulting this COMMITS the column: once
        a plan holds a delta-aware op sized to the capacity,
        ``device_batches`` must ship deltas for it on every call."""
        if not self._dict_delta_mode(column):
            return None
        self._delta_columns.add(column)
        from deequ_tpu import config

        return int(config.options().dict_delta_capacity)

    # -- global dictionaries (streaming pre-pass) -----------------------

    def _collect_uniques(
        self, column: str, cap: Optional[int]
    ) -> Optional[pa.Array]:
        """Stream distinct non-null values, staying ENTIRELY in Arrow
        (pc.unique per chunk, periodic compaction) — a Python set would
        cost GBs at tens of millions of distinct values. Returns None
        once the count provably exceeds ``cap``."""
        # an HONEST pass counter: this pre-pass reads the whole column,
        # so one-pass claims (tests/test_wire_codecs.py) can pin that
        # delta-mode suites never reach here
        with get_telemetry().traversal(
            "uniques", rows=self.num_rows, columns=1
        ):
            return self._scan_uniques(column, cap)

    def _scan_uniques(
        self, column: str, cap: Optional[int]
    ) -> Optional[pa.Array]:
        base: Optional[pa.Array] = None  # already-deduped accumulator
        fresh: List[pa.Array] = []  # per-batch uniques since last compact
        fresh_n = 0

        def compact() -> None:
            nonlocal base, fresh, fresh_n
            arrays = ([base] if base is not None else []) + fresh
            base = pc.unique(pa.concat_arrays(arrays))
            fresh = []
            fresh_n = 0

        scanner = self._source.scanner(
            columns=[column], batch_size=self._read_batch_rows
        )
        field_type = self._source.schema.field(column).type
        if pa.types.is_dictionary(field_type):
            field_type = field_type.value_type
        for batch in _read_batches(scanner):
            col = batch.column(0)
            if pa.types.is_dictionary(col.type):
                col = pc.cast(col, col.type.value_type)
            u = pc.drop_null(pc.unique(col))
            if len(u):
                fresh.append(u)
                fresh_n += len(u)
            # compact on FRESH volume only (an accumulator already past
            # the threshold must not trigger a full re-unique per batch),
            # or when the optimistic total might prove the cap exceeded
            over_cap_maybe = cap is not None and (
                (0 if base is None else len(base)) + fresh_n > cap
            )
            if fresh_n > 4 * self._read_batch_rows or over_cap_maybe:
                compact()
                if cap is not None and len(base) > cap:
                    return None
        if fresh_n:
            compact()
        if base is None:
            return pa.array([], field_type)
        if cap is not None and len(base) > cap:
            return None
        return base

    def integral_range(self, column: str):
        """Row-group min/max statistics make the range probe FREE for
        parquet sources (no data scan); unknown stats -> None (treated
        as unbounded)."""
        if self._schema.kind_of(column) != Kind.INTEGRAL:
            return None
        if not hasattr(self, "_integral_ranges"):
            self._integral_ranges = {}
        if column not in self._integral_ranges:
            rng = self._stats_min_max(column)
            self._integral_ranges[column] = (
                (int(rng[0]), int(rng[1]))
                if rng is not None and isinstance(rng[0], int)
                else None
            )
        return self._integral_ranges[column]

    def dictionary_size_within(self, column: str, cap: int):
        if self._lane_too_big(column, cap):
            return None
        if column in self._dictionaries:
            n = len(self._dictionaries[column])
            return n if n <= cap else None
        uniques = self._collect_uniques(column, cap)
        if uniques is None:
            return None  # over cap: never materialize the full set
        self._store_dictionary(column, uniques)
        return len(self._dictionaries[column])

    def _store_dictionary(self, column: str, uniques: pa.Array) -> None:
        self._value_sets[column] = uniques
        self._dictionaries[column] = dictionary_to_numpy(uniques)

    def dictionary(self, column: str) -> np.ndarray:
        if column not in self._dictionaries:
            self._store_dictionary(
                column, self._collect_uniques(column, None)
            )
        return self._dictionaries[column]

    def _dict_value_set(self, column: str) -> pa.Array:
        self.dictionary(column)
        return self._value_sets[column]

    # -- full-column materialization (resident path only) ---------------

    def _reprs_for_kind(self, kind: Kind, column: str) -> List[str]:
        """All reprs one scan can fill for a column of this kind —
        materializing any repr fills the others too, so callers needing
        several (values+mask, codes+mask+lengths) cost ONE file scan. A
        lane column fills its mask with each repr asked for (no codes:
        it has no dictionary)."""
        if kind == Kind.STRING:
            if self.string_lane(column):
                return ["mask"]
            return ["codes", "mask", "lengths"]
        return ["values", "mask"]

    def _lane_head(self, column: str):
        from deequ_tpu.data.strings import LANE_PROBE_ROWS, PROBE_CHUNK_ROWS

        scanner = self._source.scanner(
            columns=[column], batch_size=PROBE_CHUNK_ROWS
        )
        left = LANE_PROBE_ROWS
        for batch in _read_batches(scanner):
            yield batch.column(0).slice(0, left)
            left -= min(left, batch.num_rows)
            if not left:
                return

    def materialize(self, req: ColumnRequest) -> np.ndarray:
        key = req.key
        if key in self._materialized:
            return self._materialized[key]
        kind = self._schema.kind_of(req.column)
        reprs = self._reprs_for_kind(kind, req.column)
        if req.repr not in reprs:
            reprs = reprs + [req.repr]  # let the converter raise clearly
        value_set = (
            self._dict_value_set(req.column) if "codes" in reprs else None
        )
        chunks: Dict[str, List[np.ndarray]] = {r: [] for r in reprs}
        scanner = self._source.scanner(
            columns=[req.column], batch_size=self._read_batch_rows
        )
        values_dtype = self._values_dtype(req.column)
        for batch in _read_batches(scanner):
            out = _column_batch_to_reprs(
                batch.column(0), kind, reprs, value_set, values_dtype
            )
            for r in reprs:
                chunks[r].append(out[r])
        for r in reprs:
            if chunks[r]:
                arr = np.concatenate(chunks[r])
            else:
                arr = _column_batch_to_reprs(
                    pa.array([], self._source.schema.field(req.column).type),
                    kind,
                    [r],
                    value_set,
                    values_dtype,
                )[r]
            if r == "codes" and self._dict_delta_mode(req.column):
                # delta-committed codes are canonical int32 on EVERY
                # path, so resident and streaming plans of the same
                # suite see one dtype (request_dtype above agrees)
                arr = arr.astype(np.int32)
            self._materialized[f"{req.column}::{r}"] = arr
        return self._materialized[key]

    # -- streaming batches ----------------------------------------------

    def device_batches(
        self,
        requests: Sequence[ColumnRequest],
        batch_size: Optional[int] = None,
        start_batch: int = 0,
    ) -> Iterator[Dict[str, np.ndarray]]:
        """Stream fixed-size batches from the parquet source: read
        column-pruned record batches, convert to device reprs, re-chunk
        to ``batch_size``, zero-pad the tail. Host memory is bounded by
        O(read_batch + batch_size) per requested repr.

        ``start_batch`` (resilience-layer retry/resume) skips the first
        ``start_batch * batch_size`` rows of the stream by slicing the
        leading record batches away before any conversion; since the
        skip is a whole number of engine batches, the re-chunker's batch
        boundaries — and therefore every yielded batch — are identical
        to the corresponding batches of a full stream."""
        n = self.num_rows
        if batch_size is None:
            batch_size = n if n > 0 else 1
        batch_size = max(1, batch_size)
        skip_rows = start_batch * batch_size

        keys = self._dedup_requests(requests)
        by_column: Dict[str, List[str]] = {}
        for r in keys.values():
            by_column.setdefault(r.column, []).append(r.repr)
        columns = sorted(by_column)
        if not columns or n == 0:
            # degenerate: no columns requested (e.g. Size only) or empty
            yield from self._empty_or_counting_batches(
                keys, batch_size, n, skip_rows
            )
            return
        # one-pass dictionary deltas: delta-mode columns build their
        # dictionary INSIDE this pass and ship only deltas; everything
        # else keeps the legacy streaming pre-pass
        delta_cols = sorted(
            c
            for c, reprs in by_column.items()
            if "codes" in reprs and self._dict_delta_mode(c)
        )
        accs = {
            c: self._delta_dicts.setdefault(c, _IncrementalDict())
            for c in delta_cols
        }
        # per-CALL delta cursors: a fresh call (restart or resume)
        # re-ships the full accumulated dictionary on its first yielded
        # batch — idempotent by construction, since delta application
        # overwrites LUT rows with values hashed/classified from the
        # values themselves
        shipped_n = {c: 0 for c in delta_cols}
        # pre-build dictionaries for remaining code requests
        value_sets = {
            c: self._dict_value_set(c)
            for c, reprs in by_column.items()
            if "codes" in reprs and c not in accs
        }
        values_dtypes = {
            c: self._values_dtype(c)
            for c, reprs in by_column.items()
            if "values" in reprs
        }

        pending: Dict[str, List[np.ndarray]] = {k: [] for k in keys}
        pending_rows = 0
        tm = get_telemetry()

        def convert(record_batch) -> None:
            """One record batch to device reprs, appended to pending."""
            with tm.span("ingest:decode"):
                for ci, column_name in enumerate(columns):
                    kind = self._schema.kind_of(column_name)
                    wanted = by_column[column_name]
                    col = record_batch.column(ci)
                    if column_name in accs:
                        reprs = _column_batch_to_reprs(
                            col,
                            kind,
                            [r for r in wanted if r != "codes"],
                        )
                        # absorb new uniques + encode against the grown
                        # accumulator — the one traversal of the values
                        reprs["codes"] = accs[
                            column_name
                        ].absorb_and_encode(col)
                    else:
                        reprs = _column_batch_to_reprs(
                            col,
                            kind,
                            wanted,
                            value_sets.get(column_name),
                            values_dtypes.get(column_name),
                        )
                    for repr_name, arr in reprs.items():
                        pending[f"{column_name}::{repr_name}"].append(arr)

        def cut() -> Dict[str, np.ndarray]:
            """The next engine batch off the front of pending."""
            nonlocal pending_rows
            with tm.span("ingest:decode"):
                batch: Dict[str, np.ndarray] = {}
                width = min(pending_rows, batch_size)
                pad = batch_size - width
                for k in keys:
                    joined = (
                        np.concatenate(pending[k])
                        if len(pending[k]) > 1
                        else pending[k][0]
                    )
                    head, tail = joined[:width], joined[width:]
                    pending[k] = [tail] if len(tail) else []
                    if pad:
                        head = np.concatenate(
                            [head, np.zeros((pad,), dtype=head.dtype)]
                        )
                    batch[k] = head
                row_mask = np.ones((batch_size,), dtype=bool)
                if pad:
                    row_mask[width:] = False
                    for k in keys:
                        if k.endswith("::mask"):
                            batch[k] = batch[k] & row_mask
                batch[ROW_MASK] = row_mask
                # attach pending dictionary deltas to the FIRST batch
                # drained since the accumulator grew: every code in
                # this (and any earlier) batch indexes within the
                # shipped rows by construction
                for c in delta_cols:
                    acc = accs[c]
                    if acc.n > shipped_n[c]:
                        batch[DICT_DELTA_PREFIX + c] = {
                            "start": shipped_n[c],
                            "values": acc.slice_values(shipped_n[c]),
                        }
                        shipped_n[c] = acc.n
                pending_rows -= width
            return batch

        def drain(force_pad: bool):
            while pending_rows >= batch_size or (
                force_pad and pending_rows > 0
            ):
                yield cut()

        scanner = self._source.scanner(
            columns=columns, batch_size=self._read_batch_rows
        )
        for record_batch in _read_batches(scanner):
            if skip_rows > 0:
                if record_batch.num_rows <= skip_rows:
                    skip_rows -= record_batch.num_rows
                    continue
                record_batch = record_batch.slice(skip_rows)
                skip_rows = 0
            if record_batch.num_rows == 0:
                continue
            convert(record_batch)
            pending_rows += record_batch.num_rows
            yield from drain(force_pad=False)
        yield from drain(force_pad=True)
        if start_batch == 0:
            # a full uninterrupted stream saw every record batch, so
            # the accumulator IS the global dictionary — cache it and a
            # later resident pass / profiler / single-analyzer consumer
            # pays no extra data pass
            for c in delta_cols:
                if (
                    c not in self._dictionaries
                    and accs[c].values is not None
                ):
                    self._store_dictionary(c, accs[c].values)

    # -- process-sharded ingest (ROADMAP item 3) -------------------------

    def shard_row_groups(
        self, process_index: int, process_count: int
    ) -> list:
        """Deterministic balanced row-group assignment: greedy
        least-loaded-by-rows over every (path-sorted) fragment's row
        groups. Every process computes the SAME full assignment from
        the same metadata, so the shards are a disjoint cover with no
        coordination. Returns this process's row-group fragments (in
        source order)."""
        if process_count <= 0:
            raise ValueError("process_count must be positive")
        if not 0 <= process_index < process_count:
            raise ValueError(
                f"process_index {process_index} outside "
                f"[0, {process_count})"
            )
        groups = []  # (rows, file_order, rg_order, fragment)
        fragments = sorted(
            self._source.get_fragments(), key=lambda f: f.path
        )
        for fi, fragment in enumerate(fragments):
            meta = fragment.metadata
            for gi, sub in enumerate(fragment.split_by_row_group()):
                groups.append(
                    (int(meta.row_group(gi).num_rows), fi, gi, sub)
                )
        loads = [0] * process_count
        assign: list = [[] for _ in range(process_count)]
        # largest-first greedy; ties broken by source order, target
        # ties by process index — fully deterministic
        for rows, fi, gi, sub in sorted(
            groups, key=lambda g: (-g[0], g[1], g[2])
        ):
            p = min(range(process_count), key=lambda i: (loads[i], i))
            loads[p] += rows
            assign[p].append((fi, gi, sub))
        return [
            sub for _, _, sub in sorted(assign[process_index])
        ]

    def shard_view(
        self, process_index: int, process_count: int
    ) -> "ParquetDataset":
        """This process's shard as a full ParquetDataset: a pyarrow
        FileSystemDataset restricted to the assigned row-group
        fragments (reads touch ONLY those row groups), fingerprint
        tagged with (process_index, process_count) so shard checkpoints
        never collide with whole-source ones."""
        fragments = self.shard_row_groups(process_index, process_count)
        view = ParquetDataset(
            pads.FileSystemDataset(
                fragments,
                self._source.schema,
                self._source.format,
                self._source.filesystem,
            ),
            self._read_batch_rows,
        )
        view._shard_tag = (int(process_index), int(process_count))
        # one lane decision for the whole source: every shard ships the
        # same representation of a column
        view._string_lanes = self.__dict__.setdefault("_string_lanes", {})
        view._lane_head = self._lane_head
        # count_rows() on a row-group-restricted fragment reports the
        # WHOLE file (pyarrow quirk; scans are correctly restricted) —
        # recount from the assigned row-group metadata
        view._num_rows = sum(
            int(rg.num_rows)
            for fragment in fragments
            for rg in fragment.row_groups
        )
        return view

    # -- r10 ordered-pool work items -------------------------------------

    def ingest_work_items(
        self,
        requests: Sequence[ColumnRequest],
        batch_size: Optional[int] = None,
        start_batch: int = 0,
    ):
        """Work-item twin of ``device_batches`` for the ordered ingest
        pool (engine/ingest.py). The READER (this generator) does only
        Arrow-level slicing to engine-batch granularity — zero-copy,
        and parquet decompression is already parallel inside the
        pyarrow scanner. Each item's heavy conversion runs on a pool
        WORKER via ``item.decode()`` (numpy reprs + per-batch uniques
        for delta columns — order-free work), and ``item.commit``
        runs strictly in batch order on the consumer (accumulator
        absorb, codes, delta cut, end-of-stream dictionary caching —
        all the order-dependent machinery).

        ``device_batches`` is deliberately untouched: workers=1 runs
        it, byte for byte the pre-r10 single-thread path — the
        differential oracle the pool tests pin against."""
        n = self.num_rows
        if batch_size is None:
            batch_size = n if n > 0 else 1
        batch_size = max(1, batch_size)
        skip_rows = start_batch * batch_size

        keys = self._dedup_requests(requests)
        by_column: Dict[str, List[str]] = {}
        for r in keys.values():
            by_column.setdefault(r.column, []).append(r.repr)
        columns = sorted(by_column)
        if not columns or n == 0:
            index = start_batch
            for batch in self._empty_or_counting_batches(
                keys, batch_size, n, skip_rows
            ):
                yield _PrecomputedIngestItem(index, batch)
                index += 1
            return
        delta_cols = sorted(
            c
            for c, reprs in by_column.items()
            if "codes" in reprs and self._dict_delta_mode(c)
        )
        state = _IngestPlanState(
            dataset=self,
            columns=columns,
            by_column=by_column,
            kinds={c: self._schema.kind_of(c) for c in columns},
            delta_cols=delta_cols,
            accs={
                c: self._delta_dicts.setdefault(c, _IncrementalDict())
                for c in delta_cols
            },
            shipped_n={c: 0 for c in delta_cols},
            value_sets={
                c: self._dict_value_set(c)
                for c, reprs in by_column.items()
                if "codes" in reprs and c not in delta_cols
            },
            values_dtypes={
                c: self._values_dtype(c)
                for c, reprs in by_column.items()
                if "values" in reprs
            },
            start_batch=start_batch,
            batch_size=batch_size,
        )

        pending: Dict[str, List[pa.Array]] = {c: [] for c in columns}
        pending_rows = 0
        index = start_batch
        # one-item holdback so the LAST item can carry final=True (it
        # owns the end-of-stream dictionary caching in commit)
        held: Optional[_ParquetIngestItem] = None

        def cut(force_pad: bool):
            nonlocal pending_rows, index, held
            while pending_rows >= batch_size or (
                force_pad and pending_rows > 0
            ):
                width = min(pending_rows, batch_size)
                chunks: Dict[str, List[pa.Array]] = {}
                for c in columns:
                    taken: List[pa.Array] = []
                    rest: List[pa.Array] = []
                    got = 0
                    for arr in pending[c]:
                        if got >= width:
                            rest.append(arr)
                            continue
                        take = min(len(arr), width - got)
                        taken.append(
                            arr if take == len(arr) else arr.slice(0, take)
                        )
                        if take < len(arr):
                            rest.append(arr.slice(take))
                        got += take
                    chunks[c] = taken
                    pending[c] = rest
                pending_rows -= width
                item = _ParquetIngestItem(index, width, state, chunks)
                index += 1
                if held is not None:
                    yield held
                held = item

        scanner = self._source.scanner(
            columns=columns, batch_size=self._read_batch_rows
        )
        for record_batch in _read_batches(scanner):
            if skip_rows > 0:
                if record_batch.num_rows <= skip_rows:
                    skip_rows -= record_batch.num_rows
                    continue
                record_batch = record_batch.slice(skip_rows)
                skip_rows = 0
            if record_batch.num_rows == 0:
                continue
            for ci, column_name in enumerate(columns):
                pending[column_name].append(record_batch.column(ci))
            pending_rows += record_batch.num_rows
            yield from cut(force_pad=False)
        yield from cut(force_pad=True)
        if held is not None:
            held.final = True
            yield held

    def _empty_or_counting_batches(
        self, keys, batch_size: int, n: int, skip_rows: int = 0
    ):
        """No requested columns (Size()-only) or an empty source."""
        if n == 0:
            if skip_rows > 0:
                return
            batch: Dict[str, np.ndarray] = {}
            for k, r in keys.items():
                if r.repr == "codes" and self._dict_delta_mode(r.column):
                    # delta-mode codes: canonical int32, no pre-pass
                    batch[k] = np.zeros((batch_size,), dtype=np.int32)
                    continue
                kind = self._schema.kind_of(r.column)
                value_set = (
                    self._dict_value_set(r.column)
                    if r.repr == "codes"
                    else None
                )
                empty = _column_batch_to_reprs(
                    pa.array([], self._source.schema.field(r.column).type),
                    kind,
                    [r.repr],
                    value_set,
                    self._values_dtype(r.column)
                    if r.repr == "values"
                    else None,
                )[r.repr]
                batch[k] = np.zeros((batch_size,), dtype=empty.dtype)
            batch[ROW_MASK] = np.zeros((batch_size,), dtype=bool)
            yield batch
            return
        remaining = n - skip_rows
        while remaining > 0:
            width = min(remaining, batch_size)
            row_mask = np.zeros((batch_size,), dtype=bool)
            row_mask[:width] = True
            yield {ROW_MASK: row_mask}
            remaining -= width


class _IngestPlanState:
    """Shared, consumer-owned state of one ingest_work_items call: the
    dictionary accumulators and delta cursors every item's ordered
    ``commit`` mutates (only the pool consumer touches them, strictly
    in batch order), plus the immutable per-call conversion config."""

    __slots__ = (
        "dataset",
        "columns",
        "by_column",
        "kinds",
        "delta_cols",
        "accs",
        "shipped_n",
        "value_sets",
        "values_dtypes",
        "start_batch",
        "batch_size",
    )

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw[k])


class _ParquetIngestItem:
    """One engine batch's ingest work, split across pool stages:

    - ``decode()`` (any WORKER thread, order-free): cast/concat the
      Arrow chunk slices, run the authoritative repr conversion
      (_column_batch_to_reprs), pad to batch width, build the row
      mask; for delta columns also compute the batch's uniques
      (pc.unique — chunking-independent) but do NOT touch the shared
      accumulator.
    - ``commit(decoded)`` (the CONSUMER, strictly in batch order):
      absorb uniques into the shared _IncrementalDict, compute codes
      against the grown accumulator, cut the {start, values} delta
      payload, and — on the final item of an unresumed stream — cache
      the completed dictionary, exactly like device_batches' tail.

    ``complete`` is True when the item needs no ordered commit work
    (no delta columns), letting the pool wire-pack it on the worker.
    """

    __slots__ = (
        "index",
        "width",
        "final",
        "_state",
        "_chunks",
        "_delta_raw",
    )

    def __init__(self, index, width, state, chunks):
        self.index = index
        self.width = width
        self.final = False
        self._state = state
        self._chunks = chunks
        self._delta_raw = None

    @property
    def complete(self) -> bool:
        return not self._state.delta_cols

    def decode(self) -> Dict[str, np.ndarray]:
        st = self._state
        bs = st.batch_size
        delta_raw: Dict[str, tuple] = {}
        batch: Dict[str, np.ndarray] = {}
        for c in st.columns:
            chunks = []
            for arr in self._chunks[c]:
                if pa.types.is_dictionary(arr.type):
                    # cast per chunk: different record batches may
                    # carry different local dictionaries, which
                    # concat_arrays will not unify
                    arr = pc.cast(arr, arr.type.value_type)
                chunks.append(arr)
            col = (
                chunks[0]
                if len(chunks) == 1
                else pa.concat_arrays(chunks)
            )
            kind = st.kinds[c]
            wanted = st.by_column[c]
            if c in st.accs:
                reprs = _column_batch_to_reprs(
                    col, kind, [r for r in wanted if r != "codes"]
                )
                delta_raw[c] = (col, pc.drop_null(pc.unique(col)))
            else:
                reprs = _column_batch_to_reprs(
                    col,
                    kind,
                    wanted,
                    st.value_sets.get(c),
                    st.values_dtypes.get(c),
                )
            for repr_name, arr in reprs.items():
                batch[f"{c}::{repr_name}"] = arr
        pad = bs - self.width
        row_mask = np.ones((bs,), dtype=bool)
        if pad:
            row_mask[self.width:] = False
            for k, v in list(batch.items()):
                v = np.concatenate(
                    [v, np.zeros((pad,), dtype=v.dtype)]
                )
                if k.endswith("::mask"):
                    v = v & row_mask
                batch[k] = v
        batch[ROW_MASK] = row_mask
        self._delta_raw = delta_raw
        return batch

    def commit(
        self, decoded: Dict[str, np.ndarray]
    ) -> Dict[str, np.ndarray]:
        st = self._state
        for c, (col, uniques) in (self._delta_raw or {}).items():
            acc = st.accs[c]
            acc.absorb(uniques)
            codes = acc.encode(col)
            pad = st.batch_size - self.width
            if pad:
                codes = np.concatenate(
                    [codes, np.zeros((pad,), dtype=codes.dtype)]
                )
            decoded[f"{c}::codes"] = np.ascontiguousarray(codes)
            if acc.n > st.shipped_n[c]:
                decoded[DICT_DELTA_PREFIX + c] = {
                    "start": st.shipped_n[c],
                    "values": acc.slice_values(st.shipped_n[c]),
                }
                st.shipped_n[c] = acc.n
        # drop the Arrow references: once committed the batch is pure
        # numpy and the column buffers can be reclaimed
        self._delta_raw = None
        self._chunks = None
        if self.final and st.start_batch == 0:
            ds = st.dataset
            for c in st.delta_cols:
                if (
                    c not in ds._dictionaries
                    and st.accs[c].values is not None
                ):
                    ds._store_dictionary(c, st.accs[c].values)
        return decoded


class _PrecomputedIngestItem:
    """Degenerate-path item (no requested columns, or an empty
    source): the batch is already built on the reader; decode/commit
    are identity."""

    __slots__ = ("index", "width", "final", "_batch")
    complete = True

    def __init__(self, index, batch):
        self.index = index
        self.width = None
        self.final = False
        self._batch = batch

    def decode(self):
        return self._batch

    def commit(self, decoded):
        return decoded
