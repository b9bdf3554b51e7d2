"""The hashed-string lane: per-row device representations of a string
column too large for a dictionary.

A string column whose first ``LANE_PROBE_ROWS`` rows already hold more
distinct values than ``config.dict_delta_capacity`` never builds a
dictionary (``Dataset.string_lane``). Each batch of it is converted on
the ingest worker, in vectorized form over the Arrow offsets and data
buffers, into the representations its analyzers read:

- ``hash``: one u64 per row (:func:`hash64`). HLL takes its two 32-bit
  halves as the register hash pair; the exact grouping sorts it on the
  device and settles equal hashes of unequal strings on the host
  (analyzers/spill.py), so the distinct counts stay exact;
- ``match:<hex pattern>``: one bool per row, the regex found in the
  value (PatternMatch, LIKE);
- ``in:<hex json list>``: one bool per row, the value in the list
  (``IN``, ``=``);
- ``dtype``: one i32 per row, the DataType bucket of the value;
- ``lengths`` (data/table.py): the utf8 length per row.

The same functions build the per-entry lookup tables of a dictionary
column, so a string gets the same hash, match and bucket on every path
(resident, streamed, mesh, dictionary or lane) and HLL states from
either path merge. Nothing here is imported for a table without
strings.
"""

from __future__ import annotations

import json
import re
from typing import Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

# the rows a lane decision reads: a column whose first LANE_PROBE_ROWS
# rows hold more distinct values than dict_delta_capacity is a lane;
# they are read PROBE_CHUNK_ROWS at a time, to stop early on a lane
LANE_PROBE_ROWS = 1 << 20
PROBE_CHUNK_ROWS = 1 << 17

from deequ_tpu.data.table import LANE_DTYPE as DTYPE
from deequ_tpu.data.table import LANE_HASH as HASH
from deequ_tpu.data.table import LANE_PLANES

_MATCH, _IN = LANE_PLANES

_P1 = np.uint64(0x9E3779B185EBCA87)
_P2 = np.uint64(0xC2B2AE3D27D4EB4F)
_P3 = np.uint64(0x165667B19E3779F9)
_M1 = np.uint64(0xFF51AFD7ED558CCD)
_M2 = np.uint64(0xC4CEB9FE1A85EC53)


def _fmix64(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint64(33))
    x = x * _M1
    x = x ^ (x >> np.uint64(33))
    x = x * _M2
    return x ^ (x >> np.uint64(33))


def plain_strings(arr) -> pa.Array:
    """One contiguous string or large_string array (chunks combined, a
    dictionary decoded)."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type):
        arr = arr.cast(arr.type.value_type)
    return arr


def hash64(arr) -> np.ndarray:
    """One u64 per value of a utf8 array: the value's bytes as
    little-endian 8-byte words (the last one zero-filled), mixed in
    order by a multiply-xorshift round per word from a seed of the
    length, then murmur3's 64-bit finalizer. Vectorized over
    the Arrow offsets and data buffers: one numpy pass per 8 bytes of
    the longest value, over the rows still that long. Nulls hash to 0
    (every consumer masks them)."""
    arr = plain_strings(arr)
    n = len(arr)
    if n == 0:
        return np.zeros(0, dtype=np.uint64)
    wide = pa.types.is_large_string(arr.type)
    _, off_buf, data_buf = arr.buffers()[:3]
    offsets = np.frombuffer(
        off_buf, dtype=np.int64 if wide else np.int32,
        count=n + 1, offset=arr.offset * (8 if wide else 4),
    ).astype(np.int64)
    starts = offsets[:-1]
    lengths = offsets[1:] - starts
    data = np.frombuffer(data_buf, dtype=np.uint8) if data_buf else (
        np.zeros(0, dtype=np.uint8))
    # 8 zero bytes past the end, so the last word of every value reads
    # inside the buffer; ``words[i]`` is the little-endian u64 at byte i
    padded = np.zeros(len(data) + 8, dtype=np.uint8)
    padded[: len(data)] = data
    words = np.ndarray(
        (len(data) + 1,), dtype="<u8", buffer=padded, strides=(1,))
    h = lengths.astype(np.uint64) * _P1 ^ _P3
    longest = int(lengths.max())
    full = int(lengths.min()) // 8  # words every value has whole
    rows = None  # None: every row, no gather of the running hashes
    for k in range(0, longest, 8):
        if k // 8 >= full and rows is None:
            rows = np.nonzero(lengths > k)[0]
        elif rows is not None:
            rows = rows[lengths[rows] > k]
        if rows is None:
            w = words[starts + k]
            hr = h
        else:
            rem = lengths[rows] - k
            w = words[starts[rows] + k]
            short = rem < 8
            w[short] &= (np.uint64(1) << (
                rem[short].astype(np.uint64) * np.uint64(8))) - np.uint64(1)
            hr = h[rows]
        # one round: h = (h ^ w * P2) * P1, then fold the high half down
        np.multiply(w, _P2, out=w)
        np.bitwise_xor(hr, w, out=hr)
        np.multiply(hr, _P1, out=hr)
        np.bitwise_xor(hr, hr >> np.uint64(32), out=hr)
        if rows is not None:
            h[rows] = hr
    h = _fmix64(h)
    if arr.null_count:
        h[~_valid(arr)] = 0
    return h


def hash_pairs(arr):
    """(h1, h2) u32 per value: the low and high words of
    :func:`hash64`, the register index and rank source of HLL."""
    h = hash64(arr)
    return ((h & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            (h >> np.uint64(32)).astype(np.uint32))


def _valid(arr: pa.Array) -> np.ndarray:
    return ~arr.is_null().to_numpy(zero_copy_only=False)


def _bools(result) -> np.ndarray:
    """An Arrow boolean result as numpy, null -> False."""
    return np.ascontiguousarray(
        pc.fill_null(result, False).to_numpy(zero_copy_only=False),
        dtype=bool)


# pattern syntax that RE2 and Python's ``re`` both accept but read
# differently: a POSIX class (``[[:alpha:]]``, a plain set in ``re``),
# an open lower bound (``a{,3}``, literal in RE2) and ``\1``..``\9``
# (octal in RE2, a backreference in ``re``)
_RE2_READS_OTHERWISE = re.compile(r"\[:|\{,|\\[1-9]")
# the characters ``re``'s ``\s`` takes and RE2's does not
_RE_ONLY_SPACE = r"[\x0b\x1c-\x1f]"


def regex_planes(arr, patterns: Sequence[str]) -> list:
    r"""One bool plane per pattern: True where Python's ``re.search``
    finds the pattern in the value (null -> False), the semantics of
    every regex in the library (PatternMatch, LIKE/RLIKE, DataType,
    row-level results). Arrow's RE2 kernel runs a pattern over the
    values on which the two engines agree, and ``re`` runs the rest,
    value by value: every value for a pattern RE2 refuses or reads
    otherwise (a lookaround, a non-ASCII pattern), else the values
    outside ASCII (``re``'s ``\d``, ``\w``, ``\s``, ``\b`` and case
    folding are Unicode-aware), those ending in a newline where the
    pattern has ``$`` (``re``'s ``$`` also matches before one), and
    those holding ``\v`` or ``\x1c``-``\x1f`` where it has ``\s``
    or ``\S``."""
    arr = plain_strings(arr)
    ascii_ = None
    out = []
    for pattern in patterns:
        prog = re.compile(pattern)  # re's verdict on a bad pattern
        plane = None
        if pattern.isascii() and not _RE2_READS_OTHERWISE.search(pattern):
            try:
                plane = _bools(pc.match_substring_regex(arr, pattern))
            except pa.ArrowInvalid:
                pass
        if plane is None:
            plane = np.zeros(len(arr), dtype=bool)
            odd = _valid(arr)
        else:
            if ascii_ is None:
                ascii_ = _bools(pc.string_is_ascii(arr))
            odd = ~ascii_
            if "$" in pattern:
                odd |= _bools(pc.ends_with(arr, "\n"))
            if "\\s" in pattern or "\\S" in pattern:
                odd |= _bools(pc.match_substring_regex(arr, _RE_ONLY_SPACE))
        rows = np.flatnonzero(odd)
        if len(rows):
            values = arr.take(pa.array(rows)).to_pylist()
            plane[rows] = [
                v is not None and prog.search(v) is not None for v in values
            ]
        out.append(plane)
    return out


def regex_plane(arr, pattern: str) -> np.ndarray:
    """The plane of one pattern (:func:`regex_planes`)."""
    return regex_planes(arr, [pattern])[0]


def isin_plane(arr, values: Sequence[str]) -> np.ndarray:
    """True where the value is one of ``values`` (null -> False)."""
    arr = plain_strings(arr)
    return _bools(pc.is_in(arr, value_set=pa.array(
        list(values), type=arr.type)))


def classify(arr) -> np.ndarray:
    """DataType bucket per value (analyzers/states.DataTypeHistogram:
    null 0, fractional 1, integral 2, boolean 3, string 4), i32, by the
    patterns of analyzers/datatype.classify_string."""
    from deequ_tpu.analyzers.datatype import (
        _BOOLEAN_RE,
        _FRACTIONAL_RE,
        _INTEGRAL_RE,
    )

    arr = plain_strings(arr)
    fractional, integral, boolean = regex_planes(arr, [
        _FRACTIONAL_RE.pattern, _INTEGRAL_RE.pattern,
        "(?i)" + _BOOLEAN_RE.pattern,
    ])
    out = np.full(len(arr), 4, dtype=np.int32)
    out[fractional] = 1
    out[integral] = 2
    out[boolean] = 3
    if arr.null_count:
        out[~_valid(arr)] = 0
    return out


# -- lane representations ----------------------------------------------


def match_repr(pattern: str) -> str:
    return _MATCH + pattern.encode("utf-8").hex()


def in_repr(values: Sequence[str]) -> str:
    return _IN + json.dumps(sorted(set(values))).encode("utf-8").hex()


def lane_repr_of(col, repr_name: str) -> np.ndarray:
    """One batch (or the whole column) of a lane column converted to
    ``repr_name``, as the span ``ingest:strings`` on this thread."""
    from deequ_tpu.telemetry import get_telemetry

    tm = get_telemetry()
    col = plain_strings(col)
    values = len(col) - col.null_count
    with tm.span("ingest:strings", columns=1, rows=len(col),
                 values=values):
        if repr_name == HASH:
            tm.counter("strings.lane_values").inc(values)
            return hash64(col)
        if repr_name == DTYPE:
            return classify(col)
        payload = bytes.fromhex(repr_name.split(":", 1)[1]).decode("utf-8")
        if repr_name.startswith(_MATCH):
            return regex_plane(col, payload)
        return isin_plane(col, json.loads(payload))


def lane_probe(chunks, capacity: int) -> bool:
    """True when the probe rows, given as consecutive ``chunks`` (at
    most ``LANE_PROBE_ROWS`` rows in all), hold more distinct non-null
    values than ``capacity``: the column becomes a lane. Stops reading
    once the count is past ``capacity``."""
    seen = None
    for chunk in chunks:
        fresh = pc.unique(plain_strings(chunk).cast(pa.large_string()))
        seen = fresh if seen is None else pc.unique(
            pa.concat_arrays([seen, fresh]))
        if len(seen) - seen.null_count > capacity:
            return True
    return False


def dictionary_values(dictionary) -> pa.Array:
    """A host dictionary (numpy object array or Arrow array) as one
    utf8 Arrow array, for the vectorized lookup-table builders; entries
    that are not strings count by their ``str()``."""
    if isinstance(dictionary, (pa.Array, pa.ChunkedArray)):
        arr = plain_strings(dictionary)
        if pa.types.is_string(arr.type) or pa.types.is_large_string(
                arr.type):
            return arr
        dictionary = arr.to_pylist()
    try:
        return pa.array(np.asarray(dictionary, dtype=object),
                        type=pa.large_string())
    except (pa.ArrowInvalid, pa.ArrowTypeError):
        return pa.array([None if v is None else str(v) for v in dictionary],
                        type=pa.large_string())


def dictionary_matches(dictionary, pattern: str) -> np.ndarray:
    """Per dictionary entry, the pattern found in it
    (:func:`regex_plane`): the lookup table of PatternMatch on every
    path that reads dictionary codes."""
    if not len(dictionary):
        re.compile(pattern)  # a bad pattern fails on every path
        return np.zeros(0, dtype=bool)
    return regex_plane(dictionary_values(dictionary), pattern)
