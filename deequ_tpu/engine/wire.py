"""Per-column wire codecs for the streamed packed wire.

The streamed path ships every batch host->device, so every byte NOT
shipped is transfer time recovered (docs/PERF.md "Wire diet"; how
much that moves the wall on the chip is not measured yet). This module decides, ONCE
per run, a per-column *wire* dtype narrower than the canonical batch
dtype wherever the data provably allows it:

- int64/int32/int16 values -> the narrowest signed int covering the
  column's range, from parquet row-group statistics
  (``dataset.integral_range``, free — no data scan) when available,
  else from a first-batch probe;
- float64 values -> float32 when a first-batch probe shows every value
  round-trips BIT-exactly (checked on integer views, so NaN payloads
  and signed zeros count); lossy columns stay f64;
- dictionary codes and utf8 lengths -> first-batch probe (their
  canonical dtypes are already range-shaped, but delta-mode codes ship
  canonical i32 and probe down to i8/i16 on the wire).

The decode back to the canonical dtype is folded into the fused
``wire_unpack`` (engine/scan.py), so device programs see canonical
dtypes bit-identically and plan fingerprints stay data-independent.

The decision is per RUN, never per batch — the fixed-layout
no-recompile contract documented on ``narrow_int64_values``. Batches
that violate a resolved codec (stats lied, a dictionary grew past the
probed width) raise :class:`CodecViolation` on the prefetch thread;
the pack loop widens the table (``CodecTable.widen`` — a version bump
the consumer answers by rebuilding the wire + fused jit under a new
plan key) and re-packs the SAME batch, so a violation costs one
retrace, never a wrong metric or a quarantine.

Every non-identity codec is guarded on EVERY batch (vectorized
min/max or a bitwise round-trip compare, on the prefetch thread where
it overlaps device compute): parquet statistics are trusted for the
decision but verified against the data, because a corrupt file's
stats are exactly as corrupt as its values.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = [
    "CodecViolation",
    "ColumnCodec",
    "CodecTable",
    "narrowest_int_dtype",
    "resolve_codecs",
]

_SIGNED_STEPS = (np.dtype(np.int8), np.dtype(np.int16),
                 np.dtype(np.int32), np.dtype(np.int64))


def narrowest_int_dtype(lo: int, hi: int) -> np.dtype:
    """Narrowest SIGNED integer dtype covering [lo, hi] — the one
    range->width rule, shared by the stats decision and the probe."""
    for dt in _SIGNED_STEPS:
        info = np.iinfo(dt)
        if lo >= info.min and hi <= info.max:
            return dt
    return np.dtype(np.int64)


class CodecViolation(Exception):
    """A batch's values do not fit the resolved wire dtype. Raised on
    the prefetch thread by :meth:`CodecTable.encode`; the pack loop
    answers with :meth:`CodecTable.widen` + a re-pack — never an
    iterator restart, never a quarantine (the data is FINE, the
    narrowing bet lost)."""

    def __init__(self, key: str, required: np.dtype):
        super().__init__(
            f"wire codec for {key!r} violated: batch requires "
            f"{np.dtype(required).name}"
        )
        self.key = key
        self.required = np.dtype(required)


@dataclass
class ColumnCodec:
    """One wire-key's codec: ``canonical`` is what the device program
    sees (decode target), ``wire`` what ships. ``wire is None`` means
    the decision is deferred to the first-batch probe; ``origin``
    records how the width was chosen ("stats" | "probe")."""

    key: str
    canonical: np.dtype
    wire: Optional[np.dtype]
    origin: str

    @property
    def active(self) -> bool:
        return self.wire is not None and self.wire != self.canonical


@dataclass
class CodecTable:
    """The run's resolved codec set, versioned: ``widen`` bumps
    ``version``, which invalidates wires/jits built against the old
    widths (the streaming loop keys its plan-cache entry and its
    sub-batch wires on ``token()``, which embeds the version)."""

    codecs: Dict[str, ColumnCodec] = field(default_factory=dict)
    version: int = 0
    _lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False
    )

    def codec(self, key: str) -> Optional[ColumnCodec]:
        return self.codecs.get(key)

    def token(self) -> tuple:
        """Hashable fingerprint of the resolved table — appended to the
        streaming plan-cache key so a program traced against one codec
        set is never served to another (the plankey analyzer counts on
        this for ``config.wire_codecs`` coverage). Codecs-off runs
        produce the empty table's token, a distinct key."""
        return (
            self.version,
            tuple(
                (k, c.canonical.name,
                 None if c.wire is None else c.wire.name)
                for k, c in sorted(self.codecs.items())
            ),
        )

    def encode(self, key: str, values: np.ndarray) -> np.ndarray:
        """Encode one leaf for the wire (identity when no codec).
        Resolves a deferred probe on first sight; guards every resolved
        non-identity codec and raises :class:`CodecViolation` when the
        batch does not fit."""
        codec = self.codecs.get(key)
        if codec is None:
            return values
        wire = codec.wire
        if wire is None:
            wire = self._resolve_probe(codec, values)
        if wire == codec.canonical:
            return values
        if wire.kind == "i":
            if values.size:
                lo = int(values.min())
                hi = int(values.max())
                info = np.iinfo(wire)
                if lo < info.min or hi > info.max:
                    raise CodecViolation(
                        key, narrowest_int_dtype(lo, hi)
                    )
            return values.astype(wire)
        # float32 wire for a float64 canonical: ship only when every
        # value round-trips bit-exactly (integer views, so NaN
        # payloads/signed zeros are compared literally, not by ==)
        enc = values.astype(wire)
        if not np.array_equal(
            enc.astype(codec.canonical).view(np.int64),
            values.view(np.int64),
        ):
            raise CodecViolation(key, codec.canonical)
        return enc

    def _resolve_probe(
        self, codec: ColumnCodec, values: np.ndarray
    ) -> np.dtype:
        """First-batch probe: pick the wire dtype from the actual
        values (later batches are guarded; a violation widens)."""
        if codec.canonical.kind == "i":
            if values.size:
                wire = narrowest_int_dtype(
                    int(values.min()), int(values.max())
                )
            else:
                wire = np.dtype(np.int8)
            if wire.itemsize >= codec.canonical.itemsize:
                wire = codec.canonical
        else:
            enc = values.astype(np.float32)
            wire = (
                np.dtype(np.float32)
                if np.array_equal(
                    enc.astype(np.float64).view(np.int64),
                    values.view(np.int64),
                )
                else codec.canonical
            )
        with self._lock:
            if codec.wire is None:
                codec.wire = wire
                # resolution completes the table, it does not invalidate
                # anything built before the first batch — no version bump
        return codec.wire

    def widen(self, key: str, required: np.dtype) -> None:
        """A resolved codec's bet lost: widen its wire dtype to cover
        ``required`` (and everything the old width already carried),
        bump the version so wires/jits rebuild, and record the event —
        the fallback leg of the stats-based narrowing satellite."""
        from deequ_tpu.telemetry import get_telemetry

        with self._lock:
            codec = self.codecs[key]
            old = codec.wire
            new = np.dtype(required)
            if old is not None and old.kind == "i" and new.kind == "i":
                new = np.promote_types(old, new)
            if new.itemsize >= codec.canonical.itemsize:
                new = codec.canonical
            codec.wire = new
            self.version += 1
        get_telemetry().event(
            "wire_codec_widened",
            key=key,
            wire_from=None if old is None else old.name,
            wire_to=new.name,
            origin=codec.origin,
        )

    def raw_bytes_of(self, key: str, encoded: np.ndarray) -> int:
        """What this leaf would have cost at canonical width — the
        codecs-off wire's bytes, for the wire-diet counters."""
        codec = self.codecs.get(key)
        if codec is None or codec.wire is None:
            return encoded.nbytes
        return encoded.size * codec.canonical.itemsize


def resolve_codecs(dataset, requests, enabled: bool) -> CodecTable:
    """Decide the run's codec table from static metadata — parquet
    row-group statistics where present, deferred first-batch probes
    elsewhere. Touches NO data values. Disabled (or non-candidate
    columns): an empty/identity table, byte-identical to today's wire."""
    table = CodecTable()
    if not enabled:
        return table
    seen = set()
    for req in requests:
        key = req.key
        if key in seen or req.repr in ("mask", "u64bits"):
            continue
        seen.add(key)
        try:
            canonical = np.dtype(dataset.request_dtype(req))
        except Exception:  # noqa: BLE001 — unknown repr: no codec
            continue
        if canonical.kind == "i" and canonical.itemsize > 1:
            wire: Optional[np.dtype] = None
            origin = "probe"
            if req.repr == "values":
                rng = None
                probe = getattr(dataset, "integral_range", None)
                if probe is not None:
                    try:
                        rng = probe(req.column)
                    except Exception:  # noqa: BLE001 — stats optional
                        rng = None
                if rng is not None:
                    # lint-ok: wire-discipline: loop is over column
                    # REQUESTS at plan time — one decision per run
                    wire = narrowest_int_dtype(int(rng[0]), int(rng[1]))
                    origin = "stats"
                    if wire.itemsize >= canonical.itemsize:
                        continue  # stats prove no narrowing: no codec
            table.codecs[key] = ColumnCodec(key, canonical, wire, origin)
        elif canonical == np.float64 and req.repr == "values":
            table.codecs[key] = ColumnCodec(key, canonical, None, "probe")
    return table
