"""Memory-pressure classification and adaptive batch backoff.

The reference delegates out-of-memory survival to Spark's executor
re-scheduling (a task that OOMs is simply retried elsewhere); the
jax_graft engine drives its own scan loop on a fixed device, so this
module supplies the equivalent story (docs/RESILIENCE.md "Memory
pressure"):

- :func:`classify_memory_pressure` — the ONE place device allocation
  failures (XLA ``RESOURCE_EXHAUSTED`` / ``XlaRuntimeError`` OOM
  shapes) and host ``MemoryError`` are recognized and mapped onto
  :class:`MemoryPressureError`. Everything else in the engine matches
  against this classifier, never against exception strings — enforced
  by ``tools/telemetry_lint.py``.
- :class:`MemoryPressureError` — its own family, deliberately DISTINCT
  from the transient/deterministic taxonomy in ``engine/resilience.py``:
  retrying the same allocation at the same size re-OOMs (so it is not
  transient), but shrinking the allocation usually succeeds (so it is
  not a quarantine-worthy deterministic failure either). The scan loops
  answer it with :class:`AdaptiveBatchBackoff`; only an allocation that
  still fails at ``config.min_batch_rows`` flows into PR 3's
  quarantine -> ``ScanDegradation``.
- :class:`AdaptiveBatchBackoff` — the effective-batch-size state
  machine: geometric halving down to ``min_rows`` on OOM, optional
  heal-up (doubling) after ``heal_after`` consecutive clean batches.
  Observable via the ``engine.batch_rows_effective`` gauge and the
  ``engine.oom_events`` / ``engine.batch_size_backoffs`` counters plus
  ``scan_memory_pressure`` events (rendered by ``tools/obs_report.py``).
- :class:`SimulatedResourceExhausted` + :func:`simulated_device_oom` —
  the fault-injection surface (``testing/faults.py``): a synthetic
  exception carrying a real XLA-shaped ``RESOURCE_EXHAUSTED`` message,
  so tests exercise the same message-matching classification path a
  live device failure would take, with zero real allocation pressure.

Classification is intentionally conservative: message markers are only
consulted for exception types that plausibly come from the runtime
(``XlaRuntimeError``, ``RuntimeError``, the simulated stand-in) — a
``ValueError`` that merely MENTIONS memory never classifies.
"""

from __future__ import annotations

from typing import Any, Optional

from deequ_tpu.telemetry import get_telemetry


class MemoryPressureError(Exception):
    """A device or host allocation failure, classified. ``origin`` is
    ``"device"`` (XLA allocator) or ``"host"`` (Python ``MemoryError``).
    NOT transient (same-size retry re-OOMs) and not deterministic data
    corruption either — the scan loops shrink the batch instead."""

    def __init__(self, message: str, origin: str = "device"):
        super().__init__(message)
        self.origin = origin


class BackoffExhausted(MemoryPressureError):
    """Allocation still failed at ``min_rows`` — nothing left to
    shrink. The scan quarantines the remaining rows of the unit
    (PR 3's quarantine -> ScanDegradation path)."""


class SimulatedResourceExhausted(Exception):
    """Test-only stand-in for ``jaxlib``'s ``XlaRuntimeError`` OOM:
    same message shape, no real allocation. Raised by the fault
    harness (``testing/faults.py``) so classification is exercised
    end-to-end on CPU."""


def simulated_device_oom(rows: int = 0, where: str = "dispatch"):
    """An exception shaped like a real XLA device OOM (classified by
    message, exactly like the live error would be)."""
    return SimulatedResourceExhausted(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        f"{max(int(rows), 1) * 8} bytes (injected at {where})"
    )


# message markers a runtime allocation failure carries; matched ONLY
# for the runtime exception types below
_OOM_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "Out of memory",
    "out of memory",
    "Resource exhausted",
    "Failed to allocate",
)

# exception type NAMES eligible for message matching — jaxlib's
# XlaRuntimeError is matched by name so this module never imports
# jaxlib internals (and keeps working across jaxlib versions)
_RUNTIME_TYPE_NAMES = ("XlaRuntimeError", "RuntimeError")


def classify_memory_pressure(
    exc: BaseException,
) -> Optional[MemoryPressureError]:
    """``exc`` as a :class:`MemoryPressureError`, or None when it is
    not an allocation failure. The single classification point — no
    other engine module matches OOM strings (telemetry_lint rule)."""
    if isinstance(exc, MemoryPressureError):
        return exc
    if isinstance(exc, MemoryError):
        pressure = MemoryPressureError(
            f"host allocation failed: {exc}", origin="host"
        )
        pressure.__cause__ = exc
        return pressure
    if isinstance(exc, SimulatedResourceExhausted) or (
        type(exc).__name__ in _RUNTIME_TYPE_NAMES
    ):
        message = str(exc)
        if any(marker in message for marker in _OOM_MARKERS):
            pressure = MemoryPressureError(message, origin="device")
            pressure.__cause__ = exc
            return pressure
    return None


def record_memory_pressure(
    stage: str,
    batch_index: int,
    rows: int,
    pressure: MemoryPressureError,
) -> None:
    """Count + event one classified OOM (``engine.oom_events`` and a
    ``scan_memory_pressure`` event with ``action="oom"``)."""
    tm = get_telemetry()
    tm.counter("engine.oom_events").inc()
    tm.event(
        "scan_memory_pressure",
        action="oom",
        stage=stage,
        batch_index=int(batch_index),
        rows=int(rows),
        origin=pressure.origin,
        error=str(pressure)[:200],
    )


def record_spill_downgrade(stage: str, columns, path: str) -> None:
    """Count + event one memory-pressure downgrade of a spill plan
    (``engine.spill_downgrades``): a collector whose shared scan or
    finalize failed, or a deferred re-scan. The downgrade chain is
    collector -> deferred per-plan re-scan -> host Arrow, or collector
    -> host Arrow for a collector with no resident cache to re-scan."""
    tm = get_telemetry()
    tm.counter("engine.spill_downgrades").inc()
    tm.event(
        "scan_memory_pressure",
        action="spill-downgrade",
        stage=stage,
        columns=list(columns),
        path=path,
    )


class AdaptiveBatchBackoff:
    """Effective-batch-size state machine for one scan.

    Starts at ``full`` (the scan's nominal batch size — which stays the
    checkpoint identity; backoff is internal to a dispatch). ``shrink``
    halves geometrically down to ``min_rows``; ``note_clean`` heals
    back up (doubling) after ``heal_after`` consecutive clean units,
    0/negative disables healing. ``align`` keeps sizes a multiple of
    the mesh's dp extent so sharded puts stay legal.

    Zero-cost default: until the first OOM, the scan's only extra work
    is one ``effective == full`` comparison per batch — no threads, no
    telemetry, no allocation.
    """

    __slots__ = ("full", "min_rows", "heal_after", "align",
                 "effective", "_clean")

    def __init__(
        self,
        full_rows: int,
        min_rows: int,
        heal_after: int = 0,
        align: int = 1,
    ):
        self.full = max(1, int(full_rows))
        self.align = max(1, int(align))
        self.min_rows = min(
            self.full, max(self.align, int(min_rows))
        )
        self.heal_after = int(heal_after)
        self.effective = self.full
        self._clean = 0

    @property
    def active(self) -> bool:
        return self.effective < self.full

    def _aligned(self, rows: int) -> int:
        return max(
            self.align, (rows // self.align) * self.align
        )

    def shrink(self, stage: str, batch_index: int) -> bool:
        """Halve the effective size after an OOM. Returns False when
        already at the floor (backoff exhausted: the caller
        quarantines)."""
        if self.effective <= self.min_rows:
            get_telemetry().event(
                "scan_memory_pressure",
                action="exhausted",
                stage=stage,
                batch_index=int(batch_index),
                effective_rows=int(self.effective),
            )
            return False
        previous = self.effective
        self.effective = max(
            self.min_rows, self._aligned(self.effective // 2)
        )
        self._clean = 0
        tm = get_telemetry()
        tm.counter("engine.batch_size_backoffs").inc()
        tm.metrics.gauge("engine.batch_rows_effective").set(
            self.effective
        )
        tm.event(
            "scan_memory_pressure",
            action="backoff",
            stage=stage,
            batch_index=int(batch_index),
            from_rows=int(previous),
            effective_rows=int(self.effective),
        )
        return True

    def note_clean(self) -> bool:
        """One unit completed without an OOM; heal (double) after
        ``heal_after`` consecutive clean units. Returns True when a
        heal happened."""
        if self.effective >= self.full or self.heal_after <= 0:
            return False
        self._clean += 1
        if self._clean < self.heal_after:
            return False
        self._clean = 0
        previous = self.effective
        self.effective = min(self.full, self._aligned(previous * 2))
        tm = get_telemetry()
        tm.metrics.gauge("engine.batch_rows_effective").set(
            self.effective
        )
        tm.event(
            "scan_memory_pressure",
            action="heal",
            from_rows=int(previous),
            effective_rows=int(self.effective),
        )
        return True


def make_backoff(
    batch_size: int, align: int = 1
) -> Optional[AdaptiveBatchBackoff]:
    """The configured backoff controller for one scan, or None when
    ``config.memory_backoff`` is off (dispatch failures then propagate
    exactly as before this layer existed)."""
    from deequ_tpu import config

    opts = config.options()
    if not opts.memory_backoff:
        return None
    return AdaptiveBatchBackoff(
        batch_size,
        opts.min_batch_rows,
        heal_after=opts.memory_heal_after_batches,
        align=align,
    )


def oom_probe_of(dataset: Any):
    """The dataset's fault-injection probe (``testing/faults.py``
    attaches one; real datasets have none). The engine calls
    ``probe(stage, index, rows)`` inside the guarded dispatch/transfer
    stages so an injected OOM rides the exact classification path a
    live one would."""
    return getattr(dataset, "oom_probe", None)
