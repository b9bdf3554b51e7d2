"""The program's span records over the window, for the readers of the
``program_span`` metrics that read spans.

A harness that collects them hands them over as ``obs.spans``. Where it
does not, they come from the program's own in-memory ring of recent
records (``Telemetry.recent()``), read once the window has closed: the
spans that started inside the window's runs, on every thread. The ring
is bounded; where it has let go of the window's first runs, only the
runs it still holds whole are read, and the readers divide by those.
"""

from __future__ import annotations

import time
from typing import List, Optional, Tuple

# a span that opens this close before a run's start still belongs to it
# (span start times are rounded to the microsecond)
TOLERANCE_S = 1e-3


def window(obs) -> Optional[Tuple[List[dict], list]]:
    """(span records, the runs they cover), or None where there are no
    completed runs or no spans."""
    runs = [r for r in obs.runs if r.ok]
    if not runs:
        return None
    spans = getattr(obs, "spans", None)
    if spans is None:
        spans, runs = _from_ring(runs)
    if not spans or not runs:
        return None
    return spans, runs


def _from_ring(runs) -> Tuple[List[dict], list]:
    from deequ_tpu.telemetry import get_telemetry

    records = [r for r in get_telemetry().recent()
               if r.get("type") == "span" and "started_at" in r]
    if not records:
        return [], []
    # runs are timed on perf_counter, spans start on the epoch clock
    offset = time.time() - time.perf_counter()
    # the ring lets go of records in the order they finished: a run
    # that started after the earliest finish still held lost nothing
    held_from = min(r["started_at"] + r["wall_s"] for r in records)
    covered = [r for r in runs if r.start + offset >= held_from]
    if not covered:
        return [], []
    lo = covered[0].start + offset - TOLERANCE_S
    hi = covered[-1].end + offset
    return [r for r in records if lo <= r["started_at"] <= hi], covered


def outermost(spans: List[dict], within) -> List[dict]:
    """The spans that ``within(span)`` selects and that no selected
    span encloses (so nested stages are not counted twice)."""
    index = {s["span_id"]: s for s in spans}
    out = []
    for s in spans:
        if not within(s):
            continue
        parent = index.get(s.get("parent_id"))
        while parent is not None and not within(parent):
            parent = index.get(parent.get("parent_id"))
        if parent is None:
            out.append(s)
    return out


def self_s(span: dict, spans: List[dict]) -> float:
    """A span's wall less the walls of its children."""
    children = sum(s["wall_s"] for s in spans
                   if s.get("parent_id") == span["span_id"])
    return max(0.0, span["wall_s"] - children)
