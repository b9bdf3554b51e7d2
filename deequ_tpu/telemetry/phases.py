"""PhaseClock: wall-time decomposition buckets for the scan paths.

Lives in the telemetry layer so the engine's hot loop contains no clock
calls of its own (tools/telemetry_lint.py enforces that split — all
attribution comes from ONE place and stays comparable across PRs).

Buckets: ``host_wait_s`` — blocked pulling the staging generator
(source read/convert; on the resident path this also covers the
device_put DISPATCH of chunk staging); ``put_s`` — transfer dispatch
incl. link backpressure; ``dispatch_s`` — jitted step dispatch (the
FIRST step's trace+compile is split out as ``first_step_s`` so a cold
run doesn't read as dispatch overhead); ``sync_s`` — blocked on the
device queue draining.

With telemetry on, every timed interval is a live span on the scan
thread (``scan:wait``, ``scan:put``, ``scan:dispatch``,
``scan:first_step``, ``scan:sync``) and the bucket adds that span's
wall, so the ``scan_phases`` event and the spans are the same
intervals (the first step's span also carries ``retraces`` and
``hll_path``). With telemetry off the clock times on its own.

Attribution caveat (measured, docs/PERF.md): when the host->device link
saturates, backpressure and GIL contention smear waiting across
buckets — the SUM (~= wall) and bytes_shipped/wall are the robust
signals; individual buckets are indicative.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict, Iterator, Optional

from deequ_tpu.telemetry.spans import clock

SPAN_OF_BUCKET = {
    "host_wait_s": "scan:wait",
    "put_s": "scan:put",
    "dispatch_s": "scan:dispatch",
    "first_step_s": "scan:first_step",
    "sync_s": "scan:sync",
}


class PhaseClock:
    def __init__(self, mode: str):
        from deequ_tpu.telemetry.runtime import get_telemetry

        self.times: Dict[str, object] = {
            "host_wait_s": 0.0, "put_s": 0.0, "dispatch_s": 0.0,
            "first_step_s": 0.0, "sync_s": 0.0, "mode": mode,
        }
        self._steps = 0
        self._tm = get_telemetry()
        # the scan's lifetime trace count, read around the first step so
        # its span says whether it traced (and compiled or loaded) anew
        self.trace_counter: Optional[Callable[[], int]] = None
        # further attributes of the first step's span: what its plan
        # baked in (the engine sets ``hll_path``)
        self.first_step_attrs: Dict[str, object] = {}

    def timed_iter(self, iterator) -> Iterator:
        """Yield from ``iterator``, accumulating time blocked in its
        ``__next__`` into host_wait_s (keeps the caller a for-loop)."""
        it = iter(iterator)
        while True:
            with self.phase("host_wait_s"):
                item = next(it, _DONE)
            if item is _DONE:
                return
            yield item

    @contextlib.contextmanager
    def phase(self, key: str) -> Iterator[None]:
        if key == "dispatch_s":
            self._steps += 1
            if self._steps == 1:
                key = "first_step_s"
        if not self._tm.enabled:
            t0 = clock()
            try:
                yield
            finally:
                self.times[key] += clock() - t0
            return
        counter = self.trace_counter if key == "first_step_s" else None
        before = counter() if counter is not None else 0
        sp = None
        try:
            with self._tm.span(SPAN_OF_BUCKET[key]) as sp:
                try:
                    yield
                finally:
                    if key == "first_step_s":
                        sp.set(**self.first_step_attrs)
                    if counter is not None:
                        sp.set(retraces=counter() - before)
        finally:
            if sp is not None:
                self.times[key] += sp.wall_s


_DONE = object()
