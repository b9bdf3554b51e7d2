"""Ordered parallel host ingest: the decode/encode worker pool.

r9 made the streamed path bytes-bound on *encoded* bytes; this module
makes it CPU-parallel on the host side. The single prefetch worker
(engine/scan._prefetched) serializes Arrow decode, host pack and
wire-codec encode on one thread — on a multi-core host the wire diet
cannot cash out into rows/s. :func:`ordered_ingest` replaces it with a
bounded ordered pool:

- a READER thread walks the order-defining source iterator (cheap:
  Arrow-level slicing; parquet decompression is already parallel
  inside the pyarrow scanner) and enqueues ``(seq, item)`` work onto a
  bounded queue;
- N WORKER threads independently run the heavy ``work(item)`` stage
  (numpy conversion, validity/bit packing, wire-codec encode — all
  GIL-releasing);
- the CONSUMER (the generator returned to the scan loop) releases
  results strictly in sequence order, running the optional ``commit``
  stage — the ordered side of the contract (dictionary-delta absorb +
  cut, stale-wire re-pack) — on the scan thread at release time.

Ordering contract: at most ``lookahead`` items are in flight (queued +
working + done-awaiting-release), so host memory stays bounded; errors
raised anywhere (reader, worker, commit) surface on the consumer
thread at EXACTLY their sequence position, after every earlier item
has been yielded — which is what lets ``resilient_batches`` keep
computing the failing index as ``start + items_yielded``. Teardown
stops the reader and workers, releases the armed source-interrupt
event (a reader blocked inside a hung read wakes and exits), drains
the queues, and joins every thread: ``active_ingest_threads()`` (and
therefore ``scan.active_prefetch_workers``) drains to ``[]``.

Supervision: the consumer polls with ``supervisor.poll_s()`` and runs
``on_wait`` on every empty poll / ``note_arrival`` per release — the
same protocol as the single-worker path, so cancel/deadline/stall and
the watchdog attach to the pool unchanged.
"""

from __future__ import annotations

import queue
import threading
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, Iterator, Optional

from deequ_tpu.telemetry import clock, get_telemetry

# Every thread this module (or scan._prefetched) starts registers here;
# tests assert the union is [] after teardown — the leak probe.
_INGEST_THREADS: "weakref.WeakSet" = weakref.WeakSet()


def register_ingest_thread(thread: threading.Thread) -> threading.Thread:
    """Register a host-ingest thread with the leak probe (the
    thread-discipline staticcheck rule requires every Thread in
    deequ_tpu to register here or carry a waiver)."""
    _INGEST_THREADS.add(thread)
    return thread


def active_ingest_threads():
    """Ingest threads (reader + workers + single-path prefetchers)
    still alive — the teardown-joins-everything probe for tests."""
    return [t for t in _INGEST_THREADS if t.is_alive()]


@dataclass
class IngestPoolStats:
    """Per-pool accounting, filled by the pool and (optionally) by the
    caller's work/commit closures; flushed as ONE ``ingest_pool``
    telemetry event on the consumer thread at teardown, so the
    per-stage busy fractions are diagnosable from the JSONL alone
    (tools/obs_report.py "ingest pool" line). The caller's
    ``decode_s``/``encode_s`` are the walls of its ``ingest:decode`` /
    ``ingest:encode`` spans; the pool times the rest on
    ``telemetry.clock()``."""

    workers: int = 0
    released: int = 0
    decode_s: float = 0.0  # worker-side heavy stage (Arrow -> numpy)
    encode_s: float = 0.0  # worker-side pack + wire-codec encode
    commit_s: float = 0.0  # consumer-side ordered stage
    idle_s: float = 0.0  # workers waiting for work
    stall_s: float = 0.0  # consumer waiting on the reassembly head
    wall_s: float = 0.0
    peak_in_flight: int = 0
    peak_in_flight_bytes: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock)

    def add(self, stage: str, seconds: float) -> None:
        with self._lock:
            setattr(self, stage, getattr(self, stage) + seconds)

    def to_event_fields(self) -> Dict[str, Any]:
        return {
            "workers": int(self.workers),
            "released": int(self.released),
            "decode_s": round(self.decode_s, 6),
            "encode_s": round(self.encode_s, 6),
            "commit_s": round(self.commit_s, 6),
            "idle_s": round(self.idle_s, 6),
            "stall_s": round(self.stall_s, 6),
            "wall_s": round(self.wall_s, 6),
            "peak_in_flight": int(self.peak_in_flight),
            "peak_in_flight_bytes": int(self.peak_in_flight_bytes),
        }


def resolve_ingest_workers(configured: int) -> int:
    """``config.ingest_workers`` -> an actual worker count: 0 = auto
    (min(4, cpu)); never below 1."""
    if configured and configured > 0:
        return int(configured)
    import os

    return max(1, min(4, os.cpu_count() or 1))


def resolve_ingest_lookahead(configured: int, workers: int) -> int:
    """``config.ingest_lookahead`` -> in-flight bound: 0 = auto
    (2 * workers); never below workers (a tighter bound would idle
    workers by construction)."""
    if configured and configured > 0:
        return max(int(configured), workers)
    return 2 * workers


def process_sharded_feed(dataset, batch_size: int):
    """Prepare a dataset for the process-sharded global-array feed
    (``jax.make_array_from_process_local_data``): each process reads
    only its own row-group shard and contributes ``batch_size /
    process_count`` local rows per global batch.

    Returns ``(dataset, local_rows)``. Single-process (or a dataset
    without a ``shard_view`` planner) is the identity — the feed is
    still routed through ``make_array_from_process_local_data``, which
    with one process is semantically ``device_put(v, sharding)``; the
    multi-process leg swaps in the shard view and exchanges batch
    counts up front so every process runs the SAME number of
    collective puts (a short host pads with empty all-masked batches —
    the r5 uniform-exchange discipline: divergence raises everywhere
    instead of hanging the fleet in a collective).
    """
    import jax

    pc = jax.process_count()
    if pc <= 1 or not hasattr(dataset, "shard_view"):
        return dataset, int(batch_size)
    if batch_size % pc:
        raise ValueError(
            f"process-sharded ingest needs batch_size divisible by "
            f"process_count ({batch_size} % {pc} != 0)"
        )
    local_rows = batch_size // pc
    local = dataset.shard_view(jax.process_index(), pc)

    from jax.experimental import multihost_utils

    import numpy as np

    # the uniform exchange: every process learns every shard's batch
    # count BEFORE the first collective put, so imbalance pads instead
    # of hanging, and a zero-row shard fails loudly on EVERY host
    n_local = int(local.num_rows)
    # lint-ok: sync-discipline: host-side numpy over the allgather
    # payload — row counts, not device buffers; no readback happens
    counts = np.asarray(
        multihost_utils.process_allgather(
            # lint-ok: sync-discipline: builds the host payload
            np.asarray([n_local], dtype=np.int64)
        )
    ).reshape(-1)
    total_batches = int(
        max((int(c) + local_rows - 1) // local_rows for c in counts)
    )
    if total_batches > 0 and int(counts.min()) == 0:
        raise ValueError(
            "process-sharded ingest: a process was assigned zero rows "
            f"(shard row counts {counts.tolist()}) — shard planner "
            "cannot seed that host's batch structure; use fewer "
            "processes or a larger source"
        )
    return (
        _PaddedLocalFeed(local, local_rows, total_batches, counts),
        local_rows,
    )


class _PaddedLocalFeed:
    """Multi-process feed adapter: translates the engine's GLOBAL
    batch width to this process's local width and pads the tail so
    every process yields exactly ``total_batches`` batches (trailing
    pads are all-masked copies of the last real batch's structure).
    ``num_rows`` reports the GLOBAL total so engine row accounting
    stays cluster-wide. Does NOT declare ``supports_parallel_ingest``:
    the ordered pool re-engages per-host in a later revision."""

    def __init__(self, local, local_rows, total_batches, counts):
        self._local = local
        self._local_rows = int(local_rows)
        self._total_batches = int(total_batches)
        self._global_rows = int(sum(int(c) for c in counts))

    @property
    def num_rows(self) -> int:
        return self._global_rows

    def fingerprint(self):
        return self._local.fingerprint()

    def __getattr__(self, name):
        return getattr(self._local, name)

    def device_batches(self, requests, batch_size, start_batch=0):
        import numpy as np

        from deequ_tpu.data.table import ROW_MASK

        produced = start_batch
        template = None
        src = (
            self._local.device_batches(
                requests, self._local_rows, start_batch=start_batch
            )
            if start_batch
            else self._local.device_batches(requests, self._local_rows)
        )
        for batch in src:
            template = batch
            produced += 1
            yield batch
        while produced < self._total_batches:
            if template is None:
                raise ValueError(
                    "process-sharded ingest: cannot pad a shard that "
                    "yielded no batches"
                )
            from deequ_tpu.data.table import DICT_DELTA_PREFIX

            pad = {
                # lint-ok: sync-discipline: template batches are host
                # numpy (pre-put); zeroing them never touches a device
                k: np.zeros_like(np.asarray(v))
                for k, v in template.items()
                if not k.startswith(DICT_DELTA_PREFIX)
            }
            pad[ROW_MASK] = np.zeros(self._local_rows, dtype=bool)
            produced += 1
            yield pad


def ordered_ingest(
    items: Iterable[Any],
    work: Callable[[Any], Any],
    commit: Optional[Callable[[Any, Any], Any]] = None,
    *,
    workers: int,
    lookahead: int,
    supervisor=None,
    stats: Optional[IngestPoolStats] = None,
    sizer: Optional[Callable[[Any], int]] = None,
    emit_event: bool = True,
    binding=None,
) -> Iterator[Any]:
    """Yield ``commit(work(item), item)`` for each item of ``items``,
    with ``work`` fanned out over ``workers`` threads and results
    released strictly in source order (see module docstring for the
    full ordering/teardown contract). ``sizer(result)`` (optional)
    prices a finished result in bytes for the peak-in-flight gauge.
    The reader and workers run under ``binding``
    (``Telemetry.thread_binding()``; default: what is open on the
    consuming thread), so their spans join the consumer's run."""
    workers = max(1, int(workers))
    lookahead = max(workers, int(lookahead))
    stats = stats or IngestPoolStats()
    stats.workers = workers
    started = clock()
    if binding is None:
        binding = get_telemetry().thread_binding()

    work_q: "queue.Queue" = queue.Queue(maxsize=lookahead)
    stop = threading.Event()
    cond = threading.Condition()
    # seq -> ("item", result, item, nbytes) | ("error", exc, None, 0) |
    # ("done", None, None, 0); guarded by cond
    results: Dict[int, Any] = {}
    state = {
        "next_seq": 0,  # next sequence number the reader will assign
        "released": 0,  # next sequence number the consumer will yield
        "in_flight_bytes": 0,
    }

    def put_work(msg) -> bool:
        # bounded put that notices an abandoned consumer — a plain
        # q.put would block forever holding batch buffers + the scanner
        while not stop.is_set():
            try:
                work_q.put(msg, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def deposit(seq: int, entry) -> None:
        with cond:
            results[seq] = entry
            if entry[0] == "item":
                state["in_flight_bytes"] += entry[3]
                stats.peak_in_flight_bytes = max(
                    stats.peak_in_flight_bytes, state["in_flight_bytes"]
                )
            cond.notify_all()

    def read() -> None:
        seq = 0
        try:
            for item in items:
                # admission: at most ``lookahead`` items in flight —
                # bounds host memory (queued + decoding + awaiting
                # ordered release all count)
                with cond:
                    while (
                        seq - state["released"] >= lookahead
                        and not stop.is_set()
                    ):
                        cond.wait(timeout=0.1)
                    stats.peak_in_flight = max(
                        stats.peak_in_flight, seq - state["released"] + 1
                    )
                if stop.is_set():
                    return
                if not put_work((seq, item)):
                    return
                seq += 1
        # lint-ok: interrupt-swallow: the reader forwards the exception
        # (interrupts included) through the reassembly stage; the
        # consumer re-raises it on the scan thread at position seq
        except BaseException as exc:  # noqa: BLE001 — re-raised in order
            deposit(seq, ("error", exc, None, 0))
            return
        deposit(seq, ("done", None, None, 0))

    def reader() -> None:
        with binding():
            read()

    def work_loop() -> None:
        while not stop.is_set():
            t0 = clock()
            try:
                seq, item = work_q.get(timeout=0.1)
            except queue.Empty:
                stats.add("idle_s", clock() - t0)
                continue
            stats.add("idle_s", clock() - t0)
            try:
                result = work(item)
                nbytes = int(sizer(result)) if sizer is not None else 0
                deposit(seq, ("item", result, item, nbytes))
            # lint-ok: interrupt-swallow: a worker forwards its
            # exception (interrupts included) through the reassembly
            # stage; the consumer re-raises it on the scan thread at
            # EXACTLY position seq — after every earlier item
            except BaseException as exc:  # noqa: BLE001 — re-raised
                deposit(seq, ("error", exc, None, 0))

    def worker_loop() -> None:
        with binding():
            work_loop()

    reader_t = register_ingest_thread(
        threading.Thread(
            target=reader, daemon=True, name="deequ-tpu-ingest-reader"
        )
    )
    worker_ts = [
        register_ingest_thread(
            threading.Thread(
                target=worker_loop,
                daemon=True,
                name=f"deequ-tpu-ingest-{i}",
            )
        )
        for i in range(workers)
    ]
    reader_t.start()
    for t in worker_ts:
        t.start()

    def flush_stats() -> None:
        stats.wall_s = clock() - started
        if not emit_event:
            return
        # emitted on the CONSUMER (scan) thread: telemetry run
        # captures are thread-scoped
        get_telemetry().event("ingest_pool", **stats.to_event_fields())

    try:
        while True:
            want = state["released"]
            with cond:
                entry = results.get(want)
                if entry is None:
                    t0 = clock()
                    timeout = (
                        supervisor.poll_s()
                        if supervisor is not None
                        else 0.1
                    )
                    cond.wait(timeout=timeout)
                    stats.stall_s += clock() - t0
                    entry = results.get(want)
                if entry is not None:
                    del results[want]
            if entry is None:
                if supervisor is not None:
                    supervisor.on_wait()  # cancel/deadline/stall check
                continue
            tag, payload, item, nbytes = entry
            if tag == "error":
                raise payload
            if tag == "done":
                return
            if supervisor is not None:
                supervisor.note_arrival()
            t0 = clock()
            released = (
                commit(payload, item) if commit is not None else payload
            )
            stats.commit_s += clock() - t0
            stats.released += 1
            with cond:
                state["released"] = want + 1
                state["in_flight_bytes"] -= nbytes
                cond.notify_all()
            yield released
    finally:
        stop.set()  # consumer done or raised: release reader + workers
        if supervisor is not None:
            # a reader blocked inside a hung source read can't see
            # ``stop`` — set its armed interrupt event so it raises out
            supervisor.release_source()
        with cond:
            cond.notify_all()
        try:
            while True:
                work_q.get_nowait()
        except queue.Empty:
            pass
        reader_t.join(timeout=2.0)
        for t in worker_ts:
            t.join(timeout=2.0)
        flush_stats()
