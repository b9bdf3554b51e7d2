"""Engine configuration.

The reference has no global config — everything is per-run builder
options (SURVEY.md §5.6) — but the north star asks for an engine
selection flag (the ``deequ.engine=tpu`` analog) and the TPU build needs
a handful of hardware-shaping knobs that have no Spark equivalent:

- ``accumulation_dtype`` — dtype of scalar *float* state accumulators.
  On TPU, float64 is software-emulated; the hot path therefore does
  per-element work in the column's native dtype and only casts the
  per-batch *scalar* reduction results into the accumulation dtype, so
  "float64" costs a few emulated scalar ops per batch instead of an
  emulated elementwise pass. Counts are ALWAYS
  exact int64, and integral columns always widen per element to f64 —
  the knob never changes integer semantics.
- ``device_cache_bytes`` — budget for keeping device-resident columns.
  The multi-pass profiler re-reads the same columns, so columns are
  transferred once and cached on device.
- ``synthesize_all_true_masks`` — columns with no nulls get their
  validity mask created ON device (jnp.ones) instead of shipping
  num_rows bytes over the wire.
- ``compilation_cache_dir`` — persistent XLA compilation cache; the
  fused scan re-traces per run (ops are per-dataset closures) but XLA
  compilation — the dominant cost — is reused across runs/processes.
  ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
- ``engine`` — "tpu" (default: whatever jax.devices() provides) or
  "cpu" (force host platform); the engine-selection flag.

Configuration may be set via ``deequ_tpu.config.set_option``, the
``configure(...)`` context manager, or ``DEEQU_TPU_*`` environment
variables read at import.
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass, field, replace
from typing import Iterator, Optional

#: JAX's own cache variable; when set, it names the one cache directory
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the fixed fallback: the path is part of the cache key, so it must
#: not move between runs (listed in .gitignore)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def _default_compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``;
    ``DEEQU_TPU_COMPILE_CACHE=""`` turns the cache off (the tests'
    switch, tests/conftest.py)."""
    if os.environ.get("DEEQU_TPU_COMPILE_CACHE") == "":
        return ""
    return os.environ.get(JAX_CACHE_ENV) or REPO_CACHE_DIR


@dataclass
class Options:
    # dtype for scalar state accumulators ("float64" | "float32")
    accumulation_dtype: str = "float64"
    # device-resident column cache budget (bytes); 0 disables
    device_cache_bytes: int = int(
        os.environ.get("DEEQU_TPU_DEVICE_CACHE_BYTES", 8 << 30)
    )
    # synthesize masks of all-valid columns on device (skip transfer)
    synthesize_all_true_masks: bool = True
    # device budget for dense grouping count vectors (bytes); caps the
    # joint key space the frequency pass keeps on device (~2^28 keys/GB
    # at i32 counts) before spilling to the host Arrow group_by
    dense_grouping_budget_bytes: int = int(
        os.environ.get("DEEQU_TPU_DENSE_GROUPING_BYTES", 1 << 30)
    )
    # device sort+segment path for high-cardinality single-numeric-column
    # grouping (analyzers/spill.py); False forces the host Arrow fallback
    device_spill_grouping: bool = True
    # fold spill key extraction into the shared fused scan (ONE source
    # traversal for scalars + dense + spill plans, with the per-plan
    # sort finalizes overlapped); False restores the per-plan deferred
    # re-scan path — kept for differential testing and as an escape
    # hatch
    one_pass_spill: bool = True
    # route the HLL register scatter-max through the measured unroll-16
    # Pallas SMEM kernel (tools/scatter_probe.py: 1.1-1.15x over the XLA
    # scatter at (2^21, M=2^14)) on a TPU, where a kernel that fails
    # to compile raises; off the TPU the XLA scatter runs. Registers
    # are bit-identical either way (tests/test_fastpath_differential.py,
    # and on the chip chip_smoke.py's pallas phase). Off by default until
    # the production-shape probe artifact justifies flipping it
    # (docs/PERF.md "Pallas scatter")
    pallas_scatter: bool = (
        os.environ.get("DEEQU_TPU_PALLAS_SCATTER", "0") == "1"
    )
    # widened sorted-dedup HLL gate (sketches/hll.py): integer columns
    # whose O(1) range probe FAILS (unknown or wide declared range) may
    # still ride the shared KLL sort's sorted-dedup register builder
    # when their carried-register cardinality estimate says
    # mid-cardinality AND the batch's values fit the f32 mantissa
    # (both checked in-kernel; a mispredicted estimate falls back to
    # the full scatter inside the branch). False restores the
    # range-probe-only gate — kept as the differential reference
    hll_dedup_widening: bool = (
        os.environ.get("DEEQU_TPU_HLL_DEDUP_WIDENING", "1") != "0"
    )
    # per-column wire codecs on the streamed packed wire
    # (engine/wire.py, docs/PERF.md "Wire diet"): each column's wire
    # dtype is resolved ONCE per run from parquet statistics or a
    # first-batch probe (int64 -> i32/i16/i8 by range, f64 -> f32 when
    # values provably round-trip bit-exactly, codes/lengths by observed
    # magnitude) and decoded back to the canonical dtype inside the
    # fused wire_unpack, so device programs are bit-identical either
    # way. False ships today's canonical-width wire — kept as the
    # differential oracle (tests/test_wire_codecs.py)
    wire_codecs: bool = (
        os.environ.get("DEEQU_TPU_WIRE_CODECS", "1") != "0"
    )
    # one-pass dictionary deltas for streamed string codes
    # (data/parquet.py, engine/vectorize.py): dictionaries build
    # incrementally per batch and ship only the NEW uniques (delta
    # payloads applied to LUT-carrying op states), killing the
    # _dict_value_set streaming pre-pass — string-code suites traverse
    # the source exactly once. False restores the pre-pass path
    dict_deltas: bool = (
        os.environ.get("DEEQU_TPU_DICT_DELTAS", "1") != "0"
    )
    # static LUT capacity (entries) carried by delta-aware op states; a
    # dictionary growing past it is a deterministic error (raise the
    # cap or set dict_deltas=False for that source)
    dict_delta_capacity: int = int(
        os.environ.get("DEEQU_TPU_DICT_DELTA_CAPACITY", 1 << 16)
    )
    # parallel host ingest (engine/ingest.py, docs/PERF.md "r10"):
    # decode/encode worker threads feeding the streaming scan through
    # the ordered reassembly stage. 0 = auto (min(4, cpu count));
    # 1 = the single-prefetch-thread path, bit-identical to the
    # pre-pool engine (the differential oracle). Host-pipeline only:
    # never part of the plan fingerprint — flipping it must not
    # retrace or recompile anything
    ingest_workers: int = int(
        os.environ.get("DEEQU_TPU_INGEST_WORKERS", 0) or 0
    )
    # bounded prefetch queue depth for the single-worker path (the
    # old hard-coded depth=2 of engine/scan._prefetched); host-pipeline
    # only, plan-fingerprint-neutral like ingest_workers
    ingest_depth: int = int(
        os.environ.get("DEEQU_TPU_INGEST_DEPTH", 2) or 2
    )
    # max batches in flight inside the ingest pool (queued + decoding
    # + decoded-awaiting-ordered-release); bounds host memory under
    # the PR 5 admission watermark. 0 = auto (2 * workers)
    ingest_lookahead: int = int(
        os.environ.get("DEEQU_TPU_INGEST_LOOKAHEAD", 0) or 0
    )
    # process-sharded ingest on the mesh streaming path: each process
    # reads only its own row-group shard (ParquetDataset.shard_view)
    # and feeds ONE global array per batch leaf via
    # jax.make_array_from_process_local_data (SNIPPETS.md [2]
    # partitioner pattern). With a single process this is exactly the
    # plain device_put feed; multi-process runs also perform the r5
    # uniform compile-failure exchange so no host strands its peers
    process_sharded_ingest: bool = (
        os.environ.get("DEEQU_TPU_PROCESS_SHARDED_INGEST", "1") != "0"
    )
    # persistent XLA compilation cache directory ("" disables): see
    # _default_compile_cache_dir for the order
    compilation_cache_dir: str = field(
        default_factory=_default_compile_cache_dir
    )
    # engine selection: "tpu" (default jax backend) | "cpu"
    engine: str = os.environ.get("DEEQU_TPU_ENGINE", "tpu")
    # rows per fused-scan batch when streaming (None = engine default)
    batch_size: Optional[int] = None
    # per-batch retry policy for the scan's read/decode/transfer stages
    # (engine/resilience.RetryPolicy; None = the engine's default
    # policy — 3 attempts, exponential backoff, deterministic jitter).
    # Set max_attempts=1 to disable retries entirely.
    scan_retry: Optional[object] = None
    # how a degraded run (quarantined batches in the fused scan) maps
    # onto VerificationSuite status: "fail" (the run is Error), "warn"
    # (at least Warning), "tolerate" (status unchanged; the
    # degradation record still rides the result)
    degradation_policy: str = os.environ.get(
        "DEEQU_TPU_DEGRADATION_POLICY", "fail"
    )
    # batches between scan checkpoints when the engine has a
    # ScanCheckpointer attached (io/state_provider.py); <= 0 disables
    checkpoint_every_batches: int = int(
        os.environ.get("DEEQU_TPU_CHECKPOINT_EVERY", 64)
    )
    # deadlines & cancellation (engine/deadline.py, docs/RESILIENCE.md):
    # wall-clock budget for a whole analysis/verification run — on
    # exhaustion the scan exits cleanly with partial metrics and a
    # final checkpoint cursor; <= 0 disables
    run_deadline_seconds: float = float(
        os.environ.get("DEEQU_TPU_RUN_DEADLINE", 0) or 0
    )
    # per-batch stall limit: a batch taking longer than this raises
    # ScanStalled (transient -> retry -> quarantine); <= 0 disables
    batch_stall_seconds: float = float(
        os.environ.get("DEEQU_TPU_BATCH_STALL", 0) or 0
    )
    # bounded admission: at most this many concurrent analysis runs in
    # the process, the rest queue FIFO under their own deadline;
    # 0 = unlimited
    max_concurrent_runs: int = int(
        os.environ.get("DEEQU_TPU_MAX_CONCURRENT_RUNS", 0) or 0
    )
    # memory-pressure resilience (engine/memory.py,
    # docs/RESILIENCE.md "Memory pressure"): adaptive batch backoff —
    # a batch whose dispatch/transfer OOMs is re-fed through a chunked
    # path at a geometrically halved effective batch size; False
    # restores the pre-backoff behavior (a device OOM aborts the scan)
    memory_backoff: bool = (
        os.environ.get("DEEQU_TPU_MEMORY_BACKOFF", "1") != "0"
    )
    # floor for the backed-off effective batch size; an allocation
    # that still fails here quarantines the remaining rows instead
    min_batch_rows: int = int(
        os.environ.get("DEEQU_TPU_MIN_BATCH_ROWS", 4096)
    )
    # consecutive clean batches at a reduced size before the effective
    # size heals back up (doubles); <= 0 disables healing (the scan
    # stays at the reduced size until it ends)
    memory_heal_after_batches: int = int(
        os.environ.get("DEEQU_TPU_MEMORY_HEAL_AFTER", 8)
    )
    # admission high-watermark (bytes): concurrent runs queue once the
    # sum of their estimated device footprints
    # (engine.estimated_run_bytes, from scan_row_capacity geometry)
    # would exceed this — queueing instead of co-OOMing; 0 disables
    memory_watermark_bytes: int = int(
        os.environ.get("DEEQU_TPU_MEMORY_WATERMARK_BYTES", 0) or 0
    )
    # multi-tenant verification service (deequ_tpu/service/,
    # docs/SERVICE.md): executor worker threads draining the run queue
    service_workers: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_WORKERS", 2)
    )
    # of those, how many only ever take INTERACTIVE-class runs — the
    # anti-starvation reserve (a long BATCH run can never occupy every
    # worker); clamped to service_workers - 1 so batch work always has
    # at least one worker
    service_interactive_reserve: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_INTERACTIVE_RESERVE", 1)
    )
    # bytes watermark for the service's shared resident-dataset
    # registry (service/caches.py DatasetCache): registered handles are
    # evicted LRU-first once the sum of their estimated run bytes
    # exceeds this; 0 = fall back to device_cache_bytes
    service_dataset_watermark_bytes: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_DATASET_WATERMARK", 0) or 0
    )
    # per-tenant quotas: max runs a tenant may have queued+active at
    # once (submit raises QuotaExceeded beyond it), and max
    # simultaneously ACTIVE; 0 = unlimited
    service_tenant_max_pending: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_TENANT_MAX_PENDING", 0) or 0
    )
    service_tenant_max_active: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_TENANT_MAX_ACTIVE", 0) or 0
    )
    # crash isolation (engine/subproc.py, docs/RESILIENCE.md "Crash
    # isolation and recovery"): run service executions in a
    # spawn-started child process so a hard crash (SIGSEGV/OOM-kill)
    # costs one checkpoint window, not the daemon
    isolated_execution: bool = (
        os.environ.get("DEEQU_TPU_ISOLATED_EXECUTION", "0") == "1"
    )
    # child relaunches WITHOUT checkpoint progress before the run is
    # declared a crash loop (the poison-batch bound); each relaunch
    # that advanced the cursor resets the count
    crash_max_relaunches: int = int(
        os.environ.get("DEEQU_TPU_CRASH_MAX_RELAUNCHES", 3)
    )
    # per-plan crash-loop circuit breaker: seconds the breaker stays
    # OPEN (rejecting launches fast) before one half-open probe launch
    # is allowed through; <= 0 disables the breaker entirely
    crash_breaker_cooldown_s: float = float(
        os.environ.get("DEEQU_TPU_CRASH_BREAKER_COOLDOWN", 30.0)
    )
    # durable write-ahead run journal directory (service/journal.py);
    # "" disables journaling (and with it restart recovery)
    service_journal_dir: str = os.environ.get(
        "DEEQU_TPU_SERVICE_JOURNAL_DIR", ""
    )
    # load shedding at the submission edge: BATCH-priority submits are
    # rejected fast (ServiceOverloaded, with a retry-after hint) once
    # the queue holds this many runs; 0 disables
    service_shed_queue_depth: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_SHED_QUEUE_DEPTH", 0) or 0
    )
    # ... and once this many child crashes landed inside the sliding
    # crash-rate window (service-wide, any plan); 0 disables
    service_shed_crash_rate: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_SHED_CRASH_RATE", 0) or 0
    )
    # sliding-window length (seconds) for the crash-rate shed signal
    service_shed_crash_window_s: float = float(
        os.environ.get("DEEQU_TPU_SERVICE_SHED_CRASH_WINDOW", 60.0)
    )
    # scan coalescing (docs/SERVICE.md "Scan coalescing"): compatible
    # queued runs targeting the same dataset_key share ONE superset
    # scan, each tenant's AnalyzerContext sliced back out. Opt-in (like
    # pallas_scatter/isolated_execution): default-off keeps existing
    # solo-run latency/ordering semantics untouched
    service_coalesce: bool = (
        os.environ.get("DEEQU_TPU_SERVICE_COALESCE", "0") == "1"
    )
    # how long a BATCH-priority run may wait past submit for coalesce
    # peers to arrive (seconds, measured on the service's injected
    # clock); INTERACTIVE and STANDARD never wait. 0 = group only with
    # what is already queued
    service_coalesce_window_s: float = float(
        os.environ.get("DEEQU_TPU_SERVICE_COALESCE_WINDOW", 0) or 0
    )
    # ceiling on runs per superset scan (bounds merged-plan op count
    # and one failed group's blast radius)
    service_coalesce_max_members: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_COALESCE_MAX_MEMBERS", 8) or 8
    )
    # elastic device placement (service/placement.py, docs/SERVICE.md
    # "Elastic placement"): bin-pack concurrent runs onto disjoint
    # power-of-two mesh sub-slices instead of serializing whole-mesh.
    # Opt-in like coalescing: default-off keeps today's host/whole-mesh
    # engine construction untouched
    service_elastic_placement: bool = (
        os.environ.get("DEEQU_TPU_SERVICE_ELASTIC_PLACEMENT", "0") == "1"
    )
    # placement policy: one device per this many estimated run bytes
    # (the admission watermark's estimate), rounded up to a power of two
    service_placement_bytes_per_device: int = int(
        os.environ.get(
            "DEEQU_TPU_SERVICE_PLACEMENT_BYTES_PER_DEVICE", 512 << 20
        )
        or (512 << 20)
    )
    # ceiling on a single run's slice (0 = the whole pool)
    service_placement_max_devices: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_PLACEMENT_MAX_DEVICES", 0) or 0
    )
    # slice size for runs with no byte estimate (factory datasets)
    service_placement_default_devices: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_PLACEMENT_DEFAULT_DEVICES", 1)
        or 1
    )
    # LRU cap on cached Mesh objects (one per distinct device slice)
    service_placement_mesh_cache_slices: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_PLACEMENT_MESH_SLICES", 8) or 8
    )
    # end-to-end run tracing (docs/OBSERVABILITY.md "Tracing"): every
    # submission is minted a TraceContext at enqueue and the span tree
    # follows it across workers, coalesced groups, placement leases,
    # and the spawn boundary. Opt-in: default-off emits not one extra
    # span and adds no per-batch work above the existing PhaseClock
    service_trace: bool = (
        os.environ.get("DEEQU_TPU_SERVICE_TRACE", "0") == "1"
    )
    # live observability plane (telemetry/export.py serve_metrics):
    # port for the stdlib HTTP endpoint exposing /metrics (Prometheus
    # text) and /healthz (JSON health snapshot); 0 = no endpoint thread
    service_metrics_port: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_METRICS_PORT", 0) or 0
    )
    # per-class queue-wait latency objectives for the SloTracker, as
    # "class=seconds" pairs ("interactive=1.0,batch=30"); "" disables
    # SLO tracking (no tracker allocated, no oprecords persisted)
    service_slo_objectives: str = os.environ.get(
        "DEEQU_TPU_SERVICE_SLO_OBJECTIVES", ""
    )
    # checkpoint-conserving preemption (service/preempt.py,
    # docs/SERVICE.md "Preemption and autoscaling"): an INTERACTIVE
    # ticket that finds the pool/workers saturated preempts the
    # youngest solo BATCH run — cancel-with-checkpoint at the next
    # batch boundary, lease revoked, ticket requeued carrying its
    # cursor — and the victim later resumes with zero recompute and
    # zero recompile. Opt-in: default-off allocates no controller, no
    # per-attempt tokens, and changes no pop/finish semantics
    service_preemption: bool = (
        os.environ.get("DEEQU_TPU_SERVICE_PREEMPTION", "0") == "1"
    )
    # livelock bound: preemption requests a single run may absorb
    # before it becomes ineligible as a victim (it then runs to
    # completion however long interactive pressure lasts)
    service_preempt_max_per_run: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_PREEMPT_MAX_PER_RUN", 3) or 3
    )
    # queue-driven autoscaling (service/autoscale.py): a control loop
    # adjusting worker count, interactive_reserve, and the coalesce
    # window from the per-class service.queue_wait_s.* histograms and
    # SLO burn. Opt-in; requires an explicit decision cadence
    service_autoscale: bool = (
        os.environ.get("DEEQU_TPU_SERVICE_AUTOSCALE", "0") == "1"
    )
    service_autoscale_interval_s: float = float(
        os.environ.get("DEEQU_TPU_SERVICE_AUTOSCALE_INTERVAL", 10.0)
        or 10.0
    )
    service_autoscale_min_workers: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_AUTOSCALE_MIN_WORKERS", 1) or 1
    )
    service_autoscale_max_workers: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_AUTOSCALE_MAX_WORKERS", 8) or 8
    )
    # queue-wait the interactive class should stay under (seconds);
    # the controller scales up / widens the reserve while the observed
    # p99 since the last decision exceeds it
    service_autoscale_target_interactive_p99_s: float = float(
        os.environ.get(
            "DEEQU_TPU_SERVICE_AUTOSCALE_TARGET_INTERACTIVE_P99", 1.0
        )
        or 1.0
    )
    # fleet failover (service/fleet.py, docs/SERVICE.md "Fleet
    # failover"): a non-empty shared fleet dir turns each journaling
    # replica into a fleet member — heartbeat lease + peer watch +
    # orphan adoption + epoch fencing. "" (default) = solo replica,
    # every fleet path byte-identical to the pre-fleet service.
    service_fleet_dir: str = os.environ.get(
        "DEEQU_TPU_SERVICE_FLEET_DIR", ""
    )
    # replica identity in the fleet dir's lease namespace; "" derives
    # replica-<pid> (fine for single-host loopback fleets, set it
    # explicitly for real deployments so adoption provenance is stable)
    service_fleet_replica: str = os.environ.get(
        "DEEQU_TPU_SERVICE_FLEET_REPLICA", ""
    )
    service_fleet_heartbeat_s: float = float(
        os.environ.get("DEEQU_TPU_SERVICE_FLEET_HEARTBEAT", 2.0) or 2.0
    )
    # how long a peer's (epoch, stamp) pair may sit unchanged on the
    # OBSERVER's clock before the lease is declared dead and adoption
    # races begin; must comfortably exceed heartbeat_s (the default
    # survives ~5 missed beats)
    service_fleet_lease_timeout_s: float = float(
        os.environ.get("DEEQU_TPU_SERVICE_FLEET_LEASE_TIMEOUT", 10.0)
        or 10.0
    )
    # distinct replicas a plan key must crash-loop before the shared
    # breaker ledger quarantines it fleet-wide at adoption time
    service_fleet_poison_replicas: int = int(
        os.environ.get("DEEQU_TPU_SERVICE_FLEET_POISON_REPLICAS", 2) or 2
    )

    def accumulation_float(self):
        import jax.numpy as jnp

        return jnp.float64 if self.accumulation_dtype == "float64" else jnp.float32


_lock = threading.Lock()
_options = Options()
_compile_cache_installed = False


def options() -> Options:
    return _options


def set_option(**kwargs) -> None:
    global _options
    with _lock:
        _options = replace(_options, **kwargs)


@contextlib.contextmanager
def configure(**kwargs) -> Iterator[Options]:
    """Temporarily override options within a block."""
    global _options
    with _lock:
        prev = _options
        _options = replace(_options, **kwargs)
    try:
        yield _options
    finally:
        with _lock:
            _options = prev


def install_compilation_cache() -> None:
    """Enable JAX's persistent compilation cache (idempotent). Called by
    the engine on first use; makes repeated runs of structurally
    identical fused scans skip XLA compilation entirely. When the
    directory is JAX's own ``JAX_COMPILATION_CACHE_DIR``, JAX already
    reads it and nothing is set here. A failure is reported as a
    warning and the run goes on uncached."""
    global _compile_cache_installed
    if _compile_cache_installed:
        return
    cache_dir = _options.compilation_cache_dir
    if not cache_dir:
        return
    _compile_cache_installed = True  # one attempt per process
    try:
        import jax

        os.makedirs(cache_dir, exist_ok=True)
        if cache_dir != os.environ.get(JAX_CACHE_ENV):
            jax.config.update("jax_compilation_cache_dir", cache_dir)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.5
            )
        # swap in the torn-write-safe store: atomic entry writes and
        # validate-on-read, so a crash mid-put can never poison later
        # runs with a truncated executable (docs/RESILIENCE.md)
        from deequ_tpu.engine import compile_cache

        if not compile_cache.install(cache_dir):
            raise RuntimeError("jax's compilation-cache internals moved")
    except Exception as exc:  # noqa: BLE001 — the cache is an optimization
        import warnings

        warnings.warn(
            f"persistent compile cache at {cache_dir!r} not installed: "
            f"{exc!r}",
            RuntimeWarning,
            stacklevel=2,
        )
