"""The fused-scan execution engine.

This is deequ_tpu's replacement for the reference's
``AnalysisRunner.runScanningAnalyzers`` + Spark/Tungsten execution
(SURVEY.md §3.1 ★#1): all scan-shareable analyzers' state updates are
composed into ONE jitted function per batch shape, so N analyzers cost a
single pass over the data — XLA fuses the masked reductions over shared
columns the way Tungsten fuses deequ's concatenated aggregation
expressions.

Distribution model (SURVEY.md §2.6): batches are sharded row-wise over
the ``dp`` axis of a ``jax.sharding.Mesh``; states are replicated. Under
``jit`` with sharded inputs, XLA turns every masked reduction into a
per-device partial aggregate plus a psum/min/max all-reduce over ICI —
exactly the reference's partial-aggregate + merge plan, generated by the
compiler instead of the Catalyst planner.

For explicit SPMD (multi-host, and the dry-run validation path) the same
update/merge ops run under ``shard_map``; the cross-device state merge is
a generic monoid all-reduce built from ``lax.all_gather`` + the
analyzer's own ``merge`` (sums become psums, min/max become min/max
all-reduces, HLL registers become elementwise-max all-reduces).

High-cardinality grouping rides the same single pass via COLLECTOR ops
(``ScanOps.device_result``; built in analyzers/spill.py): the op's
state is a preallocated device-resident u64 key buffer sized from the
scan's row capacity (``scan_row_capacity``), its update appends each
batch's keys at a carried row offset with ``dynamic_update_slice``, and
the epilogue's packed fetch skips the buffer (``DeviceResident``) so
the post-scan sort + segment-count finalize consumes it in device
memory. This works in both resident and streaming modes and under the
DP mesh (each shard accumulates its local rows; the sharded
sort/all_to_all finalize consumes the per-shard buffers unchanged).
Each source traversal increments the ``engine.data_passes`` counter —
a mixed scalar + dense + spill suite counts exactly 1.
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deequ_tpu.analyzers.base import ScanOps, ScanShareableAnalyzer
from deequ_tpu.data.table import (
    DICT_DELTA_PREFIX,
    ROW_MASK,
    ColumnRequest,
    Dataset,
)
from deequ_tpu.engine import wire as wire_mod
from deequ_tpu.engine.deadline import (
    CancelToken,
    RunBudget,
    ScanInterrupted,
    ScanInterruption,
    ScanSupervisor,
    shutdown_installed,
    shutdown_token,
)
from deequ_tpu.engine.memory import (
    BackoffExhausted,
    classify_memory_pressure,
    make_backoff,
    oom_probe_of,
    record_memory_pressure,
)
from deequ_tpu.engine.pack import DeviceResident, packed_device_get
from deequ_tpu.engine.resilience import (
    BatchIntegrityError,
    RetryPolicy,
    ScanDegradation,
    resilient_batches,
    retry_transient,
)
from deequ_tpu.sketches import hll
from deequ_tpu.telemetry import PhaseClock, get_telemetry

DEFAULT_MAX_BATCH = 1 << 21  # 2M rows per fused-scan step (measured
# best resident throughput: per-batch fixed costs — HLL scatter setup,
# KLL sample emission — amortize over more rows; 4M+ batches regress)
# batches per resident chunk: chunks are the transfer/compute overlap
# granularity (device_put of chunk i+1 streams while chunk i computes —
# both dispatch async). One 2M-row batch per chunk: fresh runs overlap
# at 2M-row granularity, which is plenty — they are link-bound anyway
CHUNK_BATCHES = 1

# cross-run plan cache: jitted fused scans keyed by the ops' behavior
# tokens (see ScanOps.cache_token). Re-running an equal plan — same
# analyzers, schema kinds, expressions; dataset content rides the
# arguments — skips BOTH the Python re-trace and the persistent-cache
# executable deserialization (seconds for 100-analyzer profiler plans).
from collections import OrderedDict  # noqa: E402

# Config knobs read on the prepare_scan plan-building path whose effect
# the cache key already distinguishes — the staticcheck plan-key rule
# (docs/STATIC_ANALYSIS.md) fails the build on any config read here
# that is neither in _plan_cache_key nor in this mapping, so adding a
# knob to plan building forces either a key element or a justification:
PLAN_KEY_COVERED_CONFIG = {
    "engine": "the placement SHAPE is the key's third element"
    " (_resolve_placement feeds prepare_scan's sharding through"
    " _placement_shape into _plan_cache_key; elastic slices of the"
    " same size share one entry — safe because the jitted closures"
    " never capture the mesh, sharding enters via device_put outside"
    " the jit, and jit re-traces per input sharding internally)",
    "batch_size": "traces are shape-specialized: jit re-traces a cached"
    " plan for a new batch geometry, and the streaming key carries"
    " batch_size explicitly",
    "device_cache_bytes": "only forks resident vs streaming mode; the"
    " streaming executable extends the key with a ('stream', ...)"
    " suffix, so the two modes never share an entry",
    "wire_codecs": "the streaming key appends the resolved codec"
    " table's token (engine/wire.py CodecTable.token); codecs-off runs"
    " carry the empty table's token, a distinct key, and resident"
    " plans never pack a wire at all",
}


class _PlanEntry:
    """A cached jitted fused scan plus its lifetime trace counter (jax
    may re-trace the SAME cached fn for new input signatures; per-run
    trace counts are deltas of this counter)."""

    __slots__ = ("fn", "traces")

    def __init__(self, fn):
        self.fn = fn
        self.traces = 0


_PLAN_CACHE: "OrderedDict[tuple, _PlanEntry]" = OrderedDict()
_PLAN_CACHE_CAP = 32


def _placement_shape(placement):
    """Collapse a concrete placement to its SHAPE for plan keying.

    Elastic placement (service/placement.py) runs the same suite on
    many same-sized device slices; the jitted plan closures capture
    only ops/host_slots — sharding is applied via ``device_put``
    OUTSIDE the jit — so one cached plan can serve every slice of a
    given shape (jit's own cache re-traces per concrete input
    sharding). Keying on the shape instead of the device identity is
    what makes a warmed shape compile-free no matter which slice the
    pool hands out."""
    if placement is None:
        return None
    mesh = getattr(placement, "mesh", None)
    spec = getattr(placement, "spec", None)
    if mesh is not None:
        axes = tuple(
            (str(name), int(size)) for name, size in mesh.shape.items()
        )
        return ("mesh", axes, str(spec))
    return ("device", str(placement))


def _shape_label(key) -> Optional[str]:
    """Per-shape telemetry label from a plan-cache key's placement
    element: ``host`` (no placement), ``mesh<ndev>``, or ``device``."""
    if key is None:
        return None
    shape = key[2]
    if shape is None:
        return "host"
    if shape[0] == "mesh":
        ndev = 1
        for _, size in shape[1]:
            ndev *= size
        return f"mesh{ndev}"
    return "device"


def _plan_cache_key(ops, host_slots, placement):
    from deequ_tpu.analyzers.base import CACHE_TOKEN_AUTO

    tokens = []
    for op in ops:
        # None = explicit opt-out; a still-AUTO token means nobody
        # vouched for the op's closure purity — equally uncacheable
        if op.cache_token is None or op.cache_token is CACHE_TOKEN_AUTO:
            return None
        tokens.append(op.cache_token)
    # static engine decisions baked into a trace ride the key too: a
    # plan traced with one HLL register build must not be served to a
    # run whose backend probe resolves the other
    key = (
        tuple(tokens),
        tuple(host_slots),
        _placement_shape(placement),
        hll.register_build_path(),
    )
    try:
        hash(key)
    except TypeError:
        return None
    return key


def _mesh_carry(states, sharding):
    """Commit a scan's init carry to ``sharding``'s mesh, replicated,
    leaving leaves already on a mesh (sharded collector buffers) alone.
    The fused step returns its carry typed on the mesh; an init carry
    that is not makes the second step retrace and recompile the whole
    fused scan (seen on 4 v5e chips, PR 21). The copies are jax's own
    (``may_alias=False``): the carry is donated, and donating a host
    buffer jax aliased zero-copy corrupts the heap (_batch_donation)."""
    if not isinstance(sharding, NamedSharding):
        return states
    replicated = NamedSharding(sharding.mesh, P())

    def place(leaf):
        if isinstance(getattr(leaf, "sharding", None), NamedSharding):
            return leaf
        return jax.device_put(leaf, replicated, may_alias=False)

    return jax.tree_util.tree_map(place, states)


def _step_settler(sharding):
    """How a scan settles each dispatched step, chosen once per scan:
    on a CPU mesh, wait for the step before dispatching the next. XLA's
    CPU client runs every virtual device's share of a program on one
    shared thread pool, and a collective blocks its thread until all
    devices arrive: with two steps in flight the pool can fill with
    waiters of both, and the collective never completes (a fatal
    rendezvous timeout, seen as SIGABRT under parallel test load).
    Accelerators order each device's programs themselves."""
    if (
        isinstance(sharding, NamedSharding)
        and sharding.mesh.devices.flat[0].platform == "cpu"
    ):
        return jax.block_until_ready
    return lambda out: out


def _plan_cache_lookup(key, make_entry):
    """Fetch or create a plan-cache entry; ``make_entry(entry)`` must
    set ``entry.fn`` (the entry exists first so the jitted closure can
    capture its own trace counter). Returns (entry, cache_hit)."""
    label = _shape_label(key)
    tm = get_telemetry()
    with tm.span("plan:lookup") as sp:
        entry = _PLAN_CACHE.get(key) if key is not None else None
        hit = entry is not None
        sp.set(hit=hit)
        if hit:
            _PLAN_CACHE.move_to_end(key)
        else:
            entry = _PlanEntry(None)
            make_entry(entry)
            if key is not None:
                _PLAN_CACHE[key] = entry
                while len(_PLAN_CACHE) > _PLAN_CACHE_CAP:
                    _PLAN_CACHE.popitem(last=False)
    if hit:
        tm.counter("engine.plan_cache.hits").inc()
        if label is not None:
            tm.counter(f"engine.plan_cache.per_shape.{label}.hits").inc()
    else:
        tm.counter("engine.plan_cache.misses").inc()
        if label is not None:
            tm.counter(f"engine.plan_cache.per_shape.{label}.misses").inc()
    return entry, hit


def plan_token_of(key) -> Optional[str]:
    """Short stable digest of a plan-cache key — the currency warm-plan
    reporting and the service's plan registry trade in (the raw key is
    a deep tuple of op behavior tokens; a 20-hex token travels better
    through telemetry events and JSONL)."""
    if key is None:
        return None
    import hashlib

    return hashlib.sha1(repr(key).encode()).hexdigest()[:20]


def plan_cache_snapshot() -> List[str]:
    """Tokens of every compiled fused plan currently resident in the
    cross-run cache, LRU-oldest first. Warmup reports the delta of this
    around its passes; the service plan cache uses it to answer "is
    this suite's plan already warm?" without re-tracing anything."""
    return [plan_token_of(k) for k in _PLAN_CACHE.keys()]


def _init_states(dataset: Dataset, ops: Sequence[ScanOps]) -> tuple:
    """Every op's initial state, allocated on the device before the
    first batch; a dataset's fault probe (``oom_probe_of``) fails the
    allocation here as a device short of memory would."""
    probe = oom_probe_of(dataset)
    if probe is not None:
        probe("scan_init")
    return tuple(o.init() for o in ops)


def estimated_run_bytes(dataset: Dataset, engine=None) -> int:
    """Module-level convenience for admission-adjacent callers (the
    service dataset cache) that need the SAME coarse device-footprint
    estimate the admission watermark gates on, without holding an
    engine."""
    return (engine or AnalysisEngine()).estimated_run_bytes(dataset)


# live host-ingest threads (weak: a finished thread drops out on its
# own). The registry lives in engine/ingest.py so the ordered pool and
# the single prefetch worker share ONE leak probe; tests assert
# active_prefetch_workers() == [] after a cancelled scan — the
# teardown-joins-everything contract, as a probe
from deequ_tpu.engine import ingest as ingest_mod  # noqa: E402
from deequ_tpu.engine.ingest import (  # noqa: E402
    IngestPoolStats,
    ordered_ingest,
    register_ingest_thread,
    resolve_ingest_lookahead,
    resolve_ingest_workers,
)

# kept as an alias: pre-r10 code and tests registered prefetch threads
# through this name
_PREFETCH_WORKERS = ingest_mod._INGEST_THREADS


def active_prefetch_workers():
    """Host-ingest threads still alive — single-path prefetch workers
    AND ordered-pool reader/workers (leak probe for tests)."""
    return ingest_mod.active_ingest_threads()


def _prefetched(
    iterator, depth: Optional[int] = None, supervisor=None, binding=None
):
    """Run a batch iterator in a background thread with a bounded queue:
    the host-side read/convert of batch i+1 overlaps the device transfer
    and compute of batch i. Arrow/numpy work releases the GIL; all jax
    calls stay on the caller's thread.

    The queue carries tagged tuples — ``("item", x)`` / ``("error",
    exc)`` / ``("done", None)`` — so a worker-thread exception re-raises
    on the CONSUMER thread as the original exception object, original
    traceback attached (``raise exc`` preserves ``__traceback__``), in
    FIFO position: items the worker produced before dying are yielded
    first, which is what lets the resilience driver compute the failing
    batch's index as start + items_yielded.

    With a ``supervisor`` (engine/deadline.py) the consumer polls the
    queue instead of blocking forever: every EMPTY poll runs the
    cancel/deadline check and the stall detector (``on_wait`` raises
    ``ScanStalled`` once no batch has landed for ``stall_s``), and each
    item that DOES land re-arms the stall timer. Teardown releases the
    armed source-interrupt event — a worker blocked inside a hung read
    wakes, raises, notices ``stop``, and exits — then joins the worker
    so a cancelled scan leaks no thread (``active_prefetch_workers``).

    ``binding`` (``Telemetry.thread_binding()``, taken by the scan)
    puts the worker into the scan's run capture under its traversal
    span; without one the worker binds to whatever was open here."""
    import queue
    import threading

    if depth is None:
        # r10: the hard-coded depth=2 became config.ingest_depth — a
        # pure host-pipeline knob, outside the plan fingerprint
        from deequ_tpu import config

        depth = max(1, int(config.options().ingest_depth))
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = threading.Event()
    if binding is None:
        binding = get_telemetry().thread_binding()

    def put(msg) -> bool:
        # bounded put that notices an abandoned consumer — a plain
        # q.put would block forever holding batch buffers + the scanner
        while not stop.is_set():
            try:
                q.put(msg, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        with binding():
            try:
                for item in iterator:
                    # re-check AFTER a put too: the consumer's abort
                    # drain can let a blocked put succeed, and the next
                    # iteration would read+convert a whole wasted batch
                    if not put(("item", item)) or stop.is_set():
                        return
            # lint-ok: interrupt-swallow: the prefetch worker forwards
            # the exception (interrupts included) through the queue;
            # the consumer re-raises it on the scan thread
            except BaseException as exc:  # noqa: BLE001 — re-raised below
                put(("error", exc))
            else:
                put(("done", None))

    thread = threading.Thread(
        target=worker, daemon=True, name="deequ-tpu-prefetch"
    )
    register_ingest_thread(thread)
    thread.start()
    try:
        while True:
            if supervisor is None:
                tag, payload = q.get()
            else:
                try:
                    tag, payload = q.get(timeout=supervisor.poll_s())
                except queue.Empty:
                    supervisor.on_wait()  # cancel/deadline/stall check
                    continue
            if tag == "item":
                if supervisor is not None:
                    supervisor.note_arrival()
                yield payload
                continue
            if tag == "error":
                raise payload
            return  # "done"
    finally:
        stop.set()  # consumer done or raised: release the worker
        if supervisor is not None:
            # a worker blocked inside a hung source read can't see
            # ``stop`` — set its armed interrupt event so it raises out
            supervisor.release_source()
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
        thread.join(timeout=2.0)


def _supervised_iter(inner, supervisor):
    """Wrap a directly-consumed batch iterator (the resident path's
    chunk staging generator — no prefetch thread, so no queue to poll)
    with the supervisor's arrival-time stall check: a chunk that took
    longer than ``stall_s`` raises ``ScanStalled`` from ``next()``,
    where ``resilient_batches`` can classify it (raised from the loop
    BODY it would escape the retry machinery entirely)."""
    for item in inner:
        supervisor.note_arrival()
        yield item


def _prime_delta_states(states_ops, states):
    """Prime delta-aware ops (ScanOps.host_delta) with their FULL
    dictionaries on paths where no per-batch deltas flow (resident
    chunks, sharded steps). See analyzers/base.DELTA_PRIME."""
    from deequ_tpu.analyzers.base import DELTA_PRIME

    if not any(op.host_delta is not None for op in states_ops):
        return states
    return tuple(
        op.host_delta(s, DELTA_PRIME) if op.host_delta is not None else s
        for op, s in zip(states_ops, states)
    )


def _batch_donation(host_slots) -> Tuple[int, ...]:
    """donate_argnums for the streaming fused update ``(states, batch,
    consts)``. States are donated unless host-folded outputs are
    retained across iterations. The transferred batch is donated only
    on accelerators: on the CPU backend ``jax.device_put`` may alias
    the host numpy buffer zero-copy, and donating memory jax does not
    own corrupts the heap (observed as glibc malloc aborts)."""
    donate: Tuple[int, ...] = () if host_slots else (0,)
    if jax.default_backend() != "cpu":
        donate = donate + (1,)
    return donate


def _shield_device_results(states, device_slots):
    """Wrap device-result op states (collector key buffers) so the
    epilogue's packed fetch passes them through untouched — their
    arrays stay device-resident for the post-scan finalize. Also the
    one place the preallocated collector bytes are counted (once per
    run, at the epilogue)."""
    if not device_slots:
        return states
    nbytes = 0
    out = list(states)
    for i in device_slots:
        for leaf in jax.tree_util.tree_leaves(out[i]):
            if hasattr(leaf, "dtype"):
                nbytes += int(leaf.size) * np.dtype(leaf.dtype).itemsize
        out[i] = DeviceResident(out[i])
    get_telemetry().counter("engine.collector_bytes").inc(nbytes)
    return tuple(out)


def _unshield_device_results(states, device_slots):
    if not device_slots:
        return states
    out = list(states)
    for i in device_slots:
        out[i] = out[i].value
    return tuple(out)


def _structural_plan_token(
    init_states, host_slots, batch_size: int, mode: str, mesh_desc: str
) -> str:
    """Checkpoint identity for a scan plan: a digest of the carried
    state tree's STRUCTURE (treedef + leaf shapes/dtypes), the host-fold
    slots, the batch geometry and the execution mode. Deliberately NOT
    the jit plan-cache key — collector ops may opt out of plan caching
    (cache_token None/AUTO) yet must still checkpoint; structure is what
    restore actually requires to be safe. Restoring into a different
    analyzer SET with coincidentally equal structure is prevented by the
    source fingerprint + the caller wiring, not this token."""
    import hashlib

    leaves, treedef = jax.tree_util.tree_flatten(init_states)
    desc = (
        mode,
        int(batch_size),
        tuple(host_slots),
        mesh_desc,
        str(treedef),
        tuple(
            (
                tuple(getattr(leaf, "shape", ())),
                str(getattr(leaf, "dtype", type(leaf).__name__)),
            )
            for leaf in leaves
        ),
    )
    return hashlib.sha1(repr(desc).encode()).hexdigest()[:20]


def monoid_all_reduce(state, merge, axis_name: str):
    """All-reduce an arbitrary monoid state across a mesh axis: gather all
    shards' states, then fold with the analyzer's own merge. The gather
    size is static (mesh axis size), so the fold unrolls at trace time and
    XLA lowers sums to psum-like collectives."""
    gathered = jax.lax.all_gather(state, axis_name)
    n = jax.lax.psum(1, axis_name)

    def shard(i):
        return jax.tree_util.tree_map(lambda x: x[i], gathered)

    acc = shard(0)
    for i in range(1, n):
        acc = merge(acc, shard(i))
    return acc


@dataclass(frozen=True)
class ScanPlan:
    """Every STATIC decision of a fused scan, prepared ahead of (and
    independently from) execution: the ops and their column requests,
    batch geometry, data placement, the resident/streaming mode choice,
    the cross-run jit-cache key and the admission byte estimate.

    Data VALUES ride ``execute_plan`` — one plan re-executes against
    any dataset making the same static decisions, which is what lets a
    long-lived service prepare once, warm the compile, and share the
    plan across tenants and resubmissions (docs/SERVICE.md). The jitted
    executable itself lives in the module plan cache under
    ``cache_key``; ``compiled`` asks whether it is already resident."""

    ops: tuple
    host_slots: tuple
    requests: tuple
    batch_size: int
    sharding: Any
    mode: str  # "resident" | "streaming"
    cache_budget: int  # device-cache bytes backing a resident run
    cache_key: Optional[tuple]
    estimated_bytes: int
    # wire codec layer flag captured at plan time (config.wire_codecs):
    # streamed batches ship per-column narrowed dtypes, decoded in the
    # fused unpack; the resolved codec table's token extends the
    # streaming cache key so plans across the flag never share an
    # executable (docs/PERF.md "Wire diet")
    wire_codecs: bool = True

    @property
    def compiled(self) -> bool:
        """True when the fused executable for this plan is already in
        the cross-run cache (executing it will be a plan-cache hit)."""
        return self.cache_key is not None and self.cache_key in _PLAN_CACHE

    @property
    def token(self) -> Optional[str]:
        """Stable short digest identifying this plan across processes
        and telemetry (None = uncacheable plan: some op opted out)."""
        return plan_token_of(self.cache_key)


# --------------------------------------------------------------------------
# Plan composability (ROADMAP item 2: one scan, many tenants)
# --------------------------------------------------------------------------
#
# ScanPlans are sets of monoid ops over one batch geometry, so two
# compatible plans compose by op-set union: the merged plan's single
# traversal produces every member's states, and each member slices its
# own back out by op position. plan_compatibility is the predicate,
# merge_plans the union, plan_diff the inspection surface — the service
# scan coalescer (deequ_tpu/service/coalesce.py) sits on these.


def _op_dedup_token(op) -> Optional[object]:
    """The token under which an op may be deduplicated in a merged
    plan, or None when it must be kept verbatim: None/AUTO cache
    tokens mean nobody vouched for the closure's purity (the same
    contract the plan cache applies), and an unhashable token can't
    key a seen-set."""
    from deequ_tpu.analyzers.base import CACHE_TOKEN_AUTO

    token = op.cache_token
    if token is None or token is CACHE_TOKEN_AUTO:
        return None
    try:
        hash(token)
    except TypeError:
        return None
    return token


def _sharding_equal(a, b) -> bool:
    try:
        if a == b:
            return True
    except Exception:  # noqa: BLE001 — incomparable placements
        pass
    return repr(a) == repr(b)


def plan_compatibility(a: "ScanPlan", b: "ScanPlan") -> Optional[str]:
    """Why two prepared plans must NOT merge, or None when one superset
    scan can serve both. Everything a trace specializes on must match:
    batch geometry, placement, resident/streaming mode and its budget,
    and the wire-codec layer (streamed plans across the flag never
    share an executable). Dataset identity is deliberately NOT checked
    here — the caller (the coalescer's dataset_key grouping) owns it."""
    if a.batch_size != b.batch_size:
        return f"batch_size {a.batch_size} != {b.batch_size}"
    if a.mode != b.mode:
        return f"mode {a.mode} != {b.mode}"
    if a.cache_budget != b.cache_budget:
        return f"cache_budget {a.cache_budget} != {b.cache_budget}"
    if a.wire_codecs != b.wire_codecs:
        return f"wire_codecs {a.wire_codecs} != {b.wire_codecs}"
    if not _sharding_equal(a.sharding, b.sharding):
        return f"sharding {a.sharding!r} != {b.sharding!r}"
    return None


def merge_plans(*plans: "ScanPlan") -> "ScanPlan":
    """Union compatible plans into ONE superset plan whose single
    traversal computes every member's states. Cacheable ops (real
    cache_token) are deduplicated by token — two tenants both asking
    for Completeness("x") pay one op slot; opaque ops (None/AUTO
    token) are kept verbatim, mirroring the plan cache's refusal to
    vouch for them. host_slots and the cross-run cache key are
    recomputed over the merged op tuple. The merged plan inherits the
    members' mode/budget decision (compatibility already pinned them
    equal); a caller unioning MANY resident plans should re-prepare
    from the union analyzer set if the combined request set might
    outgrow the device cache budget — the service coalescer does.

    Raises ``ValueError`` on incompatible inputs (callers gate on
    :func:`plan_compatibility` first)."""
    if not plans:
        raise ValueError("merge_plans needs at least one plan")
    first = plans[0]
    for other in plans[1:]:
        reason = plan_compatibility(first, other)
        if reason is not None:
            raise ValueError(f"incompatible plans: {reason}")
    ops: List[Any] = []
    seen_ops: set = set()
    for plan in plans:
        for op in plan.ops:
            token = _op_dedup_token(op)
            if token is not None:
                if token in seen_ops:
                    continue
                seen_ops.add(token)
            ops.append(op)
    requests: List[ColumnRequest] = []
    seen_requests: set = set()
    for plan in plans:
        for request in plan.requests:
            if request not in seen_requests:
                seen_requests.add(request)
                requests.append(request)
    host_slots = tuple(
        i for i, op in enumerate(ops) if op.host_fold is not None
    )
    return ScanPlan(
        ops=tuple(ops),
        host_slots=host_slots,
        requests=tuple(requests),
        batch_size=first.batch_size,
        sharding=first.sharding,
        mode=first.mode,
        cache_budget=first.cache_budget,
        cache_key=_plan_cache_key(ops, host_slots, first.sharding),
        estimated_bytes=max(p.estimated_bytes for p in plans),
        wire_codecs=first.wire_codecs,
    )


@dataclass(frozen=True)
class PlanDiff:
    """Structural diff of two prepared plans: op behavior tokens each
    side contributes, counts of opaque (untokened) ops that can never
    be proven shared, and the compatibility verdict. ``savings`` is
    the op slots a merge would deduplicate — what the coalescer's
    telemetry calls work saved."""

    shared: tuple
    only_a: tuple
    only_b: tuple
    opaque_a: int
    opaque_b: int
    incompatible: Optional[str]

    @property
    def mergeable(self) -> bool:
        return self.incompatible is None

    @property
    def savings(self) -> int:
        return len(self.shared)


def plan_diff(a: "ScanPlan", b: "ScanPlan") -> PlanDiff:
    """Compare two plans' op sets by behavior token (order-stable on
    ``a``'s op order for ``shared``/``only_a``, ``b``'s for
    ``only_b``)."""
    tokens_a = [_op_dedup_token(op) for op in a.ops]
    tokens_b = [_op_dedup_token(op) for op in b.ops]
    set_a = {t for t in tokens_a if t is not None}
    set_b = {t for t in tokens_b if t is not None}
    return PlanDiff(
        shared=tuple(t for t in tokens_a if t in set_b),
        only_a=tuple(
            t for t in tokens_a if t is not None and t not in set_b
        ),
        only_b=tuple(
            t for t in tokens_b if t is not None and t not in set_a
        ),
        opaque_a=sum(1 for t in tokens_a if t is None),
        opaque_b=sum(1 for t in tokens_b if t is None),
        incompatible=plan_compatibility(a, b),
    )


def coalesce_key_surface() -> tuple:
    """The config-derived surface every static plan decision depends
    on, as one hashable tuple — the same knobs PLAN_KEY_COVERED_CONFIG
    documents for the plan cache, evaluated once per run submission
    instead of once per prepared plan. The service captures this onto
    each ticket at submit time; the coalescer only groups tickets with
    EQUAL surfaces, so a mid-stream ``config.configure(...)`` change
    between submissions can never smuggle two differently-planned runs
    into one superset scan."""
    from deequ_tpu import config

    opts = config.options()
    return (
        opts.engine,
        opts.batch_size,
        opts.device_cache_bytes,
        opts.wire_codecs,
        hll.register_build_path(),
    )


@dataclass
class AnalysisEngine:
    """Executes fused analyzer scans on one device or across a mesh.

    ``mesh`` — optional ``jax.sharding.Mesh`` with a ``dp`` axis; batches
    are sharded over it. ``batch_size`` — rows per fused step (static
    shape; one compile for the whole run).
    """

    mesh: Optional[Mesh] = None
    batch_size: Optional[int] = None
    dp_axis: str = "dp"
    # traces THIS run: 1 proves N analyzers fused into one program;
    # 0 means a cached plan was reused (still one fused program)
    trace_count: int = 0
    plan_cache_hit: bool = False
    # wall decomposition of the LAST run_scan — see
    # telemetry.phases.PhaseClock for the
    # bucket definitions and the attribution caveat under a saturated
    # link (VERDICT r3 next #2)
    phase_times: Optional[Dict[str, float]] = None
    # resilience (docs/RESILIENCE.md): optional mid-scan checkpointing
    # via io.state_provider.ScanCheckpointer, and the merged degradation
    # record of every run_scan since the last reset_degradation() — an
    # analysis run spans several scans (deferred fallbacks), so the
    # record accumulates rather than being overwritten per scan
    checkpointer: Optional[Any] = None
    last_degradation: Optional[ScanDegradation] = None
    # deadlines & cancellation (docs/RESILIENCE.md): optional per-run
    # time envelope and cancel token — when either is set (or a
    # graceful-shutdown handler is installed) every scan runs under a
    # ScanSupervisor + watchdog thread; an interrupt exits through the
    # checkpoint path and leaves its provenance on last_interruption
    budget: Optional[RunBudget] = None
    cancel: Optional[CancelToken] = None
    last_interruption: Optional[ScanInterruption] = None
    # LIVE degradation record of the scan currently executing (set at
    # scan start, cleared at scan end once merged into
    # last_degradation). Row-level egress probes it mid-scan so
    # quarantined units interleave into the output in source order.
    active_degradation: Optional[ScanDegradation] = None
    # LIVE row-level egress writer of the scan currently executing
    # (armed by RowSinkPlan.bind_scan_geometry, cleared when its scan
    # completes). The checkpoint writer flushes it durably BEFORE the
    # cursor is saved, and resume reconciles it with the checkpoint —
    # docs/EGRESS.md "Durable egress".
    active_egress: Optional[Any] = None

    def _resolve_placement(self, spec):
        """Data placement: the mesh sharding when meshed, the host
        platform when the engine-selection flag says "cpu", else the
        default device (None)."""
        if self.mesh is not None:
            return NamedSharding(self.mesh, spec)
        from deequ_tpu import config

        if config.options().engine == "cpu":
            try:
                return jax.devices("cpu")[0]
            except RuntimeError:
                return None
        return None

    def _resolve_batch_size(self, num_rows: int) -> int:
        size = self.batch_size
        if size is None:
            from deequ_tpu import config

            size = config.options().batch_size
        if size is None:
            size = min(max(num_rows, 1), DEFAULT_MAX_BATCH)
        if self.mesh is not None:
            ndev = self.mesh.shape[self.dp_axis]
            size = int(math.ceil(size / ndev) * ndev)
        return max(size, 1)

    def scan_row_capacity(self, dataset: Dataset) -> int:
        """Rows run_scan will feed through the ops for this dataset,
        INCLUDING the zero-padded tail of the last batch/chunk (padding
        rows carry ``__row_mask__ == False``). Collector ops preallocate
        their device key buffers from this, so the carried write offset
        can never overrun; mirrors run_scan's batch geometry exactly."""
        batch_size = self._resolve_batch_size(dataset.num_rows)
        nb = dataset.num_batches(batch_size)
        chunk_batches = min(CHUNK_BATCHES, nb)
        # resident chunks round nb up to whole chunks; streaming yields
        # exactly nb batches — the max covers whichever mode runs
        n_chunks = -(-nb // max(chunk_batches, 1))
        return max(n_chunks * chunk_batches, nb) * batch_size

    def estimated_run_bytes(self, dataset: Dataset) -> int:
        """Coarse device-footprint estimate for the admission
        controller's high-watermark gate (config.memory_watermark_bytes):
        scan row capacity x a per-row byte weight — one f64 value plus
        one mask byte per column, plus the row mask. Deliberately cheap
        and column-count based; admission needs a stable ordering
        signal, not an allocator-grade number."""
        try:
            ncols = max(1, int(dataset.num_columns))
        except Exception:  # noqa: BLE001 — columnless source: assume 1
            ncols = 1
        return self.scan_row_capacity(dataset) * (9 * ncols + 1)

    # -- resilience plumbing (docs/RESILIENCE.md) -----------------------

    def reset_degradation(self) -> None:
        """Start a fresh degradation + interruption record (the runner
        calls this once per analysis run; ``last_degradation`` /
        ``last_interruption`` then merge every scan's record until the
        next reset)."""
        self.last_degradation = None
        self.last_interruption = None
        self.active_degradation = None
        self.active_egress = None

    def _make_supervisor(self) -> Optional[ScanSupervisor]:
        """A ScanSupervisor for the next scan, or None when nothing
        supervises (no budget, no cancel token, no shutdown handler) —
        the default run pays ZERO supervision overhead: no watchdog
        thread, blocking queue gets, no per-batch checks."""
        tokens = [self.cancel]
        if shutdown_installed():
            tokens.append(shutdown_token())
        if self.budget is None and not any(
            t is not None for t in tokens
        ):
            return None
        return ScanSupervisor(self.budget, tokens)

    def _record_interruption(
        self,
        exc: ScanInterrupted,
        batch_index: int,
        row_offset: int,
        checkpointed: bool,
    ) -> None:
        kind = getattr(exc, "kind", "interrupted")
        interruption = ScanInterruption(
            kind=kind,
            reason=str(exc) or kind,
            batch_index=batch_index,
            row_offset=row_offset,
            checkpointed=checkpointed,
        )
        self.last_interruption = ScanInterruption.merge_optional(
            self.last_interruption, interruption
        )
        tm = get_telemetry()
        if kind == "deadline":
            tm.counter("engine.deadline_exceeded").inc()
        else:
            tm.counter("engine.runs_cancelled").inc()
        tm.event("run_cancelled", **interruption.to_dict())

    def _retry_policy(self) -> RetryPolicy:
        from deequ_tpu import config

        policy = config.options().scan_retry
        return policy if policy is not None else RetryPolicy()

    def _record_degradation(self, degradation: ScanDegradation) -> None:
        self.last_degradation = ScanDegradation.merge_optional(
            self.last_degradation, degradation
        )
        if self.active_degradation is degradation:
            self.active_degradation = None
        if degradation.is_degraded:
            get_telemetry().event(
                "scan_degraded",
                batches_quarantined=degradation.batches_quarantined,
                rows_skipped=degradation.rows_skipped,
                error_classes=",".join(degradation.error_classes),
            )

    def _checkpoint_setup(
        self, dataset, init_states, host_slots, batch_size: int, mode: str
    ):
        """(checkpointer, plan_token, source_fingerprint, interval) when
        checkpointing is on and the source can be fingerprinted, else
        (None, None, None, 0) — a source without a stable identity must
        not checkpoint: resume could silently fold a different dataset."""
        ckpt = self.checkpointer
        if ckpt is None:
            return None, None, None, 0
        every = ckpt.interval()
        if every <= 0:
            return None, None, None, 0
        try:
            fingerprint = dataset.fingerprint()
        except Exception:  # noqa: BLE001 — fingerprintless: no ckpt
            return None, None, None, 0
        token = _structural_plan_token(
            init_states, host_slots, batch_size, mode, str(self.mesh)
        )
        return ckpt, token, fingerprint, int(every)

    def _restore_states(self, saved, init_states):
        """Bring checkpointed states back as UNCOMMITTED host arrays:
        the first jitted step then places/replicates them exactly as it
        would the init states — committing them to a device here would
        fight the mesh path's sharded-batch executable (committed
        single-device states vs sharded inputs is a hard jit error).
        The plan token guarantees the structural match; ``init_states``
        is kept in the signature as the structure reference."""
        del init_states
        return tuple(
            jax.tree_util.tree_map(np.asarray, s)  # sync-ok: saved
            # checkpoint states are host numpy (restored from disk)
            for s in saved
        )

    def _write_checkpoint(
        self,
        ckpt,
        token: str,
        fingerprint: str,
        clock: PhaseClock,
        states,
        host_slots,
        pending,
        host_accs,
        fold_fetched,
        degradation: ScanDegradation,
        batch_index: int,
        row_offset: int,
        batch_size: int,
    ) -> None:
        """Persist one mid-scan checkpoint. Pending host-fold outputs
        are drained and folded FIRST so the saved accumulators reflect
        exactly batches [0, batch_index) in yield order — the resumed
        run then folds the remainder in the same sequence, which is what
        makes resumed metrics bit-identical even for sketches whose
        merge is only mathematically (not bitwise) order-insensitive."""
        from deequ_tpu.io.state_provider import ScanCursor

        if host_slots and pending[host_slots[0]]:
            with clock.phase("sync_s"):
                fetched = packed_device_get(pending)
            fold_fetched(fetched)
        with clock.phase("sync_s"):
            host_states = jax.device_get(states)  # sync-ok: checkpoint
            # drain — a deliberate, clock-attributed sync at the
            # checkpoint cadence, not a per-batch fetch
        # write-ahead ordering (flush THEN cursor): the sink's open
        # span segment is made durable FIRST — the folds drained above
        # routed their spans through the writer — and only then is the
        # cursor naming that durable state saved, so a resume replays
        # zero rows and drops zero rows
        egress_state = (
            self.active_egress.flush_durable()
            if self.active_egress is not None
            else None
        )
        ckpt.save(
            ScanCursor(
                batch_index,
                row_offset,
                fingerprint,
                batch_size,
                egress=egress_state,
            ),
            token,
            host_states,
            dict(host_accs),
            degradation,
        )
        tm = get_telemetry()
        tm.counter("engine.checkpoints_written").inc()
        tm.event(
            "scan_checkpoint", batch_index=batch_index, row_offset=row_offset
        )

    def _load_checkpoint(self, ckpt, token, fingerprint, batch_size, mode):
        """The resumable payload for this exact (source, plan, batch
        geometry), or None. Counts + events the resume."""
        if ckpt is None:
            return None
        payload = ckpt.load(fingerprint, token)
        if payload is None:
            return None
        cursor = payload["cursor"]
        if cursor.batch_size != batch_size or cursor.batch_index <= 0:
            return None
        tm = get_telemetry()
        tm.counter("engine.resumes").inc()
        tm.event(
            "scan_resumed",
            mode=mode,
            batch_index=cursor.batch_index,
            row_offset=cursor.row_offset,
        )
        return payload

    def run_scan(
        self,
        dataset: Dataset,
        analyzers_and_ops: Sequence[Tuple[ScanShareableAnalyzer, ScanOps]],
    ) -> List[Any]:
        """Run every analyzer's update in one fused pass over the data;
        returns final states (host-side numpy pytrees), index-aligned
        with the input.

        Two execution modes:

        - **resident** (default when the request set fits the device
          cache budget): columns are transferred once, cached on device
          as ``(num_batches, batch_size)`` stacks, and the WHOLE pass
          runs as one jitted ``lax.scan`` — a single dispatch, no
          host->device traffic on re-scan (see deequ_tpu.config).
        - **streaming**: host batches fed one jit call at a time;
          memory bounded by O(batch x columns).

        ``run_scan`` composes the two halves of the compile/execute
        split: ``prepare_scan`` (static planning — shareable, cacheable)
        then ``execute_plan`` (data values + the supervised drive)."""
        plan = self.prepare_scan(dataset, analyzers_and_ops)
        if plan is None:
            return []
        return self.execute_plan(plan, dataset)

    def prepare_scan(
        self,
        dataset: Dataset,
        analyzers_and_ops: Sequence[Tuple[ScanShareableAnalyzer, ScanOps]],
    ) -> Optional["ScanPlan"]:
        """Phase 1 of a fused scan: resolve every STATIC decision —
        column requests, batch geometry, placement, resident/streaming
        mode, the cross-run jit-cache key, the admission byte estimate —
        WITHOUT touching data values or dispatching anything. The
        returned ScanPlan is a first-class object: hold it, inspect
        ``plan.compiled``/``plan.token``, execute it repeatedly."""
        if not analyzers_and_ops:
            return None
        from deequ_tpu import config

        with get_telemetry().span("plan:prepare"):
            opts = config.options()
            requests: List[ColumnRequest] = []
            for analyzer, _ in analyzers_and_ops:
                requests.extend(analyzer.device_requests(dataset))
            ops = tuple(o for _, o in analyzers_and_ops)
            host_slots = tuple(
                i for i, op in enumerate(ops) if op.host_fold is not None
            )
            batch_size = self._resolve_batch_size(dataset.num_rows)
            sharding = self._resolve_placement(P(None, self.dp_axis))
            cache_budget = opts.device_cache_bytes
            chunk_batches = min(
                CHUNK_BATCHES, dataset.num_batches(batch_size)
            )
            resident = bool(cache_budget) and dataset.estimated_device_bytes(
                requests,
                batch_size,
                chunk_batches,
                derive_lengths=sharding is None,
            ) <= cache_budget
            return ScanPlan(
                ops=ops,
                host_slots=host_slots,
                requests=tuple(requests),
                batch_size=batch_size,
                sharding=sharding,
                mode="resident" if resident else "streaming",
                cache_budget=cache_budget if resident else 0,
                cache_key=_plan_cache_key(ops, host_slots, sharding),
                estimated_bytes=self.estimated_run_bytes(dataset),
                wire_codecs=opts.wire_codecs,
            )

    def execute_plan(
        self, plan: "ScanPlan", dataset: Dataset
    ) -> List[Any]:
        """Phase 2: drive a prepared plan over a dataset's values under
        the engine's current envelope (budget/cancel/checkpointer). The
        jitted executable is fetched from (or installed into) the
        cross-run plan cache keyed by ``plan.cache_key`` — repeat
        executions skip both re-trace and persistent-cache
        deserialization."""
        from deequ_tpu import config

        config.install_compilation_cache()
        self.plan_cache_hit = False  # per-run flag
        tm = get_telemetry()
        tm.counter("engine.scans").inc()
        # one traversal of the source, however many analyzers ride it
        # (spill collectors included) — the paper's scan-sharing claim,
        # as a counter and a span
        with tm.traversal(
            "fused",
            rows=dataset.num_rows,
            columns=len({r.column for r in plan.requests}),
        ):
            return self._execute_plan(plan, dataset)

    def _execute_plan(
        self, plan: "ScanPlan", dataset: Dataset
    ) -> List[Any]:
        tm = get_telemetry()
        ops = list(plan.ops)
        host_slots = list(plan.host_slots)
        requests = list(plan.requests)
        batch_size = plan.batch_size
        sharding = plan.sharding
        # full size until an OOM shrinks it (engine/memory.py)
        tm.metrics.gauge("engine.batch_rows_effective").set(batch_size)

        supervisor = self._make_supervisor()
        if supervisor is not None:
            try:
                supervisor.check()
            # lint-ok: interrupt-named: sanctioned clean
            # exit — checkpoint + partial result is the
            # tunnel's designed terminus (docs/RESILIENCE.md)
            except ScanInterrupted as exc:
                # envelope already dead (deadline burned by an earlier
                # scan of the same run, or cancelled before start):
                # never touch the source — record the interrupt and
                # return pristine init states (zero batches scanned)
                self._record_interruption(
                    exc, batch_index=0, row_offset=0, checkpointed=False
                )
                host = set(host_slots)
                return [
                    op.host_init()
                    if i in host
                    else jax.device_get(op.init())  # sync-ok: pristine
                    # init on deadline-before-start; runs once, inits
                    # are host numpy for every builtin op
                    for i, op in enumerate(ops)
                ]
            supervisor.start_watchdog()
        try:
            if plan.mode == "resident":
                return self._run_scan_resident(
                    dataset, ops, host_slots, requests, batch_size,
                    sharding, plan.cache_budget, supervisor,
                )
            return self._run_scan_streaming(
                dataset, ops, host_slots, requests, batch_size, supervisor,
                wire_codecs=plan.wire_codecs,
            )
        finally:
            if supervisor is not None:
                supervisor.stop_watchdog()
                # stall events detected on the watchdog thread surface
                # here, on the scan thread, inside any live run capture
                supervisor.flush_stall_events()

    def _run_scan_resident(
        self,
        dataset: Dataset,
        ops: Sequence[ScanOps],
        host_slots: List[int],
        requests: List[ColumnRequest],
        batch_size: int,
        sharding,
        budget_bytes: int,
        supervisor: Optional[ScanSupervisor] = None,
    ) -> List[Any]:
        """Chunk-pipelined resident scan: the dataset yields device
        chunks of ``(CHUNK_BATCHES, batch_size)`` stacks; each chunk's
        jitted ``lax.scan`` call dispatches async, so chunk i's compute
        overlaps chunk i+1's host->device transfer — fresh-data wall is
        max(transfer, compute), and a re-scan replays the cached chunks
        with zero transfers."""
        nb = dataset.num_batches(batch_size)
        chunk_batches = min(CHUNK_BATCHES, nb)
        # LUT consts transfer ONCE; host numpy arguments would re-ship
        # to the device on every per-chunk call
        consts = jax.device_put(tuple(op.consts or {} for op in ops))
        settle = _step_settler(sharding)

        self.trace_count = 0

        mesh, axis = self.mesh, self.dp_axis

        def make_entry(entry: _PlanEntry) -> None:
            def scan_chunk(state_tuple, stacked, consts_tuple):
                def body(carry, batch):
                    entry.traces += 1
                    new = tuple(
                        op.apply_update(s, batch, c)
                        for op, s, c in zip(ops, carry, consts_tuple)
                    )
                    ys = tuple(new[i] for i in host_slots)
                    return new, ys

                with hll.rows_sharded_over(mesh, axis):
                    return jax.lax.scan(body, state_tuple, stacked)

            # state buffers round-trip between chunk calls; donating the
            # carry would corrupt host-folded ys that alias it (see the
            # streaming path), so donate only when nothing is retained
            entry.fn = jax.jit(
                scan_chunk, donate_argnums=() if host_slots else (0,)
            )

        entry, hit = _plan_cache_lookup(
            _plan_cache_key(ops, host_slots, sharding), make_entry
        )
        self.plan_cache_hit = self.plan_cache_hit or hit

        states = _init_states(dataset, ops)
        # delta-aware ops (LUTs in state, fed by streamed dictionary
        # deltas) see no deltas on the resident path — prime them with
        # the full dictionaries, or their LUTs silently stay empty
        states = _prime_delta_states(ops, states)
        traces_before = entry.traces

        # host-folded per-batch outputs (stacked per chunk) are drained
        # in blocks: one packed fetch per drain keeps device round trips
        # rare while retained device memory stays O(drain x output)
        pending: Dict[int, List[Any]] = {i: [] for i in host_slots}
        host_accs = {i: ops[i].host_init() for i in host_slots}
        drain_every = 32  # chunks

        def fold_fetched(fetched: Dict[int, List[Any]]) -> None:
            """Fold already-FETCHED stacked per-chunk outputs into the
            host accumulators (the one copy of the unstack+fold loop)."""
            if not host_slots:
                return
            with get_telemetry().span("scan:fold"):
                for i in host_slots:
                    acc = host_accs[i]
                    for ys_chunk in fetched[i]:
                        leaves, treedef = jax.tree_util.tree_flatten(
                            ys_chunk
                        )
                        cb = leaves[0].shape[0] if leaves else 0
                        for b in range(cb):
                            acc = ops[i].host_fold(
                                acc,
                                treedef.unflatten(
                                    [leaf[b] for leaf in leaves]
                                ),
                            )
                    host_accs[i] = acc
                    pending[i] = []

        # resilience: retry/quarantine at CHUNK granularity (the unit
        # the staging generator yields), checkpoint/resume at stable
        # absolute chunk boundaries — see docs/RESILIENCE.md
        n = dataset.num_rows
        chunk_rows = chunk_batches * batch_size
        degradation = ScanDegradation()
        policy = self._retry_policy()
        ckpt, token, fingerprint, every = self._checkpoint_setup(
            dataset, states, host_slots, batch_size, "resident"
        )
        ckpt_every_chunks = (
            max(1, -(-every // max(chunk_batches, 1))) if every else 0
        )
        start_chunk = 0
        payload = self._load_checkpoint(
            ckpt, token, fingerprint, batch_size, "resident"
        )
        if self.active_egress is not None:
            # reconcile the sink with the checkpoint BEFORE restoring:
            # either the durable egress cursor is trusted (resume, zero
            # rows replayed) or the artifact restarts fresh alongside
            # the scan (payload degraded to None)
            payload = self.active_egress.align_resume(payload)
        if payload is not None:
            start_chunk = payload["cursor"].batch_index // max(
                chunk_batches, 1
            )
            states = self._restore_states(payload["states"], states)
            for i in host_slots:
                host_accs[i] = payload["host_accs"][i]
            degradation = payload["degradation"] or degradation
        # live record for mid-scan probes (row-level egress interleaves
        # quarantined units in source order); merged + cleared at scan end
        self.active_degradation = degradation

        def rows_for(ci: int) -> int:
            return max(0, min(n - ci * chunk_rows, chunk_rows))

        def make_iter(ci: int):
            kwargs = dict(
                chunk_batches=chunk_batches,
                sharding=sharding,
                budget_bytes=budget_bytes,
            )
            # legacy/custom datasets need not accept start_chunk; only
            # pass it on a restart or resume
            if ci > 0:
                kwargs["start_chunk"] = ci
            src = dataset.device_scan_chunks(
                requests, batch_size, **kwargs
            )
            if supervisor is not None:
                # fresh stall window + fresh interrupt event per
                # iterator (re)start: a retry must neither inherit the
                # stalled timer nor the consumed event of the last stall
                supervisor.reset_progress()
                attach = getattr(dataset, "attach_interrupt", None)
                if attach is not None:
                    attach(supervisor.arm_source())
                src = _supervised_iter(src, supervisor)
            return src

        clock = PhaseClock("resident")
        clock.trace_counter = lambda: entry.traces
        clock.first_step_attrs["hll_path"] = hll.register_build_path()
        self.phase_times = clock.times

        # memory pressure (engine/memory.py, docs/RESILIENCE.md): a
        # dispatch whose allocation fails is re-fed through sub-slices
        # at a geometrically halved effective size. Zero-cost default:
        # no OOM -> one `backoff.active` check per chunk, nothing else
        backoff = make_backoff(
            batch_size,
            align=(
                self.mesh.shape[self.dp_axis]
                if self.mesh is not None
                else 1
            ),
        )
        probe = oom_probe_of(dataset)

        def subdispatch_chunk(ci: int, chunk) -> bool:
            """Re-feed ONE received chunk through ``(1, effective)``-row
            sub-slices of the SAME cached jit (jax retraces per input
            shape), chaining states sequentially — the exact update/fold
            sequence a run natively configured at the smaller batch size
            executes, so metrics stay bit-identical (the differential
            oracle in tests/test_memory_pressure.py). Exhausted backoff
            quarantines the unit's remaining rows (PR 3 path). Returns
            False when the unit saw an OOM — heal accounting."""
            nonlocal states
            unit_rows = rows_for(ci)
            clean = True
            for b in range(chunk_batches):
                r = 0
                while r < batch_size:
                    w = min(backoff.effective, batch_size - r)
                    try:
                        if probe is not None:
                            probe("dispatch", ci, w)
                        sub = jax.tree_util.tree_map(
                            lambda x: x[b:b + 1, r:r + w], chunk
                        )
                        with clock.phase("dispatch_s"):
                            states, ys = settle(
                                entry.fn(states, sub, consts)
                            )
                        for pos, i in enumerate(host_slots):
                            pending[i].append(ys[pos])
                        r += w
                    except Exception as exc:  # noqa: BLE001 — classified
                        pressure = classify_memory_pressure(exc)
                        if pressure is None:
                            raise
                        record_memory_pressure("dispatch", ci, w, pressure)
                        clean = False
                        if not backoff.shrink("dispatch", ci):
                            folded = min(unit_rows, b * batch_size + r)
                            degradation.record_quarantine(
                                ci,
                                max(0, unit_rows - folded),
                                BackoffExhausted(str(pressure)),
                                1,
                            )
                            return False
            return clean

        def dispatch_chunk(ci: int, chunk) -> bool:
            """One chunk through the fused plan, OOM-guarded. Returns
            True when the unit completed without memory pressure."""
            nonlocal states
            if backoff is not None and backoff.active:
                return subdispatch_chunk(ci, chunk)
            try:
                if probe is not None:
                    probe("dispatch", ci, batch_size)
                with clock.phase("dispatch_s"):
                    states, ys = settle(
                        entry.fn(states, chunk, consts)
                    )
                for pos, i in enumerate(host_slots):
                    pending[i].append(ys[pos])
                return True
            except Exception as exc:  # noqa: BLE001 — classified below
                pressure = (
                    classify_memory_pressure(exc)
                    if backoff is not None
                    else None
                )
                if pressure is None:
                    raise  # not memory pressure (or backoff disabled)
                record_memory_pressure("dispatch", ci, batch_size, pressure)
                if not backoff.shrink("dispatch", ci):
                    degradation.record_quarantine(
                        ci,
                        rows_for(ci),
                        BackoffExhausted(str(pressure)),
                        1,
                    )
                    return False
                return subdispatch_chunk(ci, chunk)

        states = _mesh_carry(states, sharding)
        # chunks [0, done_chunks) are ACCOUNTED — folded into states or
        # quarantined — which is exactly what an interrupt checkpoints
        done_chunks = start_chunk
        interrupted: Optional[ScanInterrupted] = None
        batch_iter = clock.timed_iter(
            resilient_batches(
                make_iter, policy, degradation, rows_for,
                start=start_chunk,
            )
        )
        try:
            # host_wait here covers the chunk staging generator: host
            # column slice/packbits + device_put DISPATCH (transfers
            # stream async)
            for ci, chunk in batch_iter:
                done_chunks = ci  # received, not yet folded
                if supervisor is not None:
                    supervisor.check()
                if dispatch_chunk(ci, chunk) and backoff is not None:
                    backoff.note_clean()
                done_chunks = ci + 1
                if host_slots and len(pending[host_slots[0]]) >= drain_every:
                    with clock.phase("sync_s"):
                        fetched = packed_device_get(pending)
                    fold_fetched(fetched)
                if ckpt is not None and (ci + 1) % ckpt_every_chunks == 0:
                    self._write_checkpoint(
                        ckpt, token, fingerprint, clock, states, host_slots,
                        pending, host_accs, fold_fetched, degradation,
                        batch_index=(ci + 1) * chunk_batches,
                        row_offset=min(n, (ci + 1) * chunk_rows),
                        batch_size=batch_size,
                    )
        # lint-ok: interrupt-named: sanctioned clean
        # exit — checkpoint + partial result is the
        # tunnel's designed terminus (docs/RESILIENCE.md)
        except ScanInterrupted as exc:
            # cancel / deadline: exit CLEANLY — persist a final cursor
            # so the run is resumable, record provenance, then fall
            # through the NORMAL epilogue so partial metrics (batches
            # [0, done_chunks * chunk_batches)) still come back
            batch_iter.close()  # run the source generator's teardown NOW
            interrupted = exc
            checkpointed = False
            if ckpt is not None and done_chunks > 0:
                self._write_checkpoint(
                    ckpt, token, fingerprint, clock, states, host_slots,
                    pending, host_accs, fold_fetched, degradation,
                    batch_index=done_chunks * chunk_batches,
                    row_offset=min(n, done_chunks * chunk_rows),
                    batch_size=batch_size,
                )
                checkpointed = True
            self._record_interruption(
                exc,
                batch_index=done_chunks * chunk_batches,
                row_offset=min(n, done_chunks * chunk_rows),
                checkpointed=checkpointed,
            )

        if ckpt is not None and interrupted is None:
            ckpt.clear(token)  # completed: a stale cursor must not linger
        self._record_degradation(degradation)
        self.trace_count = entry.traces - traces_before
        # ONE packed fetch for the remaining host-fold outputs AND the
        # final carries together — each separate device sync is a
        # full host<->device round trip, so the epilogue must not pay
        # two back to back. Device-result states (collector
        # key buffers) are shielded from the fetch and stay resident.
        device_slots = [i for i, op in enumerate(ops) if op.device_result]
        with clock.phase("sync_s"):
            fetched_pending, states = packed_device_get(
                (pending, _shield_device_results(states, device_slots))
            )
        states = _unshield_device_results(states, device_slots)
        fold_fetched(fetched_pending)
        self._finish_scan_telemetry(clock)
        return [
            host_accs[i] if i in host_accs else s
            for i, s in enumerate(states)
        ]

    def _finish_scan_telemetry(self, clock: PhaseClock) -> None:
        """End-of-scan bookkeeping: the wall-decomposition event (one
        ``scan_phases`` record per run_scan, captured per-run and
        JSONL-logged; its buckets are the walls of the scan's live
        ``scan:*`` spans) plus retrace accounting."""
        tm = get_telemetry()
        if self.trace_count:
            tm.counter("engine.traces").inc(self.trace_count)
        tm.event("scan_phases", **clock.times)

    def _run_scan_streaming(
        self,
        dataset: Dataset,
        ops: Sequence[ScanOps],
        host_slots: List[int],
        requests: List[ColumnRequest],
        batch_size: int,
        supervisor: Optional[ScanSupervisor] = None,
        wire_codecs: bool = True,
    ) -> List[Any]:
        states = _init_states(dataset, ops)
        # LUTs transfer ONCE; passing host numpy would re-copy per batch
        consts = jax.device_put(tuple(op.consts or {} for op in ops))
        # taken under the traversal span, before any scan:* span opens:
        # ingest threads join this run and parent to the traversal
        binding = get_telemetry().thread_binding()

        sharding = self._resolve_placement(P(self.dp_axis))
        # wire diet (skipped under explicit sharding, where packed
        # widths may not divide the mesh): masks ship bit-packed and
        # expand in the jitted prologue; the row mask ships as ONE i32
        # width scalar (it is always a prefix of True rows); an all-valid
        # column's mask equals the row mask and ships as a 0-d sentinel.
        # The sentinel decision is STATIC per column (null_count == 0),
        # never per-batch content — a data-dependent pytree structure
        # would recompile the fused update per structure.
        #
        # On top of the byte diet, every batch ships as ONE contiguous
        # buffer PER DTYPE (layout below): a ~20-leaf batch dict put
        # leaf-by-leaf pays the per-transfer latency ~20x per batch —
        # packing makes a batch 3-5 large transfers whose cost is
        # bandwidth, not round trips.
        pack = sharding is None
        alias_keys = set()
        if pack:
            for r in requests:
                try:
                    if dataset._is_all_valid(r.column):
                        alias_keys.add(f"{r.column}::mask")
                except Exception:  # noqa: BLE001 — alias is optional
                    pass

        # per-column wire codecs (engine/wire.py, docs/PERF.md "Wire
        # diet"): dtypes narrowed ONCE per run from parquet stats or a
        # first-batch probe; decode is folded into the fused unpack so
        # device programs see canonical dtypes bit-identically. A batch
        # that violates a resolved codec widens the table (version
        # bump) and the wire + fused jit rebuild under a new plan key.
        codec_table = wire_mod.resolve_codecs(
            dataset, requests, enabled=wire_codecs and pack
        )

        def make_wire(wire_rows: int):
            """The packed wire format for one batch width: (pack,
            unpack, layout). The scan's nominal width gets the main
            wire; OOM backoff builds narrower wires on demand
            (identical layout structure at the smaller width, so the
            sub-jits compile the exact program a natively-smaller-batch
            run would — the bit-equality the differential oracle
            checks)."""
            # layout: per-dtype ordered (key, size) lists, built from
            # the FIRST batch's (deterministic) leaves; identical for
            # every batch of the run, so the fused jit compiles once
            # against it. r10: several pool workers can race the first
            # pack of a fresh wire, so the build is locked + re-checked
            # (build_layout is deterministic over sorted keys — the
            # lock prevents double-append, not divergent layouts)
            wire_layout: Dict[str, List[Tuple[str, int]]] = {}
            layout_lock = threading.Lock()

            def build_layout(masked: Dict[str, np.ndarray]) -> None:
                for k in sorted(masked):
                    v = masked[k]
                    if v.ndim == 0:
                        continue  # sentinels never ship
                    wire_layout.setdefault(
                        np.dtype(v.dtype).name, []
                    ).append((k, v.size))

            def wire_pack(batch):
                """bool masks -> packed bits / sentinels, then ONE
                buffer per dtype in layout order + the row-width
                scalar. Also the packed path's integrity gate: a
                short/corrupt batch raises BatchIntegrityError here (on
                the prefetch thread) instead of silently retracing the
                fused jit against a new shape — the resilience driver
                quarantines it (docs/RESILIENCE.md)."""
                if not pack:
                    return batch
                row = batch[ROW_MASK]
                if row.shape != (wire_rows,):
                    raise BatchIntegrityError(
                        f"streamed batch row mask has shape {row.shape},"
                        f" want ({wire_rows},) — truncated or corrupt "
                        "batch"
                    )
                width = int(row.sum())
                # the width-scalar encoding REQUIRES a True-prefix row
                # mask; a pre-filtered source yielding interior-masked
                # rows would otherwise corrupt every metric silently
                # (ADVICE r3)
                if not bool(row[:width].all()):
                    raise ValueError(
                        "streamed batch row mask must be a True-prefix "
                        "(interior-masked rows are not supported on the "
                        "packed wire format)"
                    )
                masked: Dict[str, np.ndarray] = {}
                for k, v in batch.items():
                    if k == ROW_MASK or k.startswith(DICT_DELTA_PREFIX):
                        # dictionary-delta payloads are host-only: the
                        # consumer pops them off the RAW batch and folds
                        # them into op states; they never ship
                        continue
                    if k.endswith("::mask") and v.dtype == bool:
                        if k in alias_keys:  # static: column sans nulls
                            masked[k] = np.uint8(0)  # sentinel
                        else:
                            masked[k] = np.packbits(v, bitorder="little")
                    else:
                        # per-column codec: narrowed wire dtype, guarded
                        # every batch (CodecViolation -> widen + re-pack
                        # in pack_versioned, never an integrity error)
                        masked[k] = codec_table.encode(k, v)
                if not wire_layout:
                    with layout_lock:
                        if not wire_layout:
                            build_layout(masked)
                # every batch must match the first batch's layout
                # exactly (the builder's own batch trivially does); a
                # size drift is data corruption, and shipping it would
                # recompile the fused update against garbage
                for entries in wire_layout.values():
                    for k, size in entries:
                        leaf = masked.get(k)
                        if leaf is None or (
                            leaf.ndim and leaf.size != size
                        ):
                            raise BatchIntegrityError(
                                f"streamed batch leaf {k!r} has "
                                "size "
                                f"{None if leaf is None else leaf.size},"
                                f" want {size} — corrupt batch"
                            )
                out = {"__row_width__": np.int32(width)}
                for dtype_name, entries in wire_layout.items():
                    parts = [np.ravel(masked[k]) for k, _ in entries]
                    out[f"__buf_{dtype_name}__"] = (
                        parts[0]
                        if len(parts) == 1
                        else np.concatenate(parts)
                    )
                # wire-diet accounting: what shipped vs what the same
                # batch would cost at canonical widths (the codecs-off
                # wire) — obs_report's "wire diet" line reads these
                encoded_nb = 4  # the row-width scalar
                raw_nb = 4
                for entries in wire_layout.values():
                    for k, _ in entries:
                        leaf = masked[k]
                        encoded_nb += leaf.nbytes
                        raw_nb += (
                            leaf.nbytes
                            if k.endswith("::mask")
                            else codec_table.raw_bytes_of(k, leaf)
                        )
                tm = get_telemetry()
                tm.counter("engine.wire_bytes_encoded").inc(encoded_nb)
                tm.counter("engine.wire_bytes_raw").inc(raw_nb)
                return out

            # decode targets frozen at wire-build time: any key with a
            # codec converts back to its canonical dtype inside the jit
            # (a no-op convert when the probe resolved to identity) —
            # frozen so a concurrent widen on the prefetch thread can
            # never change what an already-built unpack decodes to
            decode_to = {
                k: c.canonical for k, c in codec_table.codecs.items()
            }

            def wire_unpack(batch):
                import jax.numpy as jnp

                from deequ_tpu.data.table import _unpack_mask_bits

                if not pack:
                    return batch
                row = (
                    jax.lax.iota(jnp.int32, wire_rows)
                    < batch["__row_width__"]
                )
                out = {ROW_MASK: row}
                for k in alias_keys:
                    out[k] = row
                mask_keys: List[str] = []
                mask_pieces = []
                for dtype_name, entries in wire_layout.items():
                    buf = batch[f"__buf_{dtype_name}__"]
                    off = 0
                    for k, size in entries:
                        piece = buf[off:off + size]
                        off += size
                        if k.endswith("::mask"):  # packed mask bits
                            mask_keys.append(k)
                            mask_pieces.append(piece)
                        else:
                            target = decode_to.get(k)
                            out[k] = (
                                piece
                                if target is None
                                else piece.astype(target)
                            )
                if mask_pieces:
                    # ONE fused unpack for every packed mask on the
                    # wire: each piece is ceil(wire_rows/8) u8 (sizes
                    # pinned by the layout check in wire_pack), so
                    # they stack into a (n_masks, packed) block and a
                    # single shift/and/reshape expands all of them.
                    # Previously one unpack per column — 40 separate
                    # broadcast+and kernels per step at profile width,
                    # a measured slice of the in-engine KLL overhead
                    # (docs/PERF.md "KLL unit decomposition")
                    unpacked = _unpack_mask_bits(
                        jnp.stack(mask_pieces), wire_rows
                    )
                    for i, k in enumerate(mask_keys):
                        out[k] = unpacked[i]
                return out

            return wire_pack, wire_unpack, wire_layout

        def build_wire(width: int):
            """One wire version: (pack, unpack, layout) plus the codec
            table's token/version it was built against. A widening bump
            invalidates it and pack_versioned rebuilds."""
            p, u, layout = make_wire(width)
            return {
                "pack": p,
                "unpack": u,
                "layout": layout,
                "version": codec_table.version,
                "token": codec_table.token(),
            }

        # main-width wires cached BY CODEC VERSION under a lock: pool
        # workers may pack concurrently while a widening bumps the
        # version, and each version's wire must be built exactly once
        # (its layout freeze + the fused jit are keyed on it). Old
        # versions stay cached so an in-flight batch packed pre-widen
        # can still be identified (and re-packed) at ordered release.
        wire_cache_lock = threading.Lock()
        wires_by_version: Dict[int, dict] = {}

        def wire_for_current() -> dict:
            v = codec_table.version
            w = wires_by_version.get(v)
            if w is None:
                with wire_cache_lock:
                    w = wires_by_version.get(v)
                    if w is None:
                        w = wires_by_version[v] = build_wire(batch_size)
            return w

        def pack_versioned(b, stats: Optional[IngestPoolStats] = None):
            """Ingest-thread pack under the CURRENT codec table, as the
            span ``ingest:encode`` (its wall is the pool's
            ``encode_s``). A CodecViolation means a narrowing bet lost
            — widen the table, rebuild the wire, re-pack the SAME
            batch. It must never escape into resilient_batches, which
            would misread a perfectly good batch as corruption and
            quarantine it."""
            with get_telemetry().span("ingest:encode") as sp:
                while True:
                    w = wire_for_current()
                    try:
                        packed = w, w["pack"](b)
                        break
                    except wire_mod.CodecViolation as violation:
                        codec_table.widen(
                            violation.key, violation.required
                        )
            if stats is not None:
                stats.add("encode_s", sp.wall_s)
            return packed

        # the jitted update and its lifetime trace counter live together
        # in a _PlanEntry (exactly like the resident path): a cache-hit
        # run reuses the OLD closure, whose counter must not be the old
        # engine's — per-run trace counts are deltas of entry.traces.
        # A mid-run codec widening swaps in a new wire + entry, so one
        # run may consume several entries; accounting sums their deltas
        self.trace_count = 0
        settle = _step_settler(sharding)
        entries_used: List[Tuple[_PlanEntry, int]] = []

        mesh, axis = self.mesh, self.dp_axis

        def install_fused(new_entry: _PlanEntry, unpack) -> None:
            def fused_update(state_tuple, batch, consts_tuple):
                new_entry.traces += 1
                batch = unpack(batch)
                with hll.rows_sharded_over(mesh, axis):
                    return tuple(
                        op.apply_update(s, batch, c)
                        for op, s, c in zip(ops, state_tuple, consts_tuple)
                    )

            # host-folded outputs are retained across iterations, so
            # their buffers must not be donated back into the next
            # step; the transferred batch IS donated — compute consumes
            # it exactly once, and with the depth-2 put pipeline the
            # device would otherwise hold staged + in-flight copies.
            # EXCEPT on the CPU backend, where device_put may alias the
            # host numpy buffer zero-copy: donating memory jax does not
            # own corrupts the heap. Accelerator puts always copy.
            new_entry.fn = jax.jit(
                fused_update,
                donate_argnums=_batch_donation(host_slots),
            )

        fused = None  # created (or fetched from the plan cache) once
        # the first batch has fixed the layout — see the loop below
        cur_wire: Optional[dict] = None

        # per-batch device outputs for host-folded analyzers: folded in
        # CHUNKS so async dispatch keeps the pipeline full between
        # drains, while device memory for retained outputs stays bounded
        # at O(chunk x output) no matter how many batches stream by
        # (default profiles carry KLL outputs of (C, k) floats/batch).
        # Each drain is one packed fetch (a device round trip), so the
        # chunk length trades sync count against retained memory.
        pending: Dict[int, List[Any]] = {i: [] for i in host_slots}
        host_accs = {i: ops[i].host_init() for i in host_slots}
        drain_every = 64

        def fold_fetched(fetched: Dict[int, List[Any]]) -> None:
            """Fold already-FETCHED per-batch outputs into the host
            accumulators (the one copy of the fold loop)."""
            if not host_slots:
                return
            with get_telemetry().span("scan:fold"):
                for i in host_slots:
                    acc = host_accs[i]
                    for out in fetched[i]:
                        acc = ops[i].host_fold(acc, out)
                    host_accs[i] = acc
                    pending[i] = []

        # resilience: retry/quarantine at BATCH granularity with
        # checkpoint/resume at absolute batch boundaries — see
        # docs/RESILIENCE.md
        n = dataset.num_rows
        degradation = ScanDegradation()
        policy = self._retry_policy()
        ckpt, token, fingerprint, every = self._checkpoint_setup(
            dataset, states, host_slots, batch_size, "streaming"
        )
        start_batch = 0
        payload = self._load_checkpoint(
            ckpt, token, fingerprint, batch_size, "streaming"
        )
        if self.active_egress is not None:
            # reconcile the sink with the checkpoint BEFORE restoring:
            # either the durable egress cursor is trusted (resume, zero
            # rows replayed) or the artifact restarts fresh alongside
            # the scan (payload degraded to None)
            payload = self.active_egress.align_resume(payload)
        if payload is not None:
            start_batch = payload["cursor"].batch_index
            states = self._restore_states(payload["states"], states)
            for i in host_slots:
                host_accs[i] = payload["host_accs"][i]
            degradation = payload["degradation"] or degradation
        states = _mesh_carry(states, sharding)
        # live record for mid-scan probes (row-level egress interleaves
        # quarantined units in source order); merged + cleared at scan end
        self.active_degradation = degradation

        def rows_for(i: int) -> int:
            return max(0, min(n - i * batch_size, batch_size))

        # r10 ordered ingest pool (engine/ingest.py): workers > 1 fans
        # Arrow decode + host pack + wire encode over N threads with
        # strictly-ordered release; workers == 1 keeps the single
        # prefetch thread — the pre-r10 code path, byte for byte, which
        # is the differential oracle the pool tests pin against.
        # Pool engagement requires the dataset to OPT IN at class level
        # (``supports_parallel_ingest`` + an ``ingest_work_items``
        # planner): a ``__getattr__``-delegating wrapper (fault
        # injection, caches) that overrides ``device_batches`` without
        # defining its own planner must NOT be silently bypassed.
        from deequ_tpu import config as config_mod

        _ingest_opts = config_mod.options()
        ingest_workers = resolve_ingest_workers(
            _ingest_opts.ingest_workers
        )
        ingest_lookahead = resolve_ingest_lookahead(
            _ingest_opts.ingest_lookahead, ingest_workers
        )
        # declared at CLASS level (attribute or property — never via
        # __getattr__ delegation, which dir() does not surface), with
        # the truth value read off the instance (the fault wrapper's
        # property answers for its inner dataset)
        use_pool = (
            ingest_workers > 1
            and "supports_parallel_ingest" in dir(type(dataset))
            and bool(getattr(dataset, "supports_parallel_ingest", False))
            and hasattr(dataset, "ingest_work_items")
        )

        # process-sharded global-array feed (ROADMAP item 3, the
        # SNIPPETS [2] partitioner pattern): under a mesh sharding each
        # process contributes its LOCAL rows per leaf and jax assembles
        # ONE global array. Single-process this is semantically
        # identical to device_put(v, sharding) (local == global); with
        # process_count > 1 the dataset is first swapped for its
        # row-group shard view and batch counts are exchanged so no
        # host strands its peers in the collectives
        # (ingest.process_sharded_feed).
        if sharding is not None and _ingest_opts.process_sharded_ingest:
            dataset, local_rows = ingest_mod.process_sharded_feed(
                dataset, batch_size
            )
            if local_rows != batch_size:
                # multi-process: the pool planner below would yield
                # LOCAL-width batches the global plan can't check — the
                # feed adapter owns batching, pool re-engages per-host
                # in a later revision
                use_pool = False

            def put_leaf(v):
                return jax.make_array_from_process_local_data(
                    sharding, v
                )

        else:
            local_rows = batch_size

            def put_leaf(v):
                return jax.device_put(v, sharding)

        def _arm_source() -> None:
            if supervisor is None:
                return
            # fresh stall window + fresh interrupt event per iterator
            # (re)start (see the resident path)
            supervisor.reset_progress()
            attach = getattr(dataset, "attach_interrupt", None)
            if attach is not None:
                attach(supervisor.arm_source())

        def make_iter(i: int):
            # each item is ((wire, packed), raw): the raw host batch
            # rides along so OOM backoff can re-slice the SAME rows
            # into sub-batches (identity pack when the wire doesn't
            # pack), and the wire version travels with the bytes so the
            # consumer rebuilds its fused jit exactly when a codec
            # widening changed the encoding
            if use_pool:
                return make_pool_iter(i)
            # legacy/custom datasets need not accept start_batch; only
            # pass it on a restart or resume
            if i > 0:
                src = dataset.device_batches(
                    requests, batch_size, start_batch=i
                )
            else:
                src = dataset.device_batches(requests, batch_size)
            _arm_source()
            return _prefetched(
                ((pack_versioned(b), b) for b in src),
                supervisor=supervisor,
                binding=binding,
            )

        def make_pool_iter(i: int):
            items = dataset.ingest_work_items(
                requests, batch_size, start_batch=i
            )
            _arm_source()
            stats = IngestPoolStats()

            def work(item):
                # WORKER thread: the heavy, order-free stage — Arrow ->
                # numpy reprs (+ per-batch uniques for delta columns),
                # then the wire pack when the batch needs no ordered
                # commit (no dictionary-coded columns to sequence)
                with get_telemetry().span("ingest:decode") as sp:
                    decoded = item.decode()
                stats.add("decode_s", sp.wall_s)
                if item.complete:
                    return pack_versioned(decoded, stats), decoded
                return None, decoded

            def commit(result, item):
                # CONSUMER (scan) thread, strictly in order: absorb
                # uniques into the shared _IncrementalDict and cut the
                # {start, values} delta exactly as the single-thread
                # path would, then (re-)pack anything not yet packed
                # under the CURRENT codec table — a batch packed before
                # a widening is re-packed here, so released wire
                # versions are monotone and the fused jit never flaps
                # between versions
                packed, decoded = result
                raw = item.commit(decoded)
                if (
                    packed is None
                    or packed[0]["version"] != codec_table.version
                ):
                    packed = pack_versioned(raw, stats)
                return packed, raw

            def sizer(result) -> int:
                packed, decoded = result
                nb = 0
                for v in decoded.values():
                    nb += int(getattr(v, "nbytes", 0) or 0)
                if packed is not None and packed[1] is not decoded:
                    for v in packed[1].values():
                        nb += int(getattr(v, "nbytes", 0) or 0)
                return nb

            return ordered_ingest(
                items,
                work,
                commit,
                workers=ingest_workers,
                lookahead=ingest_lookahead,
                supervisor=supervisor,
                stats=stats,
                sizer=sizer,
                binding=binding,
            )

        validate = None
        if not pack:
            # the packed path validates inside the wire's pack; the
            # explicit-sharding path ships raw leaves, so corruption is
            # caught here (quarantined WITHOUT an iterator restart)
            def validate(item) -> None:
                b = item[0][1]
                for k, v in b.items():
                    if k.startswith(DICT_DELTA_PREFIX):
                        continue  # host-only payload, any length
                    shape = getattr(v, "shape", None)
                    if shape and shape[0] != local_rows:
                        raise BatchIntegrityError(
                            f"streamed batch leaf {k!r} has leading "
                            f"dim {shape[0]}, want {local_rows} — "
                            "truncated or corrupt batch"
                        )

        from deequ_tpu.data.table import add_transfer_bytes

        clock = PhaseClock("streaming")
        clock.trace_counter = lambda: sum(
            e.traces for e, _ in entries_used
        )
        clock.first_step_attrs["hll_path"] = hll.register_build_path()
        self.phase_times = clock.times

        # memory pressure (engine/memory.py, docs/RESILIENCE.md): an
        # OOM during transfer or dispatch re-feeds the SAME raw rows
        # through sub-batches at a geometrically halved effective size
        # on per-width wires + jits of the identical fused update —
        # the program a natively-smaller-batch run compiles, so metrics
        # stay bit-identical (tests/test_memory_pressure.py)
        backoff = make_backoff(
            batch_size,
            align=(
                self.mesh.shape[self.dp_axis]
                if self.mesh is not None
                else 1
            ),
        )
        probe = oom_probe_of(dataset)
        sub_plans: Dict[Tuple[int, int], Tuple[Any, Any]] = {}

        def sub_plan(w: int):
            """(pack, jitted update) for one sub-batch width; compiled
            lazily — the backoff path alone pays its compile cost.
            Keyed by (width, codec version): a widening invalidates
            sub-wires exactly as it invalidates the main wire."""
            plan = sub_plans.get((w, codec_table.version))
            if plan is None:
                sub_pack, sub_unpack, _ = make_wire(w)

                def sub_update(state_tuple, batch, consts_tuple):
                    batch = sub_unpack(batch)
                    with hll.rows_sharded_over(mesh, axis):
                        return tuple(
                            op.apply_update(s, batch, c)
                            for op, s, c in zip(
                                ops, state_tuple, consts_tuple
                            )
                        )

                plan = (
                    sub_pack,
                    jax.jit(
                        sub_update,
                        donate_argnums=_batch_donation(host_slots),
                    ),
                )
                sub_plans[(w, codec_table.version)] = plan
            return plan

        def stream_subdispatch(index: int, raw) -> bool:
            """Re-feed ONE streamed batch's raw host rows through
            sub-batches of the effective size (slice -> pack -> put ->
            jitted update), chaining states sequentially. Exhausted
            backoff quarantines the unit's remaining rows. Returns
            False when the unit saw an OOM — heal accounting."""
            nonlocal states
            unit_rows = rows_for(index)
            clean = True
            r = 0
            while r < batch_size:
                w = min(backoff.effective, batch_size - r)
                try:
                    if probe is not None:
                        probe("transfer", index, w)
                    sliced = {
                        k: (v[r:r + w] if getattr(v, "ndim", 0) else v)
                        for k, v in raw.items()
                        if not k.startswith(DICT_DELTA_PREFIX)
                    }
                    with get_telemetry().span("ingest:encode"):
                        while True:
                            try:
                                sub_pack, sub_fused = sub_plan(w)
                                sub = sub_pack(sliced)
                                break
                            except wire_mod.CodecViolation as violation:
                                # a sub-slice can trip a codec guard
                                # just like a full batch: widen +
                                # rebuild, same contract as
                                # pack_versioned
                                codec_table.widen(
                                    violation.key, violation.required
                                )
                    add_transfer_bytes(
                        sum(
                            np.asarray(v).nbytes  # sync-ok: host batch
                            # leaves, measured BEFORE the device put
                            for v in sub.values()
                        )
                    )
                    with clock.phase("put_s"):
                        if sharding is not None:
                            sub = {
                                k: put_leaf(v) for k, v in sub.items()
                            }
                        else:
                            sub = jax.device_put(sub)
                    if probe is not None:
                        probe("dispatch", index, w)
                    with clock.phase("dispatch_s"):
                        states = settle(
                            sub_fused(states, sub, consts)
                        )
                    for i in host_slots:
                        pending[i].append(states[i])
                    r += w
                except Exception as exc:  # noqa: BLE001 — classified
                    pressure = classify_memory_pressure(exc)
                    if pressure is None:
                        raise
                    record_memory_pressure("dispatch", index, w, pressure)
                    clean = False
                    if not backoff.shrink("dispatch", index):
                        degradation.record_quarantine(
                            index,
                            max(0, unit_rows - min(unit_rows, r)),
                            BackoffExhausted(str(pressure)),
                            1,
                        )
                        return False
            return clean

        def put_batch(index: int, packed):
            """Transfer leg of one streamed batch: issue the (async)
            host->device put and return the device batch. The transfer
            is its own fault domain: transient put failures retry in
            place (the batch is already in host memory — no iterator
            restart needed); anything else raises to the caller for
            classification."""
            add_transfer_bytes(
                sum(
                    np.asarray(v).nbytes  # sync-ok: host wire buffers,
                    # measured BEFORE the device put
                    for v in packed.values()
                )
            )
            if probe is not None:
                probe("transfer", index, batch_size)
            with clock.phase("put_s"):
                if sharding is not None:
                    return retry_transient(
                        lambda b=packed: {
                            k: put_leaf(v) for k, v in b.items()
                        },
                        policy,
                        index,
                        degradation,
                    )
                return retry_transient(
                    lambda b=packed: jax.device_put(b),
                    policy,
                    index,
                    degradation,
                )

        def dispatch_staged(j: int, batch, raw) -> bool:
            """Device-compute leg of one staged batch (transfer already
            issued), OOM-guarded. Returns True when the unit completed
            without memory pressure (quarantines already recorded)."""
            nonlocal states
            try:
                if probe is not None:
                    probe("dispatch", j, batch_size)
                with clock.phase("dispatch_s"):
                    states = settle(
                        fused(states, batch, consts)
                    )
            except Exception as exc:  # noqa: BLE001 — classified below
                pressure = (
                    classify_memory_pressure(exc)
                    if backoff is not None
                    else None
                )
                if pressure is None:
                    raise  # not memory pressure (or backoff disabled)
                record_memory_pressure(
                    "dispatch", j, batch_size, pressure
                )
                if backoff.shrink("dispatch", j):
                    return stream_subdispatch(j, raw)
                degradation.record_quarantine(
                    j,
                    rows_for(j),
                    BackoffExhausted(str(pressure)),
                    1,
                )
                return False
            for i in host_slots:
                pending[i].append(states[i])
            return True

        # batches [0, done_batches) are ACCOUNTED — folded or
        # quarantined — which is exactly what an interrupt checkpoints.
        # A STAGED batch (transferred, not yet dispatched) is NOT
        # accounted: an interrupt drops it and resume re-reads it.
        done_batches = start_batch
        interrupted: Optional[ScanInterrupted] = None
        staged: Optional[Tuple[int, Any, Any, dict]] = None

        def pop_deltas(raw) -> dict:
            """Strip host-only dictionary-delta payloads off a raw
            batch before anything slices or ships it."""
            if not isinstance(raw, dict):
                return {}
            keys = [k for k in raw if k.startswith(DICT_DELTA_PREFIX)]
            return {
                k[len(DICT_DELTA_PREFIX):]: raw.pop(k) for k in keys
            }

        def apply_deltas(deltas: dict) -> None:
            """Fold dictionary deltas into LUT-carrying op states —
            strictly in batch order, BEFORE the batch that shipped
            them, so codes never index past the shipped LUT rows."""
            nonlocal states
            if not deltas:
                return
            tm = get_telemetry()
            for column, payload in deltas.items():
                tm.counter("engine.dict_deltas").inc()
                tm.counter("engine.dict_delta_values").inc(
                    int(len(payload["values"]))
                )
                tm.event(
                    "dictionary_delta",
                    column=column,
                    start=int(payload["start"]),
                    count=int(len(payload["values"])),
                )
            states = tuple(
                op.host_delta(s, deltas)
                if op.host_delta is not None
                else s
                for op, s in zip(ops, states)
            )

        def after_batch(j: int) -> None:
            """Drain + checkpoint bookkeeping once batch j is
            accounted (dispatched or quarantined)."""
            if host_slots and len(pending[host_slots[0]]) >= drain_every:
                with clock.phase("sync_s"):
                    fetched = packed_device_get(pending)
                fold_fetched(fetched)
            if ckpt is not None and (j + 1) % every == 0:
                self._write_checkpoint(
                    ckpt, token, fingerprint, clock, states, host_slots,
                    pending, host_accs, fold_fetched, degradation,
                    batch_index=j + 1,
                    row_offset=min(n, (j + 1) * batch_size),
                    batch_size=batch_size,
                )

        def flush_staged() -> None:
            """Complete the staged batch: apply its dictionary deltas,
            dispatch its compute (the transfer already ran while the
            PREVIOUS batch computed — the depth-2 overlap), then the
            drain/checkpoint bookkeeping. done_batches advances here."""
            nonlocal staged, done_batches
            if staged is None:
                return
            j, dev, raw_j, deltas_j = staged
            staged = None
            apply_deltas(deltas_j)
            if dispatch_staged(j, dev, raw_j) and backoff is not None:
                backoff.note_clean()
            done_batches = j + 1
            after_batch(j)

        batch_iter = clock.timed_iter(
            resilient_batches(
                make_iter,
                policy,
                degradation,
                rows_for,
                start=start_batch,
                validate=validate,
            )
        )
        try:
            for index, item in batch_iter:
                # received, not yet folded; the staged predecessor is
                # still unaccounted too
                done_batches = staged[0] if staged is not None else index
                if supervisor is not None:
                    supervisor.check()
                (batch_wire, packed), raw = item
                deltas = pop_deltas(raw)
                if fused is None or batch_wire is not cur_wire:
                    # first batch (layout fixed inside pack_versioned
                    # on the prefetch thread), or a codec widening
                    # rebuilt the wire: the OLD fused must finish the
                    # staged batch — packed under the old encoding —
                    # before the switch. A cached plan skips the Python
                    # retrace across runs; the key carries the layout,
                    # the alias set and the codec table token
                    flush_staged()
                    key = _plan_cache_key(ops, host_slots, sharding)
                    if key is not None:
                        key = key + (
                            "stream",
                            batch_size,
                            tuple(
                                (d, tuple(entries))
                                for d, entries in sorted(
                                    batch_wire["layout"].items()
                                )
                            ),
                            tuple(sorted(alias_keys)),
                            batch_wire["token"],
                        )

                    def make_entry(
                        new_entry: _PlanEntry,
                        unpack=batch_wire["unpack"],
                    ) -> None:
                        install_fused(new_entry, unpack)

                    entry, hit = _plan_cache_lookup(key, make_entry)
                    self.plan_cache_hit = self.plan_cache_hit or hit
                    entries_used.append((entry, entry.traces))
                    fused = entry.fn
                    cur_wire = batch_wire
                if backoff is not None and backoff.active:
                    # degraded (post-OOM) mode: no staging — re-feed
                    # the raw rows synchronously through sub-batches
                    flush_staged()
                    apply_deltas(deltas)
                    if stream_subdispatch(index, raw):
                        backoff.note_clean()
                    done_batches = index + 1
                    after_batch(index)
                    continue
                try:
                    dev = put_batch(index, packed)
                except Exception as exc:  # noqa: BLE001 — classified
                    flush_staged()
                    apply_deltas(deltas)
                    pressure = (
                        classify_memory_pressure(exc)
                        if backoff is not None
                        else None
                    )
                    if pressure is not None:
                        record_memory_pressure(
                            "transfer", index, batch_size, pressure
                        )
                        if backoff.shrink("transfer", index):
                            if stream_subdispatch(index, raw):
                                backoff.note_clean()
                        else:
                            degradation.record_quarantine(
                                index,
                                rows_for(index),
                                BackoffExhausted(str(pressure)),
                                1,
                            )
                    else:
                        degradation.record_quarantine(
                            index, rows_for(index), exc,
                            policy.max_attempts,
                        )
                    done_batches = index + 1
                    after_batch(index)
                    continue
                # depth-2 put pipeline: batch i's transfer was issued
                # (async) BEFORE batch i-1's compute dispatch — link
                # and device work concurrently. Now complete i-1, then
                # stage i for the next iteration (or the final flush)
                flush_staged()
                staged = (index, dev, raw, deltas)
        # lint-ok: interrupt-named: sanctioned clean
        # exit — checkpoint + partial result is the
        # tunnel's designed terminus (docs/RESILIENCE.md)
        except ScanInterrupted as exc:
            # cancel / deadline: exit CLEANLY — persist a final cursor
            # so the run is resumable, record provenance, then fall
            # through the NORMAL epilogue so partial metrics (batches
            # [0, done_batches)) still come back. Closing the iterator
            # runs _prefetched's teardown NOW (stop + release + join),
            # so a cancelled scan leaks no worker thread
            batch_iter.close()
            # the staged batch was transferred but never dispatched:
            # drop it — done_batches still points AT it, so a resume
            # re-reads exactly that batch from the source
            staged = None
            interrupted = exc
            checkpointed = False
            if ckpt is not None and done_batches > 0:
                self._write_checkpoint(
                    ckpt, token, fingerprint, clock, states, host_slots,
                    pending, host_accs, fold_fetched, degradation,
                    batch_index=done_batches,
                    row_offset=min(n, done_batches * batch_size),
                    batch_size=batch_size,
                )
                checkpointed = True
            self._record_interruption(
                exc,
                batch_index=done_batches,
                row_offset=min(n, done_batches * batch_size),
                checkpointed=checkpointed,
            )

        flush_staged()  # normal exhaustion: complete the final batch
        if ckpt is not None and interrupted is None:
            ckpt.clear(token)  # completed: a stale cursor must not linger
        self._record_degradation(degradation)
        self.trace_count = sum(
            e.traces - before for e, before in entries_used
        )
        # ONE packed fetch for remaining host-fold outputs AND final
        # carries together (every separate device sync is a
        # host<->device round trip — engine/pack.py); device-result states (collector
        # key buffers) are shielded from the fetch and stay resident
        device_slots = [i for i, op in enumerate(ops) if op.device_result]
        with clock.phase("sync_s"):
            fetched_pending, states = packed_device_get(
                (pending, _shield_device_results(states, device_slots))
            )
        states = _unshield_device_results(states, device_slots)
        fold_fetched(fetched_pending)
        self._finish_scan_telemetry(clock)
        return [
            host_accs[i] if i in host_accs else s
            for i, s in enumerate(states)
        ]

    # -- explicit-SPMD variant (shard_map), used by the multi-chip
    #    dry-run and by multi-host deployments ---------------------------

    def build_sharded_step(
        self,
        dataset: Dataset,
        analyzers_and_ops: Sequence[Tuple[ScanShareableAnalyzer, ScanOps]],
        mesh: Mesh,
    ):
        """Return a jitted shard_map step: (states, global_batch) ->
        per-op results. Demonstrates/forces the explicit collective path
        (SURVEY.md §2.6):

        - device-monoid ops (sums, min/max, HLL registers, dense
          frequency counts) come back as their monoid ALL-REDUCE over
          the mesh axis — sums lower to psum-like collectives over ICI;
        - host-folded ops (KLL) come back as an ``all_gather`` of every
          shard's per-batch output with a leading mesh-axis dimension;
          the caller folds the shards on the host exactly like the
          single-device scan folds per-batch outputs (fold is the
          sketch merge, so order never changes the metric)."""

        ops = [o for _, o in analyzers_and_ops]
        axis = self.dp_axis
        consts = tuple(op.consts or {} for op in ops)

        def step(state_tuple, batch, consts_tuple):
            new_states = tuple(
                op.apply_update(s, batch, c)
                for op, s, c in zip(ops, state_tuple, consts_tuple)
            )
            return tuple(
                jax.tree_util.tree_map(
                    lambda x: jax.lax.all_gather(x, axis), s
                )
                if op.host_fold is not None
                else monoid_all_reduce(s, op.merge, axis)
                for op, s in zip(ops, new_states)
            )

        # pytree-prefix specs: states replicated, batch sharded
        # row-wise, LUT consts replicated
        sharded = jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(P(), P(axis), P()),
            out_specs=P(),
            check_vma=False,
        )
        jitted = jax.jit(sharded)
        device_consts = jax.device_put(consts)  # transfer once

        host_fold_slots = [
            i for i, op in enumerate(ops) if op.host_fold is not None
        ]
        if not host_fold_slots:  # nothing gathered: no contract to check
            return lambda states, batch: self._sharded_call(
                jitted, states, batch, device_consts
            )

        # reference shapes for the carry contract check below
        init_shapes = {
            i: [
                tuple(leaf.shape)
                for leaf in jax.tree_util.tree_leaves(ops[i].init())
            ]
            for i in host_fold_slots
        }

        def run_step(states, batch):
            """One sharded step. CARRY CONTRACT: device-monoid op
            outputs (already all-reduced) feed back as the next step's
            carry, but HOST-FOLDED op outputs come back all-gathered
            with a leading mesh-axis dim — resolve them via
            fold_sharded_host_outputs; feeding them back would corrupt
            silently, so input shapes are checked against op.init()."""
            for i in host_fold_slots:
                want = init_shapes[i]
                got = [
                    tuple(getattr(leaf, "shape", ()))
                    for leaf in jax.tree_util.tree_leaves(states[i])
                ]
                if got != want:
                    raise ValueError(
                        "sharded step carry has gathered shapes "
                        f"{got} != init shapes {want} for a host-folded "
                        "op: per-step outputs of host-folded ops must "
                        "go through fold_sharded_host_outputs, not back "
                        "into the next step (ADVICE r3)"
                    )
            return self._sharded_call(jitted, states, batch, device_consts)

        return run_step

    def _sharded_call(self, jitted, states, batch, device_consts):
        """One explicit-SPMD step with transient-fault retry. The
        sharded driver has no batch iterator to restart, so quarantine
        stays with the caller — on exhaustion the error propagates, but
        retried transients still land in ``last_degradation``."""
        policy = self._retry_policy()
        degr = ScanDegradation()
        try:
            return retry_transient(
                lambda: jitted(states, batch, device_consts),
                policy,
                0,
                degr,
            )
        finally:
            if degr.retries:
                self.last_degradation = ScanDegradation.merge_optional(
                    self.last_degradation, degr
                )

    def fold_sharded_host_outputs(
        self,
        ops: Sequence[ScanOps],
        out_states: Sequence[Any],
        n_shards: int,
    ) -> List[Any]:
        """Resolve a sharded step's outputs into final states: host-fold
        each shard's gathered output for host-folded ops; pass device
        monoid states through unchanged."""
        final: List[Any] = []
        for op, out in zip(ops, out_states):
            if op.host_fold is None:
                final.append(out)
                continue
            acc = op.host_init()
            leaves, treedef = jax.tree_util.tree_flatten(
                jax.device_get(out)  # sync-ok: mesh host-fold
                # epilogue — once per scan, after the sharded step
            )
            for shard in range(n_shards):
                acc = op.host_fold(
                    acc,
                    treedef.unflatten([leaf[shard] for leaf in leaves]),
                )
            final.append(acc)
        return final
