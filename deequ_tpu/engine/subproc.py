"""Crash isolation: run a scan/verification in a child process.

The PR 3–5 resilience stack (retry, quarantine, checkpoint, watchdog,
OOM backoff) all lives INSIDE the process — a hard crash (SIGSEGV in
XLA, the OOM killer, a stray SIGKILL) tunnels past every layer of it
and takes the whole daemon down. ROADMAP item 1 documents exactly this
failure mode as seed-reproducible on ≥1M-row streamed runs in the CI
container. This module supplies the missing fault domain: the PROCESS.

- :class:`IsolatedRunner` — executes a picklable callable in a
  spawn-started child (JAX env inherited; the child re-applies the
  parent's ``jax_platforms`` before touching a backend), streams the
  result plus the child's telemetry run-summary back over a pipe, and
  classifies child death by exit status: death by signal (negative
  ``exitcode``) or a 128+N shell-convention status becomes
  :class:`ProcessCrashed`, a :class:`TransientScanError` subclass.
- relaunch-from-checkpoint — ``ScanCheckpointer`` cursors already
  persist to durable storage, so the runner simply relaunches the same
  callable: the scan resumes from the last cursor and the completed run
  is bit-identical to an uninterrupted one (monoid states, ordered host
  folds). A crash costs one checkpoint window, nothing more.
- crash-loop bound — ``config.crash_max_relaunches`` child launches
  WITHOUT checkpoint progress (an injectable ``progress_probe``
  observes cursor advancement between launches) declare the run a
  poison batch: :class:`CrashLoopError` is raised, which the
  verification layer floors through ``config.degradation_policy``.
- :class:`CircuitBreaker` — per-plan-key breaker registry. A declared
  crash loop trips the key's breaker OPEN; further launches for that
  key fail fast (:class:`BreakerOpen` with a retry-after hint) until
  ``crash_breaker_cooldown_s`` elapses, then ONE half-open probe is
  admitted — success closes the breaker, another crash loop re-opens
  it. Clocks are injectable (tests use ``ManualClock``).

Children are always joined and reaped — no zombies, enforced both by
``finally`` blocks here and by the ``subprocess-discipline`` static
rule (tools/staticcheck/procs.py). See docs/RESILIENCE.md "Crash
isolation and recovery".
"""

from __future__ import annotations

import multiprocessing
import os
import pickle
import signal as _signal
import threading
from typing import Any, Callable, Dict, Optional, Tuple

from deequ_tpu.engine.deadline import CancelToken, MonotonicClock
from deequ_tpu.engine.resilience import TransientScanError

#: env var the parent sets so the spawned child pins the same jax
#: platform BEFORE its backend initializes (the parent may have set
#: jax_platforms programmatically — children do not inherit jax.config)
CHILD_PLATFORM_ENV = "DEEQU_TPU_CHILD_JAX_PLATFORM"

#: env var carrying the parent's ``TraceContext.encode()`` across the
#: spawn boundary (trace_id : parent span id : process label) — the
#: only channel that survives spawn. When present, the child roots its
#: spans under the parent's span and STREAMS each finished span back
#: over the pipe as a ``("span", record)`` message ahead of the final
#: result tuple, so the parent still sees where a crashed child died.
CHILD_TRACE_ENV = "DEEQU_TPU_CHILD_TRACE"

#: env var carrying the parent replica's fleet epoch guard across the
#: spawn boundary (JSON: fleet_dir / replica / epoch, built by
#: ``FleetSupervisor.child_guard()``). A child re-reads the named lease
#: chain before each durable persist: if the chain has moved past the
#: shipped epoch the PARENT was fenced — a survivor adopted its runs —
#: and the child must stop persisting too (``child_epoch_fenced``),
#: or the zombie pair would rewind the adopter's cursors.
CHILD_EPOCH_ENV = "DEEQU_TPU_CHILD_EPOCH"


class ProcessCrashed(TransientScanError):
    """The child process died without delivering a result — killed by a
    signal or exited with a crash status. Transient ON PURPOSE: the
    checkpoint survives the crash, so a relaunch resumes the scan."""

    def __init__(
        self,
        message: str,
        *,
        exitcode: Optional[int] = None,
        signal_name: Optional[str] = None,
        launches: int = 1,
    ):
        super().__init__(message)
        self.exitcode = exitcode
        self.signal_name = signal_name
        self.launches = launches


class CrashLoopError(Exception):
    """The same work crashed the child ``crash_max_relaunches`` times
    without checkpoint progress — a poison batch / poison plan. The run
    fails cleanly (floored through ``config.degradation_policy``); the
    plan's circuit breaker is tripped."""

    def __init__(
        self,
        message: str,
        *,
        launches: int,
        last_exitcode: Optional[int] = None,
        last_signal: Optional[str] = None,
    ):
        super().__init__(message)
        self.launches = launches
        self.last_exitcode = last_exitcode
        self.last_signal = last_signal


class BreakerOpen(Exception):
    """The plan's crash-loop breaker is OPEN — the launch is rejected
    fast, without spawning a child. ``retry_after_s`` hints when the
    next half-open probe will be admitted."""

    def __init__(self, message: str, *, retry_after_s: float, key: str):
        super().__init__(message)
        self.retry_after_s = max(0.0, float(retry_after_s))
        self.key = key


class ChipHeldByParent(RuntimeError):
    """The child would need the TPU that this process already holds. A
    chip belongs to one process at a time, so such a child fails or
    hangs at backend start-up; the runner refuses before spawning
    (docs/RESILIENCE.md "One process per chip")."""


class _ChildError(RuntimeError):
    """Carrier for a child exception that did not survive pickling —
    the class name and traceback text ride back instead."""

    def __init__(self, error_class: str, message: str, traceback_text: str):
        super().__init__(f"{error_class}: {message}")
        self.error_class = error_class
        self.traceback_text = traceback_text


# --------------------------------------------------------------------------
# Circuit breaker
# --------------------------------------------------------------------------

CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"


class CircuitBreaker:
    """Crash-loop breaker for ONE plan key: closed → (crash loop) open
    → (cooldown) half-open probe → closed on success / open on failure.
    ``clock`` is anything with ``.now() -> float`` (monotonic)."""

    def __init__(self, cooldown_s: float, clock: Optional[Any] = None):
        self.cooldown_s = float(cooldown_s)
        self._clock = clock or MonotonicClock()
        self._lock = threading.Lock()
        self._state = CLOSED
        self._opened_at = 0.0
        self._probing = False

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def admit(self, key: str = "") -> None:
        """Raise :class:`BreakerOpen` unless a launch may proceed. An
        OPEN breaker past its cooldown admits exactly one HALF_OPEN
        probe; concurrent launches during the probe are rejected."""
        from deequ_tpu.telemetry import get_telemetry

        with self._lock:
            if self._state == CLOSED:
                return
            now = self._clock.now()
            elapsed = now - self._opened_at
            if self._state == OPEN and elapsed >= self.cooldown_s:
                self._state = HALF_OPEN
                self._probing = True
                get_telemetry().event("crash_breaker_half_open", key=key)
                return
            if self._state == HALF_OPEN and not self._probing:
                self._probing = True
                return
            retry_after = max(0.0, self.cooldown_s - elapsed)
            raise BreakerOpen(
                f"crash-loop breaker open for {key or 'plan'} "
                f"(retry in {retry_after:.1f}s)",
                retry_after_s=retry_after,
                key=key,
            )

    def record_success(self, key: str = "") -> None:
        from deequ_tpu.telemetry import get_telemetry

        with self._lock:
            was = self._state
            self._state = CLOSED
            self._probing = False
        if was != CLOSED:
            get_telemetry().event("crash_breaker_closed", key=key)

    def record_crash_loop(self, key: str = "") -> None:
        from deequ_tpu.telemetry import get_telemetry

        with self._lock:
            self._state = OPEN
            self._opened_at = self._clock.now()
            self._probing = False
        get_telemetry().counter("engine.breaker_trips").inc()
        get_telemetry().event(
            "crash_breaker_open", key=key, cooldown_s=self.cooldown_s
        )


_breakers: Dict[str, CircuitBreaker] = {}
_breakers_lock = threading.Lock()


def breaker_for(
    key: str,
    cooldown_s: Optional[float] = None,
    clock: Optional[Any] = None,
) -> Optional[CircuitBreaker]:
    """The process-wide breaker for a plan key (created on first use).
    None when breakers are disabled (``crash_breaker_cooldown_s <= 0``)."""
    from deequ_tpu import config

    if cooldown_s is None:
        cooldown_s = config.options().crash_breaker_cooldown_s
    if cooldown_s is None or cooldown_s <= 0:
        return None
    with _breakers_lock:
        breaker = _breakers.get(key)
        if breaker is None:
            breaker = CircuitBreaker(cooldown_s, clock=clock)
            _breakers[key] = breaker
        return breaker


def reset_breakers() -> None:
    """Drop every registered breaker (test isolation)."""
    with _breakers_lock:
        _breakers.clear()


def breaker_states() -> Dict[str, str]:
    """The state of every registered crash-loop breaker, keyed by plan
    key — surfaced in the service's ``/healthz`` payload so a fleet
    dashboard sees tripped plans without scraping events."""
    with _breakers_lock:
        items = list(_breakers.items())
    return {key: breaker.state for key, breaker in items}


# --------------------------------------------------------------------------
# Checkpoint progress probe
# --------------------------------------------------------------------------


def checkpoint_progress_probe(path: str) -> Callable[[], Tuple]:
    """A progress probe over a ``ScanCheckpointer`` directory: returns a
    callable whose value changes whenever any checkpoint cursor under
    ``path`` advances. The runner compares probe values across child
    launches — a crash that happened LATER than the previous one is
    forward progress, not a loop, and resets the relaunch budget."""

    def probe() -> Tuple:
        from deequ_tpu.io.storage import storage_for

        storage = storage_for(path)
        out = []
        for key in sorted(storage.list_keys("scan-ckpt-")):
            raw = storage.read_bytes(key)
            if raw is None:
                continue
            try:
                payload = pickle.loads(raw)
            except Exception:  # noqa: BLE001 — torn blob = no progress info
                continue
            cursor = payload.get("cursor") if isinstance(payload, dict) else None
            batch_index = getattr(cursor, "batch_index", None)
            if batch_index is not None:
                # an egress cursor advancing (new durable span segment,
                # spool bytes) is forward progress even within one
                # batch_index — include it in the probe value
                eg = getattr(cursor, "egress", None)
                if eg is not None:
                    out.append(
                        (
                            key,
                            int(batch_index),
                            int(
                                getattr(
                                    eg,
                                    "last_durably_flushed_span_seq",
                                    -1,
                                )
                            ),
                            int(getattr(eg, "plane_spool_offset", 0)),
                        )
                    )
                else:
                    out.append((key, int(batch_index)))
        return tuple(out)

    return probe


# module-global hook: QuarantineWriter.flush_durable calls this after
# every durable rotation; in a spawned child it streams an
# ``("egress", record)`` frame to the parent (installed by
# ``_child_main``), everywhere else it is a no-op
_egress_notify: Optional[Callable[[Dict[str, Any]], None]] = None


def notify_egress_progress(record: Dict[str, Any]) -> None:
    """Report a durable egress flush to whoever is listening (the
    isolation parent, via the child's pipe). Best-effort: no listener,
    no cost; a torn pipe never fails the flush."""
    hook = _egress_notify
    if hook is not None:
        hook(record)


# --------------------------------------------------------------------------
# Child side
# --------------------------------------------------------------------------


def _apply_child_platform() -> None:
    """Pin the parent's jax platform in the child BEFORE any backend
    initialization (``jax.config`` does not cross the spawn boundary;
    only the environment does)."""
    platform = os.environ.get(CHILD_PLATFORM_ENV)
    if not platform:
        return
    try:
        import jax

        jax.config.update("jax_platforms", platform)
    except Exception:  # noqa: BLE001 — missing/initialized jax: run as-is
        pass


#: the cancel token for THIS process when it runs as an isolated child
#: — ``_child_main`` mints it fresh per child and a watcher thread
#: fires it when the parent sends a cancel message down the control
#: pipe. The child's work (``service._isolated_execute``) threads it
#: into the engine as ``cancel=``, so a preemption reaches a spawned
#: scan exactly like an in-process one: clean exit at the next batch
#: boundary, final checkpoint persisted, partial result shipped back
#: over the result pipe — never a SIGKILL.
_child_cancel: Optional[CancelToken] = None


def child_cancel_token() -> CancelToken:
    """The process-global cancel token a spawned child's work observes
    (a fresh, never-fired token outside a child)."""
    global _child_cancel
    if _child_cancel is None:
        _child_cancel = CancelToken()
    return _child_cancel


def child_epoch_fenced() -> bool:
    """True when this process carries a fleet epoch guard
    (``CHILD_EPOCH_ENV``) whose lease chain has moved past the shipped
    epoch — the parent replica was fenced, so this child must drop its
    durable persists too. False when no guard is set (no fleet) or the
    guard cannot be evaluated (an unreadable fleet dir must not stall a
    healthy child: the parent-side fence still protects the journal).

    Imports the storage layer lazily and re-reads the chain on every
    call — callers sit on checkpoint-interval cadence, not the batch
    hot path."""
    raw = os.environ.get(CHILD_EPOCH_ENV, "")
    if not raw:
        return False
    try:
        import json

        guard = json.loads(raw)
        fleet_dir = guard["fleet_dir"]
        replica = guard["replica"]
        epoch = int(guard["epoch"])
        from deequ_tpu.io.storage import storage_for

        storage = storage_for(fleet_dir)
        # mirrors service/fleet.py's lease layout (LEASE_DIR/_lease_key)
        # without importing service machinery into the child
        prefix = f"leases/lease-{replica}-"
        for key in storage.list_keys(prefix):
            blob = storage.read_bytes(key)
            if blob is None:
                continue
            body = json.loads(blob)
            if (
                body.get("replica") == replica
                and int(body.get("epoch", 0)) > epoch
            ):
                return True
        return False
    except Exception:  # noqa: BLE001 — unevaluable guard: stay open
        return False


def _child_trace(tm: Any) -> Optional[Any]:
    """Decode the parent's shipped trace (``CHILD_TRACE_ENV``) into the
    child's ambient context, re-tagged with a ``/child`` process label
    so a merged fleet timeline tells the two processes apart."""
    from deequ_tpu.telemetry.spans import TraceContext

    ctx = TraceContext.decode(os.environ.get(CHILD_TRACE_ENV, ""))
    if ctx is None or not tm.enabled:
        return None
    label = f"{ctx.process}/child" if ctx.process else "child"
    return TraceContext(ctx.trace_id, ctx.span_id, process=label)


def _watch_parent_cancel(cancel_conn: Any, token: CancelToken) -> None:
    """Child-side watcher: one blocking recv on the control pipe; a
    ``("cancel", reason)`` message fires the child's token. EOF (parent
    closed the pipe, i.e. the run ended without a cancel) just ends the
    watcher."""
    try:
        msg = cancel_conn.recv()
    except Exception:  # noqa: BLE001 — EOF/torn pipe: no cancel came
        return
    if isinstance(msg, tuple) and msg and msg[0] == "cancel":
        reason = msg[1] if len(msg) > 1 else "cancelled by parent"
        token.cancel(str(reason))


def _child_main(
    conn: Any,
    cancel_conn: Any,
    fn: Callable[[Any], Any],
    payload: Any,
) -> None:
    """Spawn entry point: run ``fn(payload)`` and ship ``("ok", result,
    telemetry_summary)`` or ``("err", exception, telemetry_summary)``
    back over the pipe. Anything that cannot pickle degrades to a
    :class:`_ChildError` carrier; a crash ships nothing and the parent
    classifies the exit status instead — though every span finished
    BEFORE the crash has already streamed out as a ``("span", record)``
    message, so the parent's trace still shows where the child died."""
    import traceback

    _apply_child_platform()
    from deequ_tpu.telemetry import get_telemetry

    tm = get_telemetry()
    global _child_cancel
    _child_cancel = CancelToken()
    # lint-ok: thread-discipline: child-process watcher, daemon by
    # design — it blocks on the control pipe for the child's whole
    # life and dies with the process; it never touches a scan
    threading.Thread(
        target=_watch_parent_cancel,
        args=(cancel_conn, _child_cancel),
        daemon=True,
        name="deequ-tpu-child-cancel",
    ).start()
    ctx = _child_trace(tm)
    send_lock = threading.Lock()
    if ctx is not None:

        def _stream_span(record: Dict[str, Any]) -> None:
            try:
                with send_lock:
                    conn.send(("span", record))
            except Exception:  # noqa: BLE001 — parent gone/pipe torn:
                # span streaming is best-effort, never fails the run
                pass

        tm.add_span_sink(_stream_span)

    # durable-egress progress frames are NOT gated on tracing: the
    # parent's crash-loop accounting needs them whenever a sink run is
    # isolated, traced or not (notify_egress_progress)
    def _stream_egress(record: Dict[str, Any]) -> None:
        try:
            with send_lock:
                conn.send(("egress", record))
        except Exception:  # noqa: BLE001 — best-effort, like spans
            pass

    global _egress_notify
    _egress_notify = _stream_egress
    try:
        with tm.trace_scope(ctx):
            with tm.run("isolated_child") as cap:
                result = fn(payload)
        message = ("ok", result, cap.final)
    except BaseException as exc:  # lint-ok: interrupt-swallow: child-side boundary — the exception (interrupts included) is pickled and shipped to the parent, which re-raises it; swallowing here IS the delivery
        summary = None
        try:
            summary = cap.final  # noqa: F821 — set when the run opened
        except Exception:  # noqa: BLE001
            pass
        try:
            pickle.dumps(exc)
            message = ("err", exc, summary)
        except Exception:  # noqa: BLE001 — unpicklable exception
            message = (
                "err",
                _ChildError(
                    type(exc).__name__, str(exc), traceback.format_exc()
                ),
                summary,
            )
    try:
        with send_lock:
            conn.send(message)
    except Exception:  # noqa: BLE001 — unpicklable RESULT: report, not crash
        with send_lock:
            conn.send(
                (
                    "err",
                    _ChildError(
                        "UnpicklableResult",
                        f"child result of type "
                        f"{type(message[1]).__name__} cannot cross the pipe",
                        "",
                    ),
                    None,
                )
            )
    finally:
        conn.close()


# --------------------------------------------------------------------------
# Parent side
# --------------------------------------------------------------------------


def _classify_exit(exitcode: Optional[int]) -> Tuple[str, Optional[str]]:
    """(description, signal_name) for a child that died without a
    message. Negative exitcode = killed by signal (multiprocessing
    convention); 128+N = the shell convention some runtimes re-raise."""
    if exitcode is None:
        return "child vanished without an exit status", None
    signum = None
    if exitcode < 0:
        signum = -exitcode
    elif exitcode >= 128:
        signum = exitcode - 128
    if signum is not None:
        try:
            name = _signal.Signals(signum).name
        except ValueError:
            name = f"signal {signum}"
        return f"child killed by {name} (exitcode {exitcode})", name
    return f"child exited with status {exitcode} before replying", None


class IsolatedRunner:
    """Run picklable work in spawn-started children, resuming across
    crashes from durable checkpoints.

    ``run(fn, payload)`` launches ``fn(payload)`` in a child and returns
    its result. On a crash the child is relaunched — ``fn`` must be
    resumable (checkpointer-backed scans are, by construction). Launches
    without observable progress are bounded by ``max_relaunches``; the
    breaker for ``key`` (when enabled) rejects work fast after a
    declared crash loop.
    """

    def __init__(
        self,
        *,
        key: str = "",
        max_relaunches: Optional[int] = None,
        timeout_s: Optional[float] = None,
        progress_probe: Optional[Callable[[], Any]] = None,
        breaker: Optional[CircuitBreaker] = None,
        use_breaker: bool = True,
        clock: Optional[Any] = None,
        cancel_token: Optional[CancelToken] = None,
        epoch_guard: Optional[str] = None,
    ):
        from deequ_tpu import config

        opts = config.options()
        self.key = key
        self.max_relaunches = (
            int(opts.crash_max_relaunches)
            if max_relaunches is None
            else int(max_relaunches)
        )
        self.timeout_s = timeout_s
        self.progress_probe = progress_probe
        if breaker is None and use_breaker and key:
            breaker = breaker_for(key, clock=clock)
        self.breaker = breaker
        # cooperative cancel across the process boundary: when this
        # token fires (client cancel OR a preemption), the parent sends
        # one ("cancel", reason) message down the child's control pipe
        # and keeps WAITING — the child exits cleanly through its
        # checkpoint path and ships its partial result; the runner
        # never escalates a cancel to terminate()/kill() (that is the
        # deadline path's job)
        self.cancel_token = cancel_token
        # last ("egress", record) frame streamed by any child: durable
        # egress advancement between scan checkpoints also counts as
        # forward progress for the crash-loop budget (run())
        self._last_egress_frame: Optional[Dict[str, Any]] = None
        # fleet epoch guard (CHILD_EPOCH_ENV): shipped to every child
        # this runner launches so a child of a fenced parent stops
        # persisting too (FleetSupervisor.child_guard() JSON, or None
        # when the parent is not a fleet member)
        self.epoch_guard = epoch_guard
        self._ctx = multiprocessing.get_context("spawn")

    # -- single launch ---------------------------------------------------

    def _launch_once(
        self, fn: Callable[[Any], Any], payload: Any, launches: int
    ) -> Any:
        from deequ_tpu.telemetry import get_telemetry

        tm = get_telemetry()
        parent_conn, child_conn = self._ctx.Pipe(duplex=False)
        # control pipe, parent -> child: carries at most one
        # ("cancel", reason) message (see _watch_parent_cancel)
        cancel_recv, cancel_send = self._ctx.Pipe(duplex=False)
        proc = self._ctx.Process(
            target=_child_main,
            args=(child_conn, cancel_recv, fn, payload),
            daemon=False,
        )
        platform = _parent_platform()
        if platform:
            os.environ[CHILD_PLATFORM_ENV] = platform
        # ship the ambient trace, re-anchored at the parent's CURRENT
        # open span, so child spans nest where the launch happened. The
        # env var is restored right after start() — spawn snapshots the
        # environment at launch, and a stale context must never leak
        # into a later untraced child.
        shipped_parent: Optional[int] = None
        shipped = None
        ctx = tm.current_trace()
        if ctx is not None:
            current = tm.tracer.current()
            shipped_parent = (
                current.span_id if current is not None else ctx.span_id
            )
            shipped = ctx.child(shipped_parent)
        prev_trace_env = os.environ.get(CHILD_TRACE_ENV)
        if shipped is not None:
            os.environ[CHILD_TRACE_ENV] = shipped.encode()
        else:
            os.environ.pop(CHILD_TRACE_ENV, None)
        # same snapshot-and-restore discipline for the fleet epoch
        # guard: spawn captures the environment at start(), and a stale
        # guard must never leak into a later fleet-less child
        prev_epoch_env = os.environ.get(CHILD_EPOCH_ENV)
        if self.epoch_guard:
            os.environ[CHILD_EPOCH_ENV] = self.epoch_guard
        else:
            os.environ.pop(CHILD_EPOCH_ENV, None)
        try:
            proc.start()
        finally:
            if prev_trace_env is None:
                os.environ.pop(CHILD_TRACE_ENV, None)
            else:
                os.environ[CHILD_TRACE_ENV] = prev_trace_env
            if prev_epoch_env is None:
                os.environ.pop(CHILD_EPOCH_ENV, None)
            else:
                os.environ[CHILD_EPOCH_ENV] = prev_epoch_env
        child_conn.close()  # parent's copy; the child holds the real end
        cancel_recv.close()  # ditto for the control pipe's read end
        message = None
        poll_expired = False
        timed_out = False
        cancel_sent = False
        spans: list = []
        clk = MonotonicClock()
        deadline = (
            clk.now() + self.timeout_s if self.timeout_s is not None else None
        )
        try:
            try:
                # drain ("span", record) streaming messages until the
                # final ("ok"|"err", value, summary) 3-tuple, EOF, or
                # the deadline. Spans collected here survive a crash —
                # they are replayed below even when no final message
                # ever arrives, so the trace shows where the child died.
                # With a cancel token the wait is sliced so a cancel
                # firing mid-run reaches the child promptly.
                while True:
                    if (
                        self.cancel_token is not None
                        and not cancel_sent
                        and self.cancel_token.cancelled
                    ):
                        cancel_sent = True
                        try:
                            cancel_send.send(
                                (
                                    "cancel",
                                    self.cancel_token.reason
                                    or "cancelled",
                                )
                            )
                        except Exception:  # noqa: BLE001 — child gone:
                            pass  # the result loop classifies that
                    remaining = (
                        None
                        if deadline is None
                        else max(0.0, deadline - clk.now())
                    )
                    if remaining is not None and remaining <= 0.0:
                        poll_expired = True
                        break
                    if self.cancel_token is not None:
                        wait = (
                            0.05
                            if remaining is None
                            else min(0.05, remaining)
                        )
                    else:
                        wait = remaining
                    if not parent_conn.poll(wait):
                        if self.cancel_token is not None:
                            continue  # slice over; re-check the token
                        poll_expired = True
                        break
                    msg = parent_conn.recv()
                    if (
                        isinstance(msg, tuple)
                        and len(msg) == 2
                        and msg[0] == "span"
                    ):
                        if isinstance(msg[1], dict):
                            spans.append(msg[1])
                        continue
                    if (
                        isinstance(msg, tuple)
                        and len(msg) == 2
                        and msg[0] == "egress"
                    ):
                        if isinstance(msg[1], dict):
                            self._last_egress_frame = msg[1]
                        continue
                    message = msg
                    break
            except (EOFError, OSError):
                message = None  # pipe torn by a crashing child
            # timeout means poll() genuinely expired. An EOF wakes poll()
            # while the dying child may still show is_alive() for a
            # moment — that is a CRASH to classify by exit status, and
            # must never be misread as a timeout.
            if (
                message is None
                and poll_expired
                and self.timeout_s is not None
                and proc.is_alive()
            ):
                timed_out = True
                proc.terminate()
        finally:
            try:
                cancel_send.close()
            except Exception:  # noqa: BLE001 — already torn
                pass
            proc.join(self.timeout_s)
            if proc.is_alive():  # terminate() ignored — escalate
                proc.kill()
                proc.join()
            parent_conn.close()
            exitcode = proc.exitcode
            proc.close()

        # replay streamed child spans into the parent's telemetry on
        # EVERY outcome — success, error, crash, timeout. Ids remap onto
        # the parent's counter; parentage re-roots under the span the
        # launch shipped.
        if spans:
            tm.replay_spans(spans, root_parent_id=shipped_parent)

        if timed_out:
            tm.counter("engine.child_crashes").inc()
            tm.event(
                "child_crashed",
                key=self.key,
                exitcode=exitcode,
                signal="timeout",
                launches=launches,
                spans_streamed=len(spans),
            )
            raise ProcessCrashed(
                f"child exceeded {self.timeout_s}s and was terminated",
                exitcode=exitcode,
                signal_name="timeout",
                launches=launches,
            )
        if message is None:
            description, signal_name = _classify_exit(exitcode)
            tm.counter("engine.child_crashes").inc()
            tm.event(
                "child_crashed",
                key=self.key,
                exitcode=exitcode,
                signal=signal_name,
                launches=launches,
                spans_streamed=len(spans),
            )
            raise ProcessCrashed(
                description,
                exitcode=exitcode,
                signal_name=signal_name,
                launches=launches,
            )

        status, value, child_summary = message
        _merge_child_telemetry(tm, child_summary)
        if status == "ok":
            return value
        raise value

    # -- relaunch loop ---------------------------------------------------

    def run(self, fn: Callable[[Any], Any], payload: Any = None) -> Any:
        """Execute ``fn(payload)`` in a child, relaunching across
        crashes until it completes, errors in-band, or the relaunch
        budget for a single stuck position is exhausted."""
        from deequ_tpu.telemetry import get_telemetry

        _refuse_chip_contention(_parent_platform())
        tm = get_telemetry()
        if self.breaker is not None:
            self.breaker.admit(self.key)
        last_progress = (
            self.progress_probe() if self.progress_probe is not None else None
        )
        last_egress = self._last_egress_frame
        crashes_here = 0  # crashes since the last observed progress
        launches = 0
        last_crash: Optional[ProcessCrashed] = None
        while True:
            launches += 1
            try:
                result = self._launch_once(fn, payload, launches)
            except ProcessCrashed as crash:
                last_crash = crash
                crashes_here += 1
                if self.progress_probe is not None:
                    progress = self.progress_probe()
                    if progress != last_progress:
                        last_progress = progress
                        crashes_here = 1  # this crash, at the new position
                # a durable egress flush streamed by the child is
                # progress too (span segments advance between scan
                # checkpoints) — a sink run inching forward is never a
                # crash loop
                if self._last_egress_frame != last_egress:
                    last_egress = self._last_egress_frame
                    crashes_here = 1
                if crashes_here >= self.max_relaunches:
                    if self.breaker is not None:
                        self.breaker.record_crash_loop(self.key)
                    tm.counter("engine.crash_loops").inc()
                    raise CrashLoopError(
                        f"{self.key or 'run'} crashed {crashes_here} "
                        f"launches in a row without checkpoint progress "
                        f"(last: {crash})",
                        launches=launches,
                        last_exitcode=crash.exitcode,
                        last_signal=crash.signal_name,
                    ) from crash
                tm.counter("engine.child_relaunches").inc()
                tm.event(
                    "child_relaunched",
                    key=self.key,
                    launches=launches,
                    crashes_at_position=crashes_here,
                )
                continue
            if self.breaker is not None:
                self.breaker.record_success(self.key)
            if launches > 1:
                tm.counter("engine.crash_resumes").inc()
                tm.event(
                    "crash_resumed",
                    key=self.key,
                    launches=launches,
                    last_signal=(
                        last_crash.signal_name if last_crash else None
                    ),
                )
            return result


def _refuse_chip_contention(platform: Optional[str]) -> None:
    """Raise :class:`ChipHeldByParent` when this process has started a
    TPU backend and a child pinned to ``platform`` would reach for the
    TPU too. Never starts a backend itself."""
    if platform and "tpu" not in platform.split(","):
        return
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return
    import jax

    if jax.default_backend() == "tpu":
        raise ChipHeldByParent(
            "this process holds the TPU, so an isolated child cannot "
            "start on it; run in-process (isolated_execution=False) or "
            "keep the parent off JAX"
        )


def _parent_platform() -> Optional[str]:
    """The platform string children must pin, resolved from the
    parent's live jax config (falls back to the env var)."""
    try:
        import jax

        value = getattr(jax.config, "jax_platforms", None)
        if value:
            return str(value)
    except Exception:  # noqa: BLE001
        pass
    return os.environ.get("JAX_PLATFORMS") or None


def _merge_child_telemetry(tm: Any, summary: Optional[Dict[str, Any]]) -> None:
    """Fold a child's run summary into the parent's telemetry: counter
    deltas add up, events replay (so obs reports see one stream)."""
    if not summary:
        return
    for name, delta in (summary.get("counters") or {}).items():
        try:
            tm.counter(name).inc(int(delta))
        except Exception:  # noqa: BLE001 — malformed child counter
            continue
    for record in summary.get("events") or []:
        if not isinstance(record, dict) or "event" not in record:
            continue
        fields = {k: v for k, v in record.items() if k != "event"}
        try:
            tm.event(record["event"], **fields)
        except TypeError:  # field name collides with the name parameter
            continue


def run_isolated(
    fn: Callable[[Any], Any],
    payload: Any = None,
    **kwargs: Any,
) -> Any:
    """One-shot convenience: ``IsolatedRunner(**kwargs).run(fn, payload)``."""
    return IsolatedRunner(**kwargs).run(fn, payload)
