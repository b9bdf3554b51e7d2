"""The multi-tenant verification service, end to end (PR 7
acceptance):

1. start a ``VerificationService`` (2 workers, 1 interactive reserve)
   and warm the EXACT production suites at startup (tools/warmup.py:
   compiles key on structure/shapes, never values, so synthetic data
   with the production schema warms the production plans);
2. drive FOUR concurrent clients across TWO tenants with mixed
   priorities against ONE shared dataset key — the telemetry must show
   **zero plan recompiles** after warmup and **one dataset placement**
   total (three cache hits share the resident handle);
3. the interactive reserve keeps the risk tenant's short run ahead of
   the analytics tenant's parked batch run (no priority inversion);
4. resubmission: the same suite runs again and the warm plan survives
   (still zero compiles);
5. a ``device_cache_bytes=0`` flip (stream instead of staying
   resident) compiles under a DISTINCT plan-cache entry — engine
   options are part of the plan fingerprint, so a flipped production
   run never poisons the warm cache;
6. the JSONL telemetry artifact renders the operator's ``service:``
   section (tools/obs_report.py).

Run: python examples/verification_service.py
"""

import os
import sys
import threading

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deequ_tpu import (  # noqa: E402
    Check,
    CheckLevel,
    CheckStatus,
    Dataset,
    config,
    telemetry,
)
from deequ_tpu.service import (  # noqa: E402
    Priority,
    RunRequest,
    VerificationService,
)

ROWS = 20_000
SCHEMA = {"order_id": "int64", "txn_hash": "int64", "amount": "float32"}
DATASET_KEY = "warehouse/orders"


def make_orders() -> Dataset:
    """THE shared table: every tenant's runs verify this one key, so
    the service's dataset cache places it on device exactly once."""
    rng = np.random.default_rng(42)
    return Dataset.from_pydict(
        {
            "order_id": rng.integers(0, 1 << 40, ROWS, dtype=np.int64),
            "txn_hash": rng.integers(0, 1 << 40, ROWS, dtype=np.int64),
            "amount": np.abs(
                rng.normal(40.0, 12.0, ROWS)
            ).astype(np.float32),
        }
    )


def batch_checks():
    """The analytics tenant's heavier nightly suite."""
    return [
        Check(CheckLevel.ERROR, "orders-nightly")
        .is_complete("order_id")
        .is_unique("order_id")
        .is_unique("txn_hash")
        .is_complete("amount")
        .is_non_negative("amount")
    ]


def interactive_checks():
    """The risk tenant's short pre-trade gate."""
    return [
        Check(CheckLevel.ERROR, "orders-gate")
        .is_complete("amount")
        .is_non_negative("amount")
    ]


def main() -> None:
    jsonl = os.path.abspath("service_telemetry.jsonl")
    if os.path.exists(jsonl):
        os.remove(jsonl)
    telemetry.configure(jsonl_path=jsonl)
    tm = telemetry.get_telemetry()

    svc = VerificationService(workers=2, interactive_reserve=1).start()

    # -- startup warmup: the exact suites production will submit ------
    warm_kwargs = dict(
        profile=False,
        nullable=(False,),
        wide_ints=(True,),
        batch_size=ROWS,  # engines resolve batch = min(rows, default)
        engine_variants=[{}],
    )
    tokens = svc.warmup(SCHEMA, checks=batch_checks(), **warm_kwargs)
    tokens += svc.warmup(
        SCHEMA, checks=interactive_checks(), **warm_kwargs
    )
    print(f"warmed {len(tokens)} plan token(s): {', '.join(tokens)}")

    compiles_before = tm.counter("engine.plan_cache.misses").value
    placements_before = tm.counter("service.dataset_cache.misses").value
    shares_before = tm.counter("service.dataset_cache.hits").value

    # -- four concurrent clients, two tenants, mixed priorities -------
    def request(tenant, priority, checks):
        return RunRequest(
            tenant=tenant,
            checks=checks,
            dataset_key=DATASET_KEY,
            dataset_factory=make_orders,
            priority=priority,
        )

    results = {}
    results_lock = threading.Lock()

    def client(name, handle):
        res = handle.result(timeout=300)
        with results_lock:
            results[name] = (handle, res)

    # the analytics tenant's two batch runs go in first: one occupies
    # the single general worker, the second parks in the queue
    batch_handles = [
        svc.submit(request(
            "analytics", Priority.BATCH, batch_checks()
        ))
        for _ in range(2)
    ]
    # the risk tenant's interactive runs arrive LAST yet run on the
    # reserve worker immediately — the anti-starvation guarantee
    inter_handles = [
        svc.submit(request(
            "risk", Priority.INTERACTIVE, interactive_checks()
        ))
        for _ in range(2)
    ]
    threads = [
        threading.Thread(target=client, args=(f"client-{i}", h))
        for i, h in enumerate(batch_handles + inter_handles)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)

    for name, (handle, res) in sorted(results.items()):
        wait_s = handle.started_at - handle.submitted_at
        print(
            f"  {name}: tenant={handle.tenant} "
            f"priority={Priority.name(handle.priority)} "
            f"status={res.status.value} queue_wait={wait_s:.3f}s"
        )
    assert len(results) == 4
    assert all(
        res.status == CheckStatus.SUCCESS
        for _h, res in results.values()
    )

    # no priority inversion: both interactive runs started before the
    # parked batch run got the general worker back
    parked = max(batch_handles, key=lambda h: h.started_at)
    for h in inter_handles:
        assert h.started_at < parked.started_at, (
            "interactive run waited behind a batch run"
        )

    compiles = tm.counter("engine.plan_cache.misses").value
    placements = tm.counter("service.dataset_cache.misses").value
    shares = tm.counter("service.dataset_cache.hits").value
    print(f"recompiles after warmup: {compiles - compiles_before}")
    print(
        f"dataset placements: {placements - placements_before} "
        f"(shared leases: {shares - shares_before})"
    )
    assert compiles - compiles_before == 0, "steady state recompiled"
    assert placements - placements_before == 1, "dataset placed twice"
    assert shares - shares_before == 3

    # -- resubmission: the warm plan survives -------------------------
    again = svc.submit(request(
        "risk", Priority.INTERACTIVE, interactive_checks()
    ))
    assert again.result(timeout=300).status == CheckStatus.SUCCESS
    assert tm.counter("engine.plan_cache.misses").value == compiles
    print("resubmission reused the warm plan (0 new compiles)")

    # -- flag flip => distinct plan-cache entry -----------------------
    from deequ_tpu.engine.scan import plan_cache_snapshot
    from deequ_tpu.profiles.profiler import ColumnProfiler

    dataset, _hit = svc.datasets.lease(DATASET_KEY, make_orders)
    try:
        before_flip = set(plan_cache_snapshot())
        ColumnProfiler.profile(dataset)
        mid_flip = set(plan_cache_snapshot())
        with config.configure(device_cache_bytes=0):
            ColumnProfiler.profile(dataset)
        after_flip = set(plan_cache_snapshot())
    finally:
        svc.datasets.release(DATASET_KEY)
    flipped_new = after_flip - mid_flip - before_flip
    assert flipped_new, "flag flip did not produce a distinct plan"
    print(
        f"device_cache_bytes flip compiled {len(flipped_new)} distinct "
        f"plan entr{'ies' if len(flipped_new) > 1 else 'y'}"
    )

    svc.stop(drain=True)

    # -- scan coalescing: one superset scan, many tenants -------------
    # (docs/SERVICE.md "Scan coalescing") — a separate service with
    # coalescing ON: three tenants' overlapping BATCH suites against
    # the shared key are absorbed into ONE traversal. Submitting before
    # start() makes the grouping deterministic: the first worker pop
    # atomically takes the host ticket and every compatible peer.
    passes_before = tm.counter("engine.data_passes").value
    co = VerificationService(
        workers=2, interactive_reserve=1,
        coalesce=True, coalesce_window_s=0.0,
    )
    co_handles = [
        co.submit(RunRequest(
            tenant=tenant, checks=checks, dataset_key=DATASET_KEY,
            dataset_factory=make_orders, priority=Priority.BATCH,
        ))
        for tenant, checks in [
            ("analytics", batch_checks()),
            ("risk", interactive_checks()),
            ("audit", batch_checks()),
        ]
    ]
    co.start()
    co_results = [h.result(timeout=300) for h in co_handles]
    co.stop(drain=True)
    co_passes = tm.counter("engine.data_passes").value - passes_before
    saved = tm.counter("service.scan_passes_saved").value
    print(
        f"coalescing: {len(co_handles)} tenant runs in {co_passes} "
        f"data pass(es) ({saved} pass(es) saved)"
    )
    assert all(
        r.status == CheckStatus.SUCCESS for r in co_results
    )
    assert co_passes == 1, "coalesced group re-scanned the source"

    # -- the operator's report off the JSONL artifact -----------------
    from tools.obs_report import render_service

    section = render_service(telemetry.read_jsonl(jsonl))
    assert section.startswith("service:")
    print()
    print(section)
    telemetry.configure(jsonl_path=None)
    print()
    print("service demo OK: zero recompiles after warmup, "
          "one dataset placement, no priority inversion")


if __name__ == "__main__":
    main()
