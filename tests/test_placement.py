"""Elastic device placement (docs/SERVICE.md "Elastic placement"):
pool allocation units on fake devices, slice-size policy, mesh-cache
identity, lease wait/deadline/cancel composition on ``ManualClock``,
the shape-keyed plan cache on the real 8-virtual-device mesh (same
shape over DIFFERENT devices replays one compiled plan), metric
equality across slice sizes, and the service-level composition —
concurrent runs on disjoint slices, a coalesced group sharing one
lease, and the spawn-isolation payload carrying the slice size."""

import threading
import time

import jax
import numpy as np
import pytest

from deequ_tpu.engine.deadline import (
    CancelToken,
    DeadlineExceeded,
    ManualClock,
    RunBudget,
    RunCancelled,
)
from deequ_tpu.service import (
    DevicePool,
    ElasticPlacer,
    MeshCache,
    PlacementPolicy,
    Priority,
    RunRequest,
    RunState,
    VerificationService,
)
from deequ_tpu.telemetry import get_telemetry


def _spin_until(predicate, timeout_s=10.0):
    """Real-time wait for a cross-thread condition (the clocks under
    test are fake; thread scheduling is not)."""
    deadline = time.monotonic() + timeout_s
    while not predicate():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.005)
    return True


def _fake_pool(n=8, clock=None):
    """Pool over plain ints: allocation logic needs no real devices."""
    return DevicePool(devices=list(range(n)), clock=clock or ManualClock())


# --------------------------------------------------------------------------
# DevicePool: buddy-aligned allocation
# --------------------------------------------------------------------------


class TestDevicePool:
    def test_aligned_slices_are_disjoint(self):
        pool = _fake_pool(8)
        start1, devs1 = pool.try_acquire(1)
        start2, devs2 = pool.try_acquire(2)
        start4, devs4 = pool.try_acquire(4)
        assert (start1, devs1) == (0, (0,))
        # the 2-slice may not straddle the half-busy [0,1] block
        assert (start2, devs2) == (2, (2, 3))
        assert (start4, devs4) == (4, (4, 5, 6, 7))
        assert pool.free_count() == 1  # only device 1 left
        assert pool.try_acquire(2) is None

    def test_released_slices_remerge(self):
        pool = _fake_pool(8)
        leases = [pool.try_acquire(1) for _ in range(4)]  # devs 0-3
        assert [s for s, _ in leases] == [0, 1, 2, 3]
        # free 1 and 2: adjacent but straddling the aligned boundary —
        # a 2-slice must NOT use them (it would fragment the pool)
        pool.release(1, 1)
        pool.release(2, 1)
        start, devs = pool.try_acquire(2)
        assert (start, devs) == (4, (4, 5))
        # freeing 0 and 3 re-merges both aligned 2-blocks
        pool.release(0, 1)
        pool.release(3, 1)
        assert pool.try_acquire(2)[0] == 0
        assert pool.try_acquire(2)[0] == 2

    def test_requests_round_up_to_pow2_and_clamp(self):
        pool = _fake_pool(8)
        assert len(pool.try_acquire(3)[1]) == 4
        pool2 = _fake_pool(8)
        assert len(pool2.try_acquire(100)[1]) == 8
        # a 6-device pool grants at most its floor power of two
        pool3 = _fake_pool(6)
        assert pool3.max_slice == 4
        assert len(pool3.try_acquire(8)[1]) == 4

    def test_acquire_blocks_until_release(self):
        pool = _fake_pool(1)
        start, _ = pool.try_acquire(1)
        got = []
        thread = threading.Thread(
            target=lambda: got.append(pool.acquire(1))
        )
        thread.start()
        time.sleep(0.05)
        assert not got  # still waiting: the pool is full
        pool.release(start, 1)
        thread.join(timeout=10)
        assert got and got[0][0] == 0

    def test_deadline_raises_only_when_every_budget_expired(self):
        clock = ManualClock()
        pool = _fake_pool(1, clock=clock)
        pool.try_acquire(1)  # pool full forever
        budgets = [
            RunBudget(deadline_s=1.0, clock=clock),
            RunBudget(deadline_s=10.0, clock=clock),
        ]
        outcome = []

        def waiter():
            try:
                pool.acquire(1, budgets=budgets)
            except BaseException as exc:  # noqa: BLE001 — under test
                outcome.append(exc)

        thread = threading.Thread(target=waiter)
        thread.start()
        clock.advance(2.0)  # one member expired: the group still waits
        time.sleep(0.05)
        assert not outcome
        clock.advance(20.0)  # every member expired
        assert _spin_until(lambda: outcome)
        thread.join(timeout=10)
        assert isinstance(outcome[0], DeadlineExceeded)

    def test_cancel_raises_only_when_every_token_fired(self):
        clock = ManualClock()
        pool = _fake_pool(1, clock=clock)
        pool.try_acquire(1)
        tokens = [CancelToken(), CancelToken()]
        outcome = []

        def waiter():
            try:
                pool.acquire(1, cancels=tokens)
            except BaseException as exc:  # noqa: BLE001 — under test
                outcome.append(exc)

        thread = threading.Thread(target=waiter)
        thread.start()
        tokens[0].cancel("one member gone")
        time.sleep(0.05)
        assert not outcome  # the surviving member keeps the wait alive
        tokens[1].cancel("all members gone")
        assert _spin_until(lambda: outcome)
        thread.join(timeout=10)
        assert isinstance(outcome[0], RunCancelled)


# --------------------------------------------------------------------------
# PlacementPolicy: slice sizing
# --------------------------------------------------------------------------


class TestPlacementPolicy:
    def test_footprint_to_slice_table(self):
        policy = PlacementPolicy(bytes_per_device=512 << 20)
        mb512 = 512 << 20
        assert policy.slice_size(0, 8) == 1  # no estimate -> default
        assert policy.slice_size(1, 8) == 1
        assert policy.slice_size(mb512, 8) == 1
        assert policy.slice_size(mb512 + 1, 8) == 2
        assert policy.slice_size(3 * mb512, 8) == 4  # pow2 round-up
        assert policy.slice_size(100 * mb512, 8) == 8  # pool clamp

    def test_max_devices_floors_to_pow2(self):
        policy = PlacementPolicy(bytes_per_device=1, max_devices=6)
        assert policy.slice_size(1 << 40, 8) == 4

    def test_default_devices_for_unsized_runs(self):
        policy = PlacementPolicy(default_devices=2)
        assert policy.slice_size(0, 8) == 2
        assert policy.slice_size(-1, 8) == 2


# --------------------------------------------------------------------------
# MeshCache: identity + LRU
# --------------------------------------------------------------------------


class TestMeshCache:
    def test_same_slice_returns_same_mesh_object(self):
        cache = MeshCache(cap=4)
        devices = jax.devices()[:2]
        assert cache.mesh_for(devices) is cache.mesh_for(devices)
        assert len(cache) == 1

    def test_lru_evicts_past_cap(self):
        cache = MeshCache(cap=2)
        devices = jax.devices()
        cache.mesh_for(devices[:1])
        cache.mesh_for(devices[1:2])
        cache.mesh_for(devices[2:3])  # evicts devices[:1]
        assert len(cache) == 2
        # jax interns Mesh objects, so eviction is observed via keys
        assert (0,) not in cache._meshes
        assert set(cache._meshes) == {(1,), (2,)}
        cache.mesh_for(devices[1:2])  # touch -> MRU
        cache.mesh_for(devices[3:4])  # evicts (2,), not (1,)
        assert set(cache._meshes) == {(1,), (3,)}


# --------------------------------------------------------------------------
# ElasticPlacer: lease lifecycle, telemetry, affinity
# --------------------------------------------------------------------------


class TestElasticPlacer:
    def _placer(self, **kw):
        clock = kw.pop("clock", ManualClock())
        return ElasticPlacer(
            pool=DevicePool(devices=list(jax.devices()), clock=clock),
            clock=clock,
            **kw,
        )

    def test_place_release_roundtrip_and_telemetry(self):
        tm = get_telemetry()
        placed_before = tm.counter("service.placements").value
        placer = self._placer()
        lease = placer.place(estimated_bytes=1, run_ids=["r1"])
        assert lease.ndev == 1
        assert lease.mesh.shape == {"dp": 1}
        assert placer.snapshot()["active_slices"] == 1
        assert (
            tm.counter("service.placements").value - placed_before == 1
        )
        placer.release(lease)
        placer.release(lease)  # idempotent
        snap = placer.snapshot()
        assert snap["active_slices"] == 0
        assert snap["pool_free"] == snap["pool_total"]

    def test_concurrent_leases_are_disjoint(self):
        placer = self._placer()
        leases = [placer.place(estimated_bytes=1) for _ in range(4)]
        seen = set()
        for lease in leases:
            ids = set(lease.device_ids)
            assert not seen & ids
            seen |= ids
        for lease in leases:
            placer.release(lease)

    def test_shape_affinity_prefers_last_granted_shape(self):
        placer = self._placer(
            policy=PlacementPolicy(bytes_per_device=1 << 20)
        )
        lease = placer.place(
            estimated_bytes=2 << 20, hint=("ds", "plan")
        )
        assert lease.ndev == 2
        placer.release(lease)
        # the same structural hint now lands on 2 devices even with no
        # estimate — its per-shape plan is already compiled
        assert placer.slice_for(0, hint=("ds", "plan")) == 2
        assert placer.slice_for(0, hint=("other", "plan")) == 1


# --------------------------------------------------------------------------
# Shape-keyed plan cache: real engine on the 8-virtual-device host
# --------------------------------------------------------------------------


def _small_dataset(rows=4_000, seed=3):
    from deequ_tpu.data import Dataset

    rng = np.random.default_rng(seed)
    return Dataset.from_pydict(
        {
            "k1": rng.integers(0, 1 << 30, rows, dtype=np.int64),
            "v1": rng.normal(0, 1, rows).astype(np.float32),
        }
    )


ANALYZER_SET = None  # built lazily: analyzers import jax at module init


def _analyzers():
    from deequ_tpu.analyzers import Completeness, Mean, Size, Sum

    return [Size(), Completeness("k1"), Mean("v1"), Sum("v1")]


def _mesh_over(devices):
    from jax.sharding import Mesh

    return Mesh(np.array(list(devices)), ("dp",))


class TestShapeKeyedPlanCache:
    def test_same_shape_different_devices_replays_one_plan(self):
        """The tentpole compile-economics pin: a 2-device slice over
        devices [2,3] must HIT the plan compiled on devices [0,1] —
        the cache key carries the placement SHAPE, not the devices."""
        from deequ_tpu.analyzers import AnalysisRunner
        from deequ_tpu.engine import AnalysisEngine

        tm = get_telemetry()
        devices = jax.devices()
        data = _small_dataset(seed=21)
        AnalysisRunner.do_analysis_run(
            data,
            _analyzers(),
            engine=AnalysisEngine(mesh=_mesh_over(devices[:2])),
        )
        hits_before = tm.counter(
            "engine.plan_cache.per_shape.mesh2.hits"
        ).value
        misses_before = tm.counter(
            "engine.plan_cache.per_shape.mesh2.misses"
        ).value
        data2 = _small_dataset(seed=22)  # fresh handle, same shape
        AnalysisRunner.do_analysis_run(
            data2,
            _analyzers(),
            engine=AnalysisEngine(mesh=_mesh_over(devices[2:4])),
        )
        assert (
            tm.counter(
                "engine.plan_cache.per_shape.mesh2.misses"
            ).value
            == misses_before
        )
        assert (
            tm.counter("engine.plan_cache.per_shape.mesh2.hits").value
            > hits_before
        )

    def test_slice_sizes_agree_on_metrics(self):
        """The same suite on a 1-, 2- and 4-device slice: count-family
        metrics bit-equal, float32 aggregations within reduction-order
        noise (the test_mesh.py equality contract, per slice shape)."""
        from deequ_tpu.analyzers import AnalysisRunner
        from deequ_tpu.engine import AnalysisEngine

        devices = jax.devices()
        data = _small_dataset(seed=23)
        analyzers = _analyzers()
        single = AnalysisRunner.do_analysis_run(
            data, analyzers, engine=AnalysisEngine()
        )
        for ndev in (1, 2, 4):
            sliced = AnalysisRunner.do_analysis_run(
                data,
                analyzers,
                engine=AnalysisEngine(mesh=_mesh_over(devices[:ndev])),
            )
            for a in analyzers:
                want = single.metric(a).value.get()
                got = sliced.metric(a).value.get()
                if a.name in ("Size", "Completeness"):
                    assert got == want, (ndev, a, got, want)
                else:
                    # float32 partial sums reassociate across slices
                    assert got == pytest.approx(want, rel=1e-5), (
                        ndev, a,
                    )


# --------------------------------------------------------------------------
# Service composition: disjoint slices, coalesced groups, isolation
# --------------------------------------------------------------------------


def _factory_seed50():
    return _small_dataset(seed=50)


def _suite(i=0):
    from deequ_tpu import Check, CheckLevel

    return [
        Check(CheckLevel.ERROR, f"suite-{i}")
        .is_complete("k1")
        .is_non_negative("k1")
    ]


class TestServiceElasticComposition:
    def test_concurrent_runs_execute_on_disjoint_slices(self):
        svc = VerificationService(
            workers=4, isolated=False, coalesce=False,
            elastic_placement=True,
        )
        handles = [
            svc.submit(
                RunRequest(
                    tenant=f"t{i}",
                    checks=_suite(i),
                    dataset_key=f"elastic/{i}",
                    dataset_factory=lambda i=i: _small_dataset(
                        seed=30 + i
                    ),
                    priority=Priority.BATCH,
                )
            )
            for i in range(4)
        ]
        svc.start()
        try:
            results = [h.result(timeout=300) for h in handles]
        finally:
            svc.stop(drain=False, timeout=30)
        from deequ_tpu.verification import VerificationSuite

        for i, (h, r) in enumerate(zip(handles, results)):
            assert h.status == RunState.DONE
            assert h.placement is not None
            assert h.placement["ndev"] == 1  # small run -> small slice
            solo = VerificationSuite.do_verification_run(
                _small_dataset(seed=30 + i), _suite(i)
            )
            assert r.status == solo.status
            for (a, m), (wa, wm) in zip(
                sorted(dict(r.metrics).items(), key=lambda kv: str(kv[0])),
                sorted(
                    dict(solo.metrics).items(), key=lambda kv: str(kv[0])
                ),
            ):
                assert str(a) == str(wa)
                assert m.value.get() == wm.value.get(), a
        # the pool is whole again and the snapshot says so
        snap = svc.snapshot()["placement"]
        assert snap["active_slices"] == 0
        assert snap["pool_free"] == snap["pool_total"]

    def test_coalesced_group_shares_one_lease(self):
        tm = get_telemetry()
        placed_before = tm.counter("service.placements").value
        svc = VerificationService(
            workers=2, isolated=False, coalesce=True,
            coalesce_window_s=0.0, elastic_placement=True,
        )
        handles = [
            svc.submit(
                RunRequest(
                    tenant=f"t{i}",
                    checks=_suite(i),
                    dataset_key="elastic/shared",
                    dataset_factory=lambda: _small_dataset(seed=40),
                    priority=Priority.BATCH,
                )
            )
            for i in range(2)
        ]
        svc.start()
        try:
            for h in handles:
                h.result(timeout=300)
        finally:
            svc.stop(drain=False, timeout=30)
        # ONE lease for the whole group, visible on every member
        assert (
            tm.counter("service.placements").value - placed_before == 1
        )
        ids = {tuple(h.placement["device_ids"]) for h in handles}
        assert len(ids) == 1

    def test_lease_deadline_fails_run_not_worker(self):
        """Pool of one device, first run holds it; the second's budget
        expires while waiting for the lease — it FAILS with
        DeadlineExceeded, and the worker survives to serve the next
        run. All on fake time."""
        clock = ManualClock()
        release = threading.Event()

        def execute(ticket):
            release.wait(timeout=30)
            return object()

        placer = ElasticPlacer(
            pool=DevicePool(
                devices=list(jax.devices())[:1], clock=clock
            ),
            clock=clock,
        )
        svc = VerificationService(
            workers=2, interactive_reserve=0, clock=clock,
            execute=execute, placer=placer, coalesce=False,
        )
        first = svc.submit(
            RunRequest(
                tenant="a", checks=_suite(), dataset_key="d/1",
                dataset_factory=lambda: object(),
            )
        )
        second = svc.submit(
            RunRequest(
                tenant="b", checks=_suite(), dataset_key="d/2",
                dataset_factory=lambda: object(), deadline_s=5.0,
            )
        )
        svc.start()
        try:
            assert _spin_until(
                lambda: first.status == RunState.RUNNING
            )
            clock.advance(10.0)  # burns the waiter's budget
            assert _spin_until(
                lambda: second.status == RunState.FAILED
            )
            with pytest.raises(DeadlineExceeded):
                second.result(timeout=0)
            release.set()
            assert _spin_until(
                lambda: first.status == RunState.DONE
            )
        finally:
            release.set()
            svc.stop(drain=False, timeout=30)

    def test_cancel_while_waiting_for_lease(self):
        clock = ManualClock()
        release = threading.Event()

        def execute(ticket):
            release.wait(timeout=30)
            return object()

        placer = ElasticPlacer(
            pool=DevicePool(
                devices=list(jax.devices())[:1], clock=clock
            ),
            clock=clock,
        )
        svc = VerificationService(
            workers=2, interactive_reserve=0, clock=clock,
            execute=execute, placer=placer, coalesce=False,
        )
        first = svc.submit(
            RunRequest(
                tenant="a", checks=_suite(), dataset_key="d/1",
                dataset_factory=lambda: object(),
            )
        )
        second = svc.submit(
            RunRequest(
                tenant="b", checks=_suite(), dataset_key="d/2",
                dataset_factory=lambda: object(),
            )
        )
        svc.start()
        try:
            assert _spin_until(
                lambda: first.status == RunState.RUNNING
            )
            second.cancel("changed my mind")
            assert _spin_until(
                lambda: second.status
                in (RunState.FAILED, RunState.CANCELLED)
            )
            with pytest.raises(RunCancelled):
                second.result(timeout=0)
            release.set()
        finally:
            release.set()
            svc.stop(drain=False, timeout=30)

    def test_child_engine_refuses_missing_devices(self):
        """A child that sees fewer devices than its run was placed on
        raises instead of silently running unsharded."""
        import jax

        from deequ_tpu.service.service import _child_engine

        with pytest.raises(RuntimeError, match="refusing"):
            _child_engine({"placement_ndev": len(jax.devices()) + 1})

    def test_isolation_payload_carries_slice_size(self):
        """Crash isolation composes: the lease itself cannot cross the
        spawn boundary, so the payload ships the slice SIZE and the
        child rebuilds an equal-shape mesh over its own devices."""
        from deequ_tpu.service.service import _child_engine

        svc = VerificationService(
            workers=1, isolated=True, coalesce=False,
            elastic_placement=True,
        )
        # build the payload directly from an admitted ticket + lease;
        # the factory must be a picklable module-level function or the
        # payload (correctly) degrades to None
        from deequ_tpu.analyzers import Completeness

        # Check constraints close over lambdas and cannot cross the
        # spawn boundary — analyzer-only requests can (the established
        # isolated-run idiom, see test_coalesce.TestIsolatedCoalescing)
        handle = svc.submit(
            RunRequest(
                tenant="t", checks=(), dataset_key="iso/1",
                required_analyzers=[Completeness("k1")],
                dataset_factory=_factory_seed50,
            )
        )
        ticket = svc.queue.pop(should_stop=lambda: True)
        lease = svc.placer.place(estimated_bytes=1)
        ticket.lease = lease
        try:
            payload = svc._isolation_payload(ticket)
            assert payload["placement_ndev"] == 1
            engine = _child_engine(
                {"placement_ndev": 2, "checkpoint_path": None}
            )
            assert engine is not None
            assert engine.mesh.shape == {"dp": 2}
            assert _child_engine({"placement_ndev": None}) is None
        finally:
            svc.placer.release(lease)
            svc.queue.task_done(ticket)
            handle.cancel("test cleanup")

    def test_service_warmup_covers_every_slice_shape(self, monkeypatch):
        """``warmup()`` on an elastic service warms EVERY pow2 slice
        shape up to the pool max, so a pool-pressure resize never
        compiles in steady state."""
        captured = {}

        def fake_warm_plans(schema, **kwargs):
            captured.update(kwargs)
            return {"tokens": ["tok-a"]}

        import deequ_tpu.service.service as service_mod

        monkeypatch.setattr(
            service_mod,
            "_load_warm_plans",
            lambda: fake_warm_plans,
        )
        svc = VerificationService(
            workers=1, isolated=False, elastic_placement=True
        )
        tokens = svc.warmup({"k1": "integral"})
        assert tokens == ["tok-a"]
        assert captured["mesh_shapes"] == [1, 2, 4, 8]
