"""Plane supervisor: fault-tolerant TPU runtime lifecycle (tpu/supervisor.py).

The defect these tests pin down: a server configured with the TPU
merge plane hung at boot, serving nothing, whenever the TPU runtime was
wedged (device discovery that never returns). The supervisor inverts
the ownership: the plane is an accelerator the server may acquire,
never a boot dependency.

Chaos scenarios covered, with the invariant "hardware absence degrades
throughput, never availability" checked in each:
- wedged init: the server boots within the init deadline, accepts
  WebSocket connections and syncs documents on the CPU path
- late init: the plane hot-attaches and takes over serving
- failed init: BROKEN is terminal, the server keeps serving
- mid-flight wedge: the watchdog canary overruns, the breaker opens,
  served docs drain to the CPU path with zero request loss (including
  sync waiters stranded behind the wedged flush)
- flapping recovery: wedge -> recover -> wedge again, with the breaker
  and transition counters accounting for every swing
"""

import asyncio
import threading

from hocuspocus_tpu.tpu import SupervisedTpuMergeExtension
from hocuspocus_tpu.tpu.supervisor import (
    BREAKER_CLOSED,
    BREAKER_OPEN,
    STATE_BROKEN,
    STATE_DEGRADED,
    STATE_INITIALIZING,
    STATE_READY,
    CircuitBreaker,
)
from tests.utils import new_hocuspocus, new_provider, retryable_assertion, wait_synced


def _assert(cond, detail=None):
    assert cond, detail


def _fast_ext(**overrides):
    """A supervised serve-mode extension tuned for test cadence."""
    kwargs = dict(
        serve=True,
        num_docs=8,
        capacity=512,
        flush_interval_ms=1,
        init_timeout=60.0,
        watchdog_interval=0.1,
        breaker_threshold=2,
        canary_deadline=0.25,
    )
    kwargs.update(overrides)
    return SupervisedTpuMergeExtension(**kwargs)


class _WedgeableStep:
    """Swappable step factory: pass-through until wedge() is called;
    wedged steps block on the gate, then run the real step — modeling a
    hung device that later completes the in-flight launch. Covers ALL
    THREE device entry points (the dense step, the sparse busy-doc step
    and the run-merge append step — flushes and the canary dispatch
    through one of them). `entered` latches once a dispatch is
    physically blocked on the gate: its caller (timer flush, drain or
    canary) holds the plane flush lock at that point, so tests can wait
    on it before asserting wedge-dependent behavior. Call recover() in
    the test's finally — a blocked executor thread outliving the test
    deadlocks the event-loop teardown."""

    def __init__(self, plane) -> None:
        self.plane = plane
        self.real = plane._step_fn
        self.real_sparse = plane._sparse_step_fn
        self.real_append = plane._append_step_fn
        self.gate = threading.Event()
        self.entered = threading.Event()
        self.wedged = False
        plane._step_fn = self._factory
        plane._sparse_step_fn = self._sparse_factory
        plane._append_step_fn = self._append_factory

    def _factory(self):
        real_step = self.real()
        if not self.wedged:
            return real_step

        def blocked(state, ops):
            self.entered.set()
            self.gate.wait()
            return real_step(state, ops)

        return blocked

    def _sparse_factory(self):
        real_step = self.real_sparse()
        if not self.wedged:
            return real_step

        def blocked(state, ops, slots):
            self.entered.set()
            self.gate.wait()
            return real_step(state, ops, slots)

        return blocked

    def _append_factory(self):
        real_step = self.real_append()
        if not self.wedged:
            return real_step

        def blocked(state, *args):
            self.entered.set()
            self.gate.wait()
            return real_step(state, *args)

        return blocked

    def wedge(self) -> None:
        self.wedged = True
        self.gate.clear()

    def recover(self) -> None:
        self.wedged = False
        self.gate.set()


# -- breaker unit behavior ---------------------------------------------------


def test_circuit_breaker_state_machine():
    breaker = CircuitBreaker(threshold=3)
    assert breaker.state == BREAKER_CLOSED
    assert not breaker.record_failure()
    assert not breaker.record_failure()
    assert breaker.record_failure(), "threshold-th consecutive failure trips"
    assert breaker.state == BREAKER_OPEN
    # half-open probe fails: back to open, no re-trip signal
    assert breaker.try_half_open()
    assert not breaker.record_failure()
    assert breaker.state == BREAKER_OPEN
    # half-open probe passes: closed, recovery signalled
    assert breaker.try_half_open()
    assert breaker.record_success()
    assert breaker.state == BREAKER_CLOSED
    assert breaker.consecutive_failures == 0
    # a lone failure after recovery does not trip
    assert not breaker.record_failure()
    assert breaker.transitions["closed->open"] == 1
    assert breaker.transitions["half_open->closed"] == 1


# -- wedged / late / failed init ---------------------------------------------


async def test_wedged_init_boots_and_serves_within_deadline():
    """THE round-5 defect: a TPU runtime that never initializes must
    not keep the server from serving. Boot completes immediately, a
    provider connects and syncs well within the init deadline, and the
    supervisor lands in DEGRADED (CPU-merge mode) once the deadline
    passes."""
    gate = threading.Event()

    def wedged_factory():
        gate.wait()  # blocks forever: simulated wedged device discovery
        raise AssertionError("never reached in this test")

    ext = SupervisedTpuMergeExtension(
        runtime_factory=wedged_factory, init_timeout=0.5, watchdog_interval=0.05
    )
    server = await new_hocuspocus(extensions=[ext])
    a = new_provider(server, name="wedged-init")
    b = new_provider(server, name="wedged-init")
    try:
        assert ext.supervisor.state == STATE_INITIALIZING
        # sync completes while init is still wedged (CPU path)
        await wait_synced(a, b, timeout=10)
        a.document.get_text("t").insert(0, "cpu serves")
        await retryable_assertion(
            lambda: _assert(b.document.get_text("t").to_string() == "cpu serves")
        )
        await retryable_assertion(
            lambda: _assert(ext.supervisor.state == STATE_DEGRADED)
        )
        assert ext.supervisor.counters["init_timeouts"] == 1
        health = ext.health_status()
        assert health["degraded"] and health["init"]["pending"]
    finally:
        gate.set()  # unblock the daemon thread before teardown
        a.destroy()
        b.destroy()
        await server.destroy()


async def test_late_init_hot_attaches_live_documents():
    """Init completes AFTER the deadline: the plane hot-attaches,
    documents loaded during the degraded window are re-onboarded from
    their CPU snapshots, and serving switches to the plane with no
    content loss in either direction."""
    from hocuspocus_tpu.tpu.merge_plane import TpuMergeExtension

    gate = threading.Event()

    def late_factory():
        gate.wait()
        return TpuMergeExtension(
            serve=True, num_docs=8, capacity=512, flush_interval_ms=1
        )

    ext = SupervisedTpuMergeExtension(
        runtime_factory=late_factory, init_timeout=0.2, watchdog_interval=0.05
    )
    server = await new_hocuspocus(extensions=[ext])
    a = new_provider(server, name="late-doc")
    b = new_provider(server, name="late-doc")
    try:
        await wait_synced(a, b)
        a.document.get_text("t").insert(0, "before;")
        await retryable_assertion(
            lambda: _assert(b.document.get_text("t").to_string() == "before;")
        )
        await retryable_assertion(
            lambda: _assert(ext.supervisor.state == STATE_DEGRADED)
        )
        gate.set()  # the runtime finally comes up
        await retryable_assertion(
            lambda: _assert(
                ext.supervisor.state == STATE_READY
                and ext.runtime.is_served("late-doc"),
                ext.supervisor.snapshot(),
            )
        )
        broadcasts_before = ext.plane.counters["plane_broadcasts"]
        a.document.get_text("t").insert(0, "plane;")
        await retryable_assertion(
            lambda: _assert(b.document.get_text("t").to_string() == "plane;before;")
        )
        # the post-attach frame really rode the plane
        await retryable_assertion(
            lambda: _assert(
                ext.plane.counters["plane_broadcasts"] > broadcasts_before
            )
        )
        # a cold joiner syncs the full state from the plane
        c = new_provider(server, name="late-doc")
        try:
            await wait_synced(c)
            assert c.document.get_text("t").to_string() == "plane;before;"
        finally:
            c.destroy()
        assert ext.supervisor.transitions.get("degraded->ready") == 1
    finally:
        a.destroy()
        b.destroy()
        await server.destroy()


async def test_load_in_flight_at_ready_is_onboarded():
    """A document whose load straddles the READY transition: its
    after_load hook passed the extension while it was still
    INITIALIZING, and it is not in `instance.documents` yet when the
    attach sweep runs. The sweep must wait for the load and onboard it
    — missed, the document stays on the CPU path for life."""
    from hocuspocus_tpu.tpu.merge_plane import TpuMergeExtension

    init_gate = threading.Event()
    load_gate = asyncio.Event()
    passed_the_plane_hook = asyncio.Event()

    def late_factory():
        init_gate.wait()
        return TpuMergeExtension(
            serve=True, num_docs=8, capacity=512, flush_interval_ms=1
        )

    async def slow_after_load(data):
        # inline hooks run after every extension's: the supervised
        # extension's after_load_document has already returned
        passed_the_plane_hook.set()
        await load_gate.wait()

    # no canary inside the test: a breaker trip and recovery would run
    # a second sweep and onboard what the attach sweep missed
    ext = SupervisedTpuMergeExtension(
        runtime_factory=late_factory, init_timeout=60.0, watchdog_interval=60.0
    )
    server = await new_hocuspocus(
        extensions=[ext], after_load_document=slow_after_load
    )
    a = new_provider(server, name="in-flight")
    try:
        await asyncio.wait_for(passed_the_plane_hook.wait(), timeout=10)
        instance = server.hocuspocus
        assert ext.supervisor.state == STATE_INITIALIZING
        assert "in-flight" in instance.loading_documents
        assert "in-flight" not in instance.documents
        init_gate.set()  # the runtime comes up while the load is in flight
        await retryable_assertion(
            lambda: _assert(ext.supervisor.state == STATE_READY)
        )
        assert "in-flight" in instance.loading_documents
        load_gate.set()
        await wait_synced(a)
        await retryable_assertion(
            lambda: _assert(
                ext.runtime.is_served("in-flight"), ext.supervisor.snapshot()
            )
        )
        # ... and really rides the plane from here on
        b = new_provider(server, name="in-flight")
        try:
            await wait_synced(b)
            broadcasts_before = ext.plane.counters["plane_broadcasts"]
            a.document.get_text("t").insert(0, "served")
            await retryable_assertion(
                lambda: _assert(b.document.get_text("t").to_string() == "served")
            )
            assert ext.plane.counters["plane_broadcasts"] > broadcasts_before
        finally:
            b.destroy()
    finally:
        init_gate.set()
        load_gate.set()
        a.destroy()
        await server.destroy()


async def test_failed_init_is_broken_but_server_serves():
    def dead_factory():
        raise RuntimeError("INTERNAL: no TPU platform found (injected)")

    ext = SupervisedTpuMergeExtension(
        runtime_factory=dead_factory, init_timeout=5.0, watchdog_interval=0.05
    )
    server = await new_hocuspocus(extensions=[ext])
    a = new_provider(server, name="broken-doc")
    b = new_provider(server, name="broken-doc")
    try:
        await retryable_assertion(lambda: _assert(ext.supervisor.state == STATE_BROKEN))
        assert ext.supervisor.counters["init_failures"] == 1
        await wait_synced(a, b)
        a.document.get_text("t").insert(0, "still serving")
        await retryable_assertion(
            lambda: _assert(b.document.get_text("t").to_string() == "still serving")
        )
        # BROKEN is terminal: no canary probes, no runtime
        assert ext.runtime is None
        health = ext.health_status()
        assert health["state"] == "broken" and not health["init"]["pending"]
    finally:
        a.destroy()
        b.destroy()
        await server.destroy()


# -- mid-flight wedge --------------------------------------------------------


async def test_midflight_wedge_trips_breaker_and_drains_to_cpu():
    """The device wedges while docs are plane-served and traffic is in
    flight. The canary overruns its deadline, the breaker opens, served
    docs degrade via the full-state CPU broadcast, sync waiters caught
    behind the wedged flush resolve to the CPU path, and no edit made
    at ANY point is lost."""
    ext = _fast_ext()
    server = await new_hocuspocus(extensions=[ext])
    a = new_provider(server, name="wedge-doc")
    b = new_provider(server, name="wedge-doc")
    joiners = []
    wedge = None
    try:
        await wait_synced(a, b)
        await retryable_assertion(
            lambda: _assert(
                ext.supervisor.state == STATE_READY
                and ext.runtime.is_served("wedge-doc")
            )
        )
        a.document.get_text("t").insert(0, "pre;")
        await retryable_assertion(
            lambda: _assert(b.document.get_text("t").to_string() == "pre;")
        )
        wedge = _WedgeableStep(ext.plane)
        wedge.wedge()
        # edits DURING the wedge: broadcasts build host-side, and after
        # the trip they ride the CPU fan-out — either way they arrive
        a.document.get_text("t").insert(0, "mid;")
        await retryable_assertion(
            lambda: _assert(ext.supervisor.state == STATE_DEGRADED),
            timeout=15,
        )
        assert ext.supervisor.breaker.state == BREAKER_OPEN
        assert ext.plane.counters["cpu_fallbacks"] >= 1
        # cold joiners during the wedge sync via the CPU path — the
        # exact "stalled document" scenario the drain prevents
        for _ in range(2):
            c = new_provider(server, name="wedge-doc")
            joiners.append(c)
        await wait_synced(*joiners, timeout=15)
        for c in joiners:
            await retryable_assertion(
                lambda c=c: _assert(
                    c.document.get_text("t").to_string() == "mid;pre;"
                )
            )
        # steady-state edits keep flowing on the CPU path, both ways
        b.document.get_text("t").insert(0, "cpu;")
        await retryable_assertion(
            lambda: _assert(a.document.get_text("t").to_string() == "cpu;mid;pre;")
        )
    finally:
        if wedge is not None:
            wedge.recover()  # let the blocked device thread finish cleanly
        for c in joiners:
            c.destroy()
        a.destroy()
        b.destroy()
        await server.destroy()


async def test_flapping_wedge_recover_wedge_is_accounted():
    """Wedge -> recover (hot re-attach) -> wedge again. Every swing is
    visible in the transition counters, content converges after each
    phase, and the second degradation drains cleanly too."""
    ext = _fast_ext()
    server = await new_hocuspocus(extensions=[ext])
    a = new_provider(server, name="flap-doc")
    b = new_provider(server, name="flap-doc")
    wedge = None
    try:
        await wait_synced(a, b)
        await retryable_assertion(
            lambda: _assert(
                ext.supervisor.state == STATE_READY
                and ext.runtime.is_served("flap-doc")
            )
        )
        wedge = _WedgeableStep(ext.plane)
        expected = ""
        for cycle in range(2):
            # wedge: breaker opens, doc drains to CPU
            wedge.wedge()
            await retryable_assertion(
                lambda: _assert(ext.supervisor.state == STATE_DEGRADED),
                timeout=15,
            )
            frag = f"down{cycle};"
            expected = frag + expected
            a.document.get_text("t").insert(0, frag)
            await retryable_assertion(
                lambda: _assert(b.document.get_text("t").to_string() == expected)
            )
            # recover: half-open canary passes, plane re-attaches
            wedge.recover()
            await retryable_assertion(
                lambda: _assert(
                    ext.supervisor.state == STATE_READY
                    and ext.runtime.is_served("flap-doc"),
                    ext.supervisor.snapshot(),
                ),
                timeout=20,
            )
            frag = f"up{cycle};"
            expected = frag + expected
            a.document.get_text("t").insert(0, frag)
            await retryable_assertion(
                lambda: _assert(b.document.get_text("t").to_string() == expected)
            )
        transitions = ext.supervisor.transitions
        assert transitions.get("ready->degraded") == 2, transitions
        assert transitions.get("degraded->ready") == 2, transitions
        assert ext.supervisor.counters["degrades"] == 2
        # initial attach + two recoveries
        assert ext.supervisor.counters["attaches"] == 3
        breaker_moves = ext.supervisor.breaker.transitions
        assert breaker_moves.get("closed->open") == 2, breaker_moves
        assert breaker_moves.get("half_open->closed") == 2, breaker_moves
        # a late joiner after the flapping sees the complete history
        c = new_provider(server, name="flap-doc")
        try:
            await wait_synced(c)
            await retryable_assertion(
                lambda: _assert(c.document.get_text("t").to_string() == expected)
            )
        finally:
            c.destroy()
    finally:
        if wedge is not None:
            wedge.recover()
        a.destroy()
        b.destroy()
        await server.destroy()


async def test_breaker_open_parks_lane_classes_and_resume_restores():
    """Scheduler-vs-supervisor interaction (tpu/scheduler.py): tripping
    the breaker must PARK the device lane — every queued or new
    flush/hydration/compaction admission defers instead of stacking
    blocked tasks onto the wedged device, while pause-exempt canary
    probes still pass (half-open recovery needs the chip). Recovery
    resumes the lane and admissions flow again."""
    from hocuspocus_tpu.tpu.scheduler import (
        CLASS_BACKGROUND,
        CLASS_CANARY,
        CLASS_CATCHUP,
        CLASS_INTERACTIVE,
        DeviceLane,
        LaneDeferred,
    )

    lane = DeviceLane()
    ext = _fast_ext(lane=lane)
    server = await new_hocuspocus(extensions=[ext])
    a = new_provider(server, name="lane-park-doc")
    b = new_provider(server, name="lane-park-doc")
    wedge = None
    try:
        await wait_synced(a, b)
        await retryable_assertion(
            lambda: _assert(
                ext.supervisor.state == STATE_READY
                and ext.runtime.is_served("lane-park-doc")
            )
        )
        a.document.get_text("t").insert(0, "pre;")
        await retryable_assertion(
            lambda: _assert(b.document.get_text("t").to_string() == "pre;")
        )
        wedge = _WedgeableStep(ext.plane)
        wedge.wedge()
        a.document.get_text("t").insert(0, "mid;")
        await retryable_assertion(
            lambda: _assert(ext.supervisor.state == STATE_DEGRADED),
            timeout=15,
        )
        # the trip parked the lane: every non-exempt class defers at the
        # door — flush timers, hydration rounds and compaction sweeps
        # all reschedule instead of queueing against the wedge
        assert lane.paused, "breaker-open must park the device lane"
        deferrals_before = lane.counters["deferrals"]
        for cls in (CLASS_INTERACTIVE, CLASS_CATCHUP, CLASS_BACKGROUND):
            try:
                ticket = await lane.admit(cls, site="test")
            except LaneDeferred:
                continue
            ticket.release()
            raise AssertionError(f"class {cls} admitted through a parked lane")
        assert lane.counters["deferrals"] >= deferrals_before + 3
        # deferred flushes surface in the plane's flight recorder so
        # /debug/docs explains scheduling-induced latency
        from hocuspocus_tpu.observability.flight_recorder import (
            get_flight_recorder,
        )

        b.document.get_text("t").insert(0, "cpu;")  # CPU path keeps flowing
        await retryable_assertion(
            lambda: _assert(a.document.get_text("t").to_string() == "cpu;mid;pre;")
        )
        # recovery: the wedge clears, the half-open canary passes
        # (pause-exempt admission), the lane resumes with serving
        wedge.recover()
        await retryable_assertion(
            lambda: _assert(
                ext.supervisor.state == STATE_READY and not lane.paused,
                ext.supervisor.snapshot(),
            ),
            timeout=20,
        )
        assert lane.class_admissions[CLASS_CANARY] > 0, "canary rode the lane"
        ticket = await lane.admit(CLASS_INTERACTIVE, site="test")
        ticket.release()
        a.document.get_text("t").insert(0, "back;")
        await retryable_assertion(
            lambda: _assert(
                b.document.get_text("t").to_string() == "back;cpu;mid;pre;"
            )
        )
        # __plane__ carries the park's paper trail for operators
        events = [
            e["event"] for e in get_flight_recorder().events("__plane__")
        ]
        assert "supervisor.transition" in events
    finally:
        if wedge is not None:
            wedge.recover()
        a.destroy()
        b.destroy()
        await server.destroy()
    # teardown must never leave a (possibly process-global) lane parked
    assert not lane.paused


async def test_abort_pending_resolves_stranded_sync_waiters():
    """A batched sync waiter stranded behind a wedged flush must not
    stall its client: abort_pending resolves it to None (CPU fallback)
    and the later (post-unwedge) drain resolution is a guarded no-op."""
    ext = _fast_ext()
    server = await new_hocuspocus(extensions=[ext])
    a = new_provider(server, name="strand-doc")
    wedge = None
    try:
        await wait_synced(a)
        await retryable_assertion(
            lambda: _assert(
                ext.supervisor.state == STATE_READY
                and ext.runtime.is_served("strand-doc")
            )
        )
        serving = ext.runtime.serving
        # wedge FIRST, then edit: the flush timer (or the canary) takes
        # the dispatch into the gate while holding the plane flush lock,
        # so the batched sync below deterministically strands behind it
        # — editing before wedging races the 1ms timer, which can land
        # the op pre-wedge and let the drain serve real bytes
        wedge = _WedgeableStep(ext.plane)
        wedge.wedge()
        a.document.get_text("t").insert(0, "content")
        await retryable_assertion(
            lambda: _assert(wedge.entered.is_set()), timeout=15
        )
        waiter = asyncio.ensure_future(
            serving.batched_sync("strand-doc", server.documents["strand-doc"], None)
        )
        await asyncio.sleep(0.05)
        assert not waiter.done() or waiter.result() is None
        serving.paused = True
        serving.abort_pending()
        result = await asyncio.wait_for(waiter, 5)
        assert result is None, "stranded waiter must degrade to CPU, not hang"
        # while paused, new sync requests short-circuit to CPU fallback
        assert (
            await serving.batched_sync(
                "strand-doc", server.documents["strand-doc"], None
            )
            is None
        )
    finally:
        if wedge is not None:
            wedge.recover()
        a.destroy()
        await server.destroy()


async def test_healthz_endpoint_reports_plane_state():
    import json

    import aiohttp

    ext = _fast_ext()
    server = await new_hocuspocus(extensions=[ext])
    wedge = None
    try:
        await retryable_assertion(lambda: _assert(ext.supervisor.state == STATE_READY))
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{server.http_url}/healthz") as response:
                assert response.status == 200
                body = json.loads(await response.text())
        assert body["status"] == "ok"
        plane = body["extensions"]["SupervisedTpuMergeExtension"]
        assert plane["state"] == "ready" and plane["serving_from_plane"]
        # degrade and re-check: still HTTP 200 (the server serves), but
        # marked degraded so balancers can steer
        wedge = _WedgeableStep(ext.plane)
        wedge.wedge()
        await retryable_assertion(
            lambda: _assert(ext.supervisor.state == STATE_DEGRADED), timeout=15
        )
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{server.http_url}/healthz") as response:
                assert response.status == 200
                body = json.loads(await response.text())
        assert body["status"] == "degraded"
        assert body["extensions"]["SupervisedTpuMergeExtension"]["breaker"][
            "state"
        ] == "open"
    finally:
        if wedge is not None:
            wedge.recover()
        await server.destroy()


async def test_sharded_runtime_under_supervision():
    """shards>1 builds the doc-partitioned router under the same
    supervisor: canaries probe every shard plane, docs on different
    shards serve from their planes, and a wedge in ONE shard still
    degrades (the canary sweep is serving-wide by design — a sick chip
    is a sick chip)."""
    ext = SupervisedTpuMergeExtension(
        shards=2,
        serve=True,
        num_docs=8,
        capacity=512,
        flush_interval_ms=1,
        init_timeout=60.0,
        watchdog_interval=0.1,
        breaker_threshold=2,
        canary_deadline=0.25,
    )
    server = await new_hocuspocus(extensions=[ext])
    writers = []
    readers = []
    wedge = None
    try:
        for d in range(4):
            writers.append(new_provider(server, name=f"shard-sup-{d}"))
            readers.append(new_provider(server, name=f"shard-sup-{d}"))
        await wait_synced(*writers, *readers)
        await retryable_assertion(
            lambda: _assert(
                ext.supervisor.state == STATE_READY
                and all(
                    ext.runtime.is_served(f"shard-sup-{d}") for d in range(4)
                ),
                ext.supervisor.snapshot(),
            )
        )
        for d in range(4):
            writers[d].document.get_text("t").insert(0, f"doc{d};")
        await retryable_assertion(
            lambda: _assert(
                all(
                    readers[d].document.get_text("t").to_string() == f"doc{d};"
                    for d in range(4)
                )
            )
        )
        # wedge one shard's plane: the sweep canary overruns, all docs
        # drain to CPU, edits keep flowing
        wedge = _WedgeableStep(ext.runtime.shards[0].plane)
        wedge.wedge()
        await retryable_assertion(
            lambda: _assert(ext.supervisor.state == STATE_DEGRADED), timeout=15
        )
        for d in range(4):
            writers[d].document.get_text("t").insert(0, "cpu;")
        await retryable_assertion(
            lambda: _assert(
                all(
                    readers[d].document.get_text("t").to_string() == f"cpu;doc{d};"
                    for d in range(4)
                )
            )
        )
    finally:
        if wedge is not None:
            wedge.recover()
        for p in writers + readers:
            p.destroy()
        await server.destroy()
