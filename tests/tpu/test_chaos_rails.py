"""Chaos for the degradation rails (round-4 verdict item 7).

The existing rails tests trigger CLEAN degradations (plane full,
capacity overflow). These inject the messy versions: the device step
dying mid-flush with broadcasts in flight, Redis vanishing during a
serve window, and a recycle storm colliding with a catch-up storm.
Invariants under every fault: no data loss (every provider converges to
the CPU-authoritative state), no stuck docs (each is either
plane-served or counted as degraded — counters account for every doc),
and the server keeps serving.

Reference analog: per-socket error isolation (`Server.ts:71-80`) is the
reference's whole fault story; the plane adds device/network fault
domains that need their own rails (SURVEY.md §5.3).
"""

import asyncio

from hocuspocus_tpu.extensions import Redis
from hocuspocus_tpu.net.mini_redis import MiniRedis
from hocuspocus_tpu.tpu import TpuMergeExtension
from tests.utils import new_hocuspocus, new_provider, retryable_assertion, wait_synced


def _assert(cond):
    assert cond


async def test_device_fault_mid_flush_degrades_all_without_loss():
    """The device step raises (XlaRuntimeError stand-in) while served
    docs have fresh edits queued and broadcasts in flight. The dead
    flush consumed queued ops — every served doc must degrade via the
    full-state CPU broadcast, receivers stay whole, and edits keep
    flowing on the CPU path afterward."""
    ext = TpuMergeExtension(num_docs=8, capacity=512, flush_interval_ms=1, serve=True)
    server = await new_hocuspocus(extensions=[ext])
    pairs = []
    try:
        for d in range(3):
            a = new_provider(server, name=f"chaos-{d}")
            b = new_provider(server, name=f"chaos-{d}")
            pairs.append((a, b))
            await wait_synced(a, b)
        for i, (a, _b) in enumerate(pairs):
            a.document.get_text("t").insert(0, f"pre{i};")
        await retryable_assertion(
            lambda: _assert(
                all(
                    b.document.get_text("t").to_string() == f"pre{i};"
                    for i, (_a, b) in enumerate(pairs)
                )
            )
        )
        served_before = len(ext._docs)
        assert served_before == 3, "setup: all docs should be plane-served"
        fallbacks_before = ext.plane.counters["cpu_fallbacks"]

        # kill the device: every step from here raises mid-flush
        # (both entry points — dense sweeps and sparse busy-doc batches)
        def dead_step_factory():
            def dead_step(state, ops, slots=None):
                raise RuntimeError("XlaRuntimeError: DEVICE_FAULT (injected)")

            return dead_step

        ext.plane._step_fn = dead_step_factory
        ext.plane._sparse_step_fn = dead_step_factory

        # edits DURING the fault window — their queued ops ride the
        # flush that dies
        for i, (a, _b) in enumerate(pairs):
            a.document.get_text("t").insert(0, f"mid{i};")

        # every served doc degrades; the accounting adds up
        await retryable_assertion(lambda: _assert(len(ext._docs) == 0))
        assert (
            ext.plane.counters["cpu_fallbacks"] - fallbacks_before == served_before
        ), "every served doc must be counted exactly once as a fallback"
        assert ext.plane.counters["docs_retired_fallback"] >= served_before

        # no data loss: the fault-window edits reach the other side
        await retryable_assertion(
            lambda: _assert(
                all(
                    b.document.get_text("t").to_string() == f"mid{i};pre{i};"
                    for i, (_a, b) in enumerate(pairs)
                )
            )
        )

        # steady state continues on the CPU path, both directions
        for i, (_a, b) in enumerate(pairs):
            b.document.get_text("t").insert(0, f"post{i};")
        await retryable_assertion(
            lambda: _assert(
                all(
                    a.document.get_text("t").to_string() == f"post{i};mid{i};pre{i};"
                    for i, (a, _b) in enumerate(pairs)
                )
            )
        )

        # late joiners cold-sync the whole state via the CPU path
        c = new_provider(server, name="chaos-0")
        try:
            await wait_synced(c)
            assert c.document.get_text("t").to_string() == "post0;mid0;pre0;"
        finally:
            c.destroy()
    finally:
        for a, b in pairs:
            a.destroy()
            b.destroy()
        await server.destroy()


async def test_redis_outage_during_serve_window_keeps_plane_and_heals():
    """Redis dies while a plane-served doc is mid-traffic: publish
    failures must NOT degrade the plane (the network fault domain is
    not the device fault domain). Edits made during the outage flow
    cross-instance once Redis returns, via resubscribe + the sync
    exchange."""
    redis = await MiniRedis().start()
    port = redis.port
    ext_a = TpuMergeExtension(num_docs=8, capacity=512, flush_interval_ms=1, serve=True)
    ext_b = TpuMergeExtension(num_docs=8, capacity=512, flush_interval_ms=1, serve=True)
    redis_a = Redis(port=port, identifier="out-a", disconnect_delay=100)
    redis_b = Redis(port=port, identifier="out-b", disconnect_delay=100)
    server_a = await new_hocuspocus(extensions=[redis_a, ext_a])
    server_b = await new_hocuspocus(extensions=[redis_b, ext_b])
    provider_a = new_provider(server_a, name="outage-doc")
    provider_b = new_provider(server_b, name="outage-doc")
    try:
        await wait_synced(provider_a, provider_b)
        provider_a.document.get_text("t").insert(0, "up;")
        await retryable_assertion(
            lambda: _assert(provider_b.document.get_text("t").to_string() == "up;")
        )
        assert "outage-doc" in ext_a._docs and "outage-doc" in ext_b._docs

        # the outage, mid-capture-window: publishes start failing
        await redis.stop()
        for i in range(5):
            provider_a.document.get_text("t").insert(3, f"dark{i};")
            await asyncio.sleep(0.01)
        expected = "up;" + "".join(f"dark{i};" for i in reversed(range(5)))

        # LOCAL serving survived the outage: doc still plane-served at A
        # and same-instance receivers stay live
        local = new_provider(server_a, name="outage-doc")
        try:
            await wait_synced(local)
            await retryable_assertion(
                lambda: _assert(
                    local.document.get_text("t").to_string()
                    == provider_a.document.get_text("t").to_string()
                )
            )
        finally:
            local.destroy()
        assert "outage-doc" in ext_a._docs, "publish failure degraded the plane"

        # redis returns; subscribers reconnect; the next change's
        # exchange heals the outage-window edits
        redis.port = port
        await redis.start()
        await retryable_assertion(
            lambda: _assert(
                len(redis.subscribers.get(b"hocuspocus:outage-doc", set())) >= 2
            )
        )
        provider_a.document.get_text("t").insert(0, "back;")
        await retryable_assertion(
            lambda: _assert(
                provider_b.document.get_text("t").to_string() == "back;" + expected
            )
        )
        # both planes are still serving this doc (no degradation)
        assert "outage-doc" in ext_a._docs and "outage-doc" in ext_b._docs
    finally:
        provider_a.destroy()
        provider_b.destroy()
        await server_a.destroy()
        await server_b.destroy()
        await redis.stop()


async def test_recycle_storm_concurrent_with_catchup_storm():
    """Row-recycling churn (append-only rows exhausted by insert+delete
    tombstones, docs recycling onto fresh rows) while a wave of cold
    joiners demands catch-up serves of the same docs. Every joiner must
    receive the full correct state — a recycle mid-serve must not hand
    out a half-rebuilt row — and every doc ends the storm either
    plane-served or counted."""
    # RLE arena: the production 100k-regime substrate, and the one where
    # a re-lowered snapshot is COMPACT (ContentDeleted runs cost one
    # entry each) so tombstone churn actually recycles instead of
    # re-exhausting the fresh row
    ext = TpuMergeExtension(
        num_docs=16,
        capacity=24,
        flush_interval_ms=1,
        serve=True,
        native_lane=False,
        arena="rle",
    )
    server = await new_hocuspocus(extensions=[ext])
    writers = []
    joiners = []
    try:
        n_docs = 4
        for d in range(n_docs):
            w = new_provider(server, name=f"storm-{d}")
            writers.append(w)
            await wait_synced(w)

        def exhausted() -> int:
            c = ext.plane.counters
            return c["docs_retired_overflow"] + c["docs_retired_capacity"]

        async def churn(d: int) -> None:
            # burst churn (insert + immediate delete leaves a tombstoned
            # run behind each cycle) until SOME doc exhausts its
            # 24-entry row; live snapshots stay tiny, which is exactly
            # the doc class recycling rescues
            text = writers[d].document.get_text("t")
            i = 0
            while exhausted() == 0 and i < 100:
                burst = f"d{d}burst{i};"
                base = len(text.to_string())
                text.insert(base, burst)
                text.delete(base, len(burst))
                i += 1
                await asyncio.sleep(0.02)

        async def join_wave(d: int, count: int) -> None:
            for _ in range(count):
                c = new_provider(server, name=f"storm-{d}")
                joiners.append((d, c))
                await asyncio.sleep(0.05)

        # the storm: burst-churn every doc while cold joiners arrive
        await asyncio.gather(
            *[churn(d) for d in range(n_docs)],
            *[join_wave(d, 4) for d in range(n_docs)],
        )
        assert exhausted() >= 1, ext.plane.counters

        # sparse nudges while the recycle queues behind warmup compiles
        # and piled flush cycles (tight churn would outgrow the fresh
        # row before the attempt takes the lock)
        for _ in range(60):
            if ext.plane.counters["docs_recycled"]:
                break
            for d in range(n_docs):
                writers[d].document.get_text("t").insert(0, "z")
            await asyncio.sleep(1.0)
        assert ext.plane.counters["docs_recycled"] >= 1, ext.plane.counters

        # every joiner converges to its writer's full state
        def all_converged():
            for d, c in joiners:
                want = writers[d].document.get_text("t").to_string()
                got = c.document.get_text("t").to_string()
                assert got == want, f"joiner of storm-{d} diverged"

        await retryable_assertion(all_converged)

        # accounting: each doc is live on the plane or counted as
        # retired/degraded — nothing vanished
        counters = ext.plane.counters
        retired = sum(
            counters[k]
            for k in counters
            if k.startswith("docs_retired_")
        )
        for d in range(n_docs):
            name = f"storm-{d}"
            if name not in ext._docs:
                assert retired > 0, f"{name} gone from the plane but never counted"

        # storm over: a fresh edit on every doc still propagates
        for d in range(n_docs):
            writers[d].document.get_text("t").insert(0, "after-storm;")
        await retryable_assertion(all_converged)
    finally:
        for _d, c in joiners:
            c.destroy()
        for w in writers:
            w.destroy()
        await server.destroy()


async def test_wedged_tpu_runtime_server_still_accepts_and_syncs():
    """A server configured with the TPU merge plane whose runtime is
    wedged (device discovery blocks forever) must still accept WebSocket connections and complete sync WITHIN
    the configured init deadline, serving on the CPU path. Previously
    plane construction blocked boot and the server served nothing."""
    import threading
    import time

    from hocuspocus_tpu.tpu import SupervisedTpuMergeExtension

    gate = threading.Event()

    def wedged_runtime_factory():
        gate.wait()  # simulated wedged TPU runtime: init never returns

    init_timeout = 2.0
    ext = SupervisedTpuMergeExtension(
        runtime_factory=wedged_runtime_factory,
        init_timeout=init_timeout,
        watchdog_interval=0.1,
    )
    started = time.monotonic()
    server = await new_hocuspocus(extensions=[ext])
    a = new_provider(server, name="verdict-doc")
    b = new_provider(server, name="verdict-doc")
    try:
        # connection + full sync handshake, bounded by the init deadline
        await wait_synced(a, b, timeout=init_timeout)
        assert time.monotonic() - started < init_timeout, (
            "sync must complete within the init deadline, not behind it"
        )
        a.document.get_text("t").insert(0, "availability first")
        await retryable_assertion(
            lambda: _assert(
                b.document.get_text("t").to_string() == "availability first"
            )
        )
        # the plane never came up; the supervisor says so
        await retryable_assertion(
            lambda: _assert(ext.supervisor.state == "degraded")
        )
        assert ext.health_status()["degraded"]
    finally:
        gate.set()
        a.destroy()
        b.destroy()
        await server.destroy()
