"""Pipelined cross-instance replication lane (ISSUE 8).

Covers the fast path end to end: the fire-and-forget pipelined RESP
client (enqueue-only publishes, background reply reader, reconnect
resync), per-tick publish coalescing through the broadcast-tick seam,
the batched inbound inbox with overflow -> anti-entropy healing, the
single-round-trip store-lock acquire, and mini_redis's bounded
per-subscriber queues with slow-subscriber disconnect.
"""

import asyncio
import random

import pytest

from hocuspocus_tpu.crdt import encode_state_as_update
from hocuspocus_tpu.extensions import Redis
from hocuspocus_tpu.net.mini_redis import MiniRedis
from hocuspocus_tpu.net.resp import (
    PipelinedRedisClient,
    RedisClient,
    RedisSubscriber,
    RespError,
    encode_command,
)

from tests.utils import new_hocuspocus, new_provider, retryable_assertion, wait_synced


def _assert(cond):
    assert cond


# -- pipelined client ---------------------------------------------------------


async def test_pipelined_publishes_batch_into_few_flushes():
    """N same-tick publish_nowait calls ship as ONE write+drain — the
    flush count stays far below the command count and every frame still
    arrives."""
    redis = await MiniRedis().start()
    received = []
    sub = RedisSubscriber(
        port=redis.port, on_message=lambda ch, data: received.append(data)
    )
    try:
        await sub.subscribe("lane")
        client = PipelinedRedisClient(port=redis.port)
        for i in range(64):
            client.publish_nowait("lane", b"m%d" % i)
        await retryable_assertion(lambda: _assert(len(received) == 64))
        assert received == [b"m%d" % i for i in range(64)], "order must hold"
        assert client.counters["publishes"] == 64
        # one enqueue tick -> one (maybe two, if the connect ate a tick)
        # flush batches, not 64 round trips
        assert client.counters["flushes"] <= 4
        assert client.counters["max_batch"] >= 16
        # the reply reader consumes every ack
        await retryable_assertion(lambda: _assert(client.pending == 0))
        assert client.counters["reply_errors"] == 0
        client.close()
    finally:
        sub.close()
        await redis.stop()


async def test_pipelined_execute_rides_the_lane():
    """execute/execute_many share the pipeline: replies resolve in
    order, error replies surface per command without desyncing the
    stream (commands after the error still answer correctly)."""
    redis = await MiniRedis().start()
    try:
        client = PipelinedRedisClient(port=redis.port)
        assert await client.ping()
        await client.set("k", b"v")
        assert await client.get("k") == b"v"
        replies = await client.execute_many(
            [("SET", "a", "1"), ("BOGUS",), ("GET", "a")]
        )
        assert replies[0] == "OK"
        assert isinstance(replies[1], RespError)
        assert replies[2] == b"1"
        assert client.counters["reply_errors"] == 1
        # the stream stayed in sync after the error reply
        assert await client.get("k") == b"v"
        client.close()
    finally:
        await redis.stop()


async def test_pipelined_reply_error_accounting_for_publishes():
    """A fire-and-forget command that errors is COUNTED (reply reader)
    and later publishes keep working."""
    redis = await MiniRedis().start()
    received = []
    sub = RedisSubscriber(
        port=redis.port, on_message=lambda ch, data: received.append(data)
    )
    try:
        await sub.subscribe("chan")
        client = PipelinedRedisClient(port=redis.port)
        # smuggle an erroring command through the fire-and-forget lane
        client._enqueue(encode_command("NOSUCH"), None)
        client.publish_nowait("chan", b"after-error")
        await retryable_assertion(lambda: _assert(received == [b"after-error"]))
        assert client.counters["reply_errors"] == 1
        client.close()
    finally:
        sub.close()
        await redis.stop()


async def test_reconnect_mid_pipeline_flushes_or_resends():
    """Kill the server with commands buffered and in flight; after the
    restart the lane must resync — buffered commands are flushed or
    resent on the fresh socket, never half-written — and new publishes
    flow again."""
    redis = await MiniRedis().start()
    port = redis.port
    client = PipelinedRedisClient(port=port)
    try:
        assert await client.ping()  # establish the connection
        await redis.stop()
        # enqueue against the dead server: these must survive the resync
        for i in range(8):
            client.publish_nowait("chan", b"r%d" % i)
        redis = await MiniRedis(port=port).start()
        received = []
        sub = RedisSubscriber(
            port=port, on_message=lambda ch, data: received.append(data)
        )
        try:
            await sub.subscribe("chan")
            # at-most-once per attempt: anything the resync window
            # dropped is bounded by the shed path; everything else must
            # arrive intact and in order. Publish a sentinel through the
            # healed lane to prove the stream is byte-aligned.
            client.publish_nowait("chan", b"sentinel")
            await retryable_assertion(lambda: _assert(b"sentinel" in received))
            dropped = client.counters["dropped"]
            survived = [f for f in received if f != b"sentinel"]
            assert len(survived) + dropped >= 8, (
                f"frames vanished unaccounted: {survived} dropped={dropped}"
            )
            assert survived == sorted(survived), "resend must preserve order"
            # the healed connection still answers request/response
            assert await client.ping()
        finally:
            sub.close()
    finally:
        client.close()
        await redis.stop()


async def test_acquire_lock_single_round_trip_and_contention():
    """The execute_many acquire path: SET NX + holder GET in one
    pipelined round trip, correct under contention."""
    redis = await MiniRedis().start()
    try:
        a = PipelinedRedisClient(port=redis.port)
        b = RedisClient(port=redis.port)  # execute_many path too
        assert await a.acquire_lock("lk", "tok-a", 5000)
        assert not await b.acquire_lock("lk", "tok-b", 5000)
        assert await a.release_lock("lk", "tok-a")
        assert await b.acquire_lock("lk", "tok-b", 5000)
        a.close()
        b.close()
    finally:
        await redis.stop()


# -- two-instance convergence -------------------------------------------------


async def _fuzz_two_instances(fast_path: bool, seed: int) -> None:
    """Random concurrent edits on both instances; both documents must
    converge to byte-identical state."""
    rng = random.Random(seed)
    redis = await MiniRedis().start()
    kwargs = dict(port=redis.port, disconnect_delay=100)
    if not fast_path:
        kwargs.update(pipeline=False, coalesce=False, inbox_batch=False)
    server_a = await new_hocuspocus(extensions=[Redis(identifier="fz-a", **kwargs)])
    server_b = await new_hocuspocus(extensions=[Redis(identifier="fz-b", **kwargs)])
    provider_a = new_provider(server_a, name="fuzz-doc")
    provider_b = new_provider(server_b, name="fuzz-doc")
    try:
        await wait_synced(provider_a, provider_b)
        texts = [provider_a.document.get_text("t"), provider_b.document.get_text("t")]
        for round_no in range(12):
            for text in texts:
                for _ in range(rng.randrange(1, 4)):
                    if len(text) and rng.random() < 0.3:
                        start = rng.randrange(len(text))
                        text.delete(start, min(len(text) - start, rng.randrange(1, 4)))
                    else:
                        pos = rng.randrange(len(text) + 1)
                        text.insert(pos, f"{round_no}x{rng.randrange(100)}")
            await asyncio.sleep(0.02)

        def converged():
            sa = provider_a.document.get_text("t").to_string()
            sb = provider_b.document.get_text("t").to_string()
            _assert(sa == sb and len(sa) > 0)
            # byte-identical FINAL STATES, not just equal strings: the
            # full encoded update (structs + tombstones) must agree
            ua = encode_state_as_update(provider_a.document)
            ub = encode_state_as_update(provider_b.document)
            _assert(ua == ub)

        await retryable_assertion(converged, timeout=20)
    finally:
        provider_a.destroy()
        provider_b.destroy()
        await server_a.destroy()
        await server_b.destroy()
        await redis.stop()


async def test_two_instance_convergence_fuzz_fast_path_on():
    await _fuzz_two_instances(fast_path=True, seed=8)


async def test_two_instance_convergence_fuzz_fast_path_off():
    """The differential leg: per-op publishing/applying converges to the
    same place, proving coalescing+pipelining change cost, not
    semantics."""
    await _fuzz_two_instances(fast_path=False, seed=8)


async def test_fast_path_actually_coalesces_and_pipelines():
    """Under a burst, the lane must publish FEWER frames than updates
    (frames_saved > 0) and ship >1 command per pipelined flush on
    average."""
    redis = await MiniRedis().start()
    ext_a = Redis(port=redis.port, identifier="co-a", disconnect_delay=100)
    server_a = await new_hocuspocus(extensions=[ext_a])
    server_b = await new_hocuspocus(
        extensions=[Redis(port=redis.port, identifier="co-b", disconnect_delay=100)]
    )
    provider_a = new_provider(server_a, name="burst-doc")
    provider_b = new_provider(server_b, name="burst-doc")
    try:
        await wait_synced(provider_a, provider_b)
        text = provider_a.document.get_text("t")
        for burst in range(6):
            for i in range(8):  # one tick's burst at the server
                text.insert(len(text), f"b{burst}i{i};")
            await asyncio.sleep(0.05)
        await retryable_assertion(
            lambda: _assert(
                provider_b.document.get_text("t").to_string() == text.to_string()
            )
        )
        stats = ext_a.replication_stats
        assert stats["updates_enqueued"] > stats["update_frames_published"]
        assert stats["frames_saved"] > 0
        pub = ext_a.pub
        assert pub.counters["flushes"] > 0
        assert pub.counters["commands_flushed"] / pub.counters["flushes"] > 1.0
    finally:
        provider_a.destroy()
        provider_b.destroy()
        await server_a.destroy()
        await server_b.destroy()
        await redis.stop()


async def test_inbox_overflow_heals_via_anti_entropy():
    """Flood instance B's tiny inbox: frames are dropped (counted) but
    the drain publishes an anti-entropy SyncStep1 and the doc converges
    anyway — loss is never silent."""
    redis = await MiniRedis().start()
    ext_b = Redis(
        port=redis.port, identifier="ov-b", disconnect_delay=100, inbox_limit=2
    )
    server_a = await new_hocuspocus(
        extensions=[Redis(port=redis.port, identifier="ov-a", disconnect_delay=100)]
    )
    server_b = await new_hocuspocus(extensions=[ext_b])
    provider_a = new_provider(server_a, name="flood-doc")
    provider_b = new_provider(server_b, name="flood-doc")
    try:
        await wait_synced(provider_a, provider_b)
        text = provider_a.document.get_text("t")
        # block B's inbox drains (the drain task serializes on this
        # lock) so inbound frames PILE UP against the bound instead of
        # draining once per tick
        await ext_b._drain_lock.acquire()
        try:
            for i in range(40):
                text.insert(len(text), f"f{i};")
                await asyncio.sleep(0.005)
            await retryable_assertion(
                lambda: _assert(ext_b.replication_stats["inbox_overflows"] > 0),
                timeout=10,
            )
        finally:
            ext_b._drain_lock.release()
        await retryable_assertion(
            lambda: _assert(
                provider_b.document.get_text("t").to_string() == text.to_string()
                and len(text) > 0
            ),
            timeout=20,
        )
    finally:
        provider_a.destroy()
        provider_b.destroy()
        await server_a.destroy()
        await server_b.destroy()
        await redis.stop()


# -- telemetry ----------------------------------------------------------------


async def test_replication_metrics_on_metrics_endpoint():
    """The hocuspocus_redis_* family renders on /metrics (deterministic
    exposition) once the Metrics extension enables wire telemetry, and
    the pipeline/coalescing counters actually move under traffic."""
    import aiohttp

    from hocuspocus_tpu.observability import Metrics

    redis = await MiniRedis().start()
    server_a = await new_hocuspocus(
        extensions=[
            Redis(port=redis.port, identifier="mx-a", disconnect_delay=100),
            Metrics(),
        ]
    )
    server_b = await new_hocuspocus(
        extensions=[Redis(port=redis.port, identifier="mx-b", disconnect_delay=100)]
    )
    provider_a = new_provider(server_a, name="metric-doc")
    provider_b = new_provider(server_b, name="metric-doc")
    try:
        await wait_synced(provider_a, provider_b)
        text = provider_a.document.get_text("t")
        for i in range(12):
            text.insert(len(text), f"m{i};")
        await retryable_assertion(
            lambda: _assert(
                provider_b.document.get_text("t").to_string() == text.to_string()
            )
        )
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{server_a.http_url}/metrics") as response:
                assert response.status == 200
                body = await response.text()
        for family in (
            "hocuspocus_redis_pipeline_depth",
            "hocuspocus_redis_flush_batch_commands",
            "hocuspocus_redis_publish_flush_seconds",
            "hocuspocus_redis_reply_errors_total",
            "hocuspocus_redis_inbox_depth",
            "hocuspocus_redis_inbox_drained_frames",
            "hocuspocus_redis_inbox_overflow_total",
            "hocuspocus_redis_frames_saved_total",
        ):
            assert family in body, f"{family} missing from /metrics"
        # flushes happened (batch histogram counted samples)
        count_line = next(
            line
            for line in body.splitlines()
            if line.startswith("hocuspocus_redis_flush_batch_commands_count")
        )
        assert float(count_line.split()[-1]) > 0
    finally:
        provider_a.destroy()
        provider_b.destroy()
        await server_a.destroy()
        await server_b.destroy()
        await redis.stop()


# -- mini_redis bounded subscriber queues ------------------------------------


async def test_mini_redis_disconnects_slow_subscriber():
    """A subscriber that never reads fills its bounded queue; mini_redis
    disconnects it and counts the dropped frame — fast consumers on the
    same channel keep receiving."""
    redis = await MiniRedis(subscriber_queue_limit=8).start()
    try:
        # raw slow subscriber: subscribes, then never reads again
        reader, writer = await asyncio.open_connection("127.0.0.1", redis.port)
        from hocuspocus_tpu.net.resp import encode_command

        writer.write(encode_command("SUBSCRIBE", "busy"))
        await writer.drain()
        await reader.readexactly(1)  # first confirmation byte: subscribed

        received = []
        fast = RedisSubscriber(
            port=redis.port, on_message=lambda ch, data: received.append(data)
        )
        await fast.subscribe("busy")
        pub = RedisClient(port=redis.port)
        # the OS buffers on both ends absorb early frames, megabytes of
        # them where the kernel grows the buffers; keep publishing until
        # the mini-redis queue jams and the client is dropped, under a
        # deadline and not a count
        loop = asyncio.get_running_loop()
        deadline = loop.time() + 30.0
        while redis.counters["slow_disconnects"] == 0 and loop.time() < deadline:
            await pub.publish("busy", b"x" * 512)
        assert redis.counters["slow_disconnects"] == 1
        assert redis.counters["dropped_slow"] >= 1
        # the fast subscriber never stopped receiving
        before = len(received)
        await pub.publish("busy", b"final")
        await retryable_assertion(lambda: _assert(b"final" in received))
        assert len(received) > before
        pub.close()
        fast.close()
        writer.close()
    finally:
        await redis.stop()


# -- outage hardening: byte-capped outbox + partition heal (ISSUE 12) ---------


def test_pipelined_outbox_byte_cap_sheds_oldest_publishes():
    """During a transport outage the outbox must stay byte-bounded:
    enqueues past `max_outbox_bytes` shed the OLDEST publishes with
    accounting (never OOM), the newest frames survive, and the shed
    arms the resync hook."""
    # no running loop: publishes buffer without a flush task, exactly
    # like an outage window between flush cycles
    client = PipelinedRedisClient(port=1, max_outbox_bytes=2048)
    payload = b"p" * 128
    for i in range(64):
        client.publish_nowait("lane", b"%03d-" % i + payload)
    assert client.counters["dropped"] > 0
    assert client.counters["shed_bytes"] > 0
    assert client._outbox_bytes <= client.max_outbox_bytes
    # oldest-first: the latest publish is still buffered, the first is gone
    encoded = b"".join(c.encoded for c in client._outbox)
    assert b"063-" in encoded
    assert b"000-" not in encoded
    assert client._needs_resync
    # accounting closes: dropped + buffered == published
    assert client.counters["dropped"] + len(client._outbox) == 64
    client.close()


async def test_pipelined_resync_fires_after_outage_heals():
    """Publishes shed while the server is unreachable arm `on_resync`;
    the first successful reconnect fires it exactly once (the Redis
    extension wires this to its SyncStep1 anti-entropy exchange)."""
    redis = await MiniRedis().start()
    port = redis.port
    fired = []
    client = PipelinedRedisClient(port=port, reconnect_delay=0.01)
    client.on_resync = lambda: fired.append(1)
    try:
        client.publish_nowait("lane", b"before")
        await retryable_assertion(lambda: _assert(client.pending == 0))
        await redis.stop()
        # outage: these publishes are shed with accounting
        for i in range(4):
            client.publish_nowait("lane", b"lost-%d" % i)
        await retryable_assertion(lambda: _assert(client.counters["dropped"] > 0))
        assert not fired, "resync must wait for the reconnect"
        redis = await MiniRedis(port=port).start()
        client.publish_nowait("lane", b"after")
        await retryable_assertion(lambda: _assert(fired == [1]))
        # later flushes do not re-fire a consumed resync
        client.publish_nowait("lane", b"steady")
        await retryable_assertion(lambda: _assert(client.pending == 0))
        assert fired == [1]
    finally:
        client.close()
        await redis.stop()


async def test_one_way_partition_accounted_and_healed_by_anti_entropy():
    """Chaos acceptance (docs/guides/overload.md): one-way partition
    instance A's publishes at the mini_redis hop — every dropped
    publish is ACCOUNTED (`dropped_partition`), B diverges, and after
    the heal the anti-entropy SyncStep1 exchange reconverges both
    instances to byte-identical state with zero silent loss."""
    redis = await MiniRedis().start()
    ext_a = Redis(port=redis.port, identifier="pt-a", disconnect_delay=100)
    ext_b = Redis(port=redis.port, identifier="pt-b", disconnect_delay=100)
    # CI-scale anti-entropy cadence so the heal lands inside the test
    ext_a.plane_anti_entropy_seconds = 0.2
    ext_b.plane_anti_entropy_seconds = 0.2
    server_a = await new_hocuspocus(extensions=[ext_a])
    server_b = await new_hocuspocus(extensions=[ext_b])
    provider_a = new_provider(server_a, name="part-doc")
    provider_b = new_provider(server_b, name="part-doc")
    try:
        await wait_synced(provider_a, provider_b)
        text_a = provider_a.document.get_text("t")
        text_a.insert(0, "linked.")
        await retryable_assertion(
            lambda: _assert(
                provider_b.document.get_text("t").to_string() == "linked."
            )
        )
        # ONE-WAY partition: A's publishes blackhole, B->A still flows
        redis.partition_publisher("pt-a")
        text_a.insert(0, "dark-")
        await retryable_assertion(
            lambda: _assert(redis.counters["dropped_partition"] > 0)
        )
        # B never sees the partition-era edit (the drop is real)
        await asyncio.sleep(0.3)
        assert provider_b.document.get_text("t").to_string() == "linked."
        dropped = redis.counters["dropped_partition"]
        assert dropped > 0
        # heal: the next change's anti-entropy exchange reconverges
        redis.heal_partition()
        text_a.insert(0, "healed-")

        def converged():
            sa = provider_a.document.get_text("t").to_string()
            sb = provider_b.document.get_text("t").to_string()
            _assert(sa == sb == "healed-dark-linked.")
            _assert(
                encode_state_as_update(provider_a.document)
                == encode_state_as_update(provider_b.document)
            )

        await retryable_assertion(converged, timeout=20)
    finally:
        provider_a.destroy()
        provider_b.destroy()
        await server_a.destroy()
        await server_b.destroy()
        await redis.stop()


def test_pipelined_outbox_cap_never_sheds_a_single_oversized_frame():
    """The byte cap bounds ACCUMULATION, not single-frame size: one
    frame larger than the whole cap must survive enqueue (shedding it
    would loop forever — the anti-entropy heal republishes the same
    frame), while older buffered publishes still shed around it."""
    client = PipelinedRedisClient(port=1, max_outbox_bytes=1024)
    client.publish_nowait("lane", b"old-" + b"x" * 256)
    client.publish_nowait("lane", b"huge-" + b"y" * 4096)  # alone > cap
    assert any(b"huge-" in c.encoded for c in client._outbox)
    assert client.counters["dropped"] == 1  # the old frame, not the huge one
    # and a lone oversized enqueue on an empty outbox is never dropped
    client2 = PipelinedRedisClient(port=1, max_outbox_bytes=64)
    client2.publish_nowait("lane", b"z" * 1024)
    assert len(client2._outbox) == 1
    assert client2.counters["dropped"] == 0
    client.close()
    client2.close()
