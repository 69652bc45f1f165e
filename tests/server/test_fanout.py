"""Broadcast fan-out engine (server/fanout.py): per-tick coalescing,
catch-up tiering, batched transport drains, shared frames.

The acceptance bar is CONVERGENCE EQUIVALENCE: coalesced + tiered
delivery must yield byte-identical document state to per-frame
delivery for every client — including clients that entered catch-up
mode mid-burst — while sending strictly fewer frames.
"""

from __future__ import annotations

import asyncio
import random

import pytest

from hocuspocus_tpu.crdt import (
    Doc,
    apply_update,
    encode_state_as_update,
)
from hocuspocus_tpu.crdt.encoding import Decoder
from hocuspocus_tpu.observability.wire import get_wire_telemetry
from hocuspocus_tpu.protocol.frames import parse_frame_header
from hocuspocus_tpu.protocol.message import MessageType
from hocuspocus_tpu.protocol.sync import (
    MESSAGE_YJS_SYNC_STEP2,
    MESSAGE_YJS_UPDATE,
    coalesce_updates,
)
from hocuspocus_tpu.server.connection import Connection
from hocuspocus_tpu.server.document import Document
from hocuspocus_tpu.server.transports import CallbackWebSocketTransport


def _apply_frame(doc: Doc, data: bytes) -> None:
    """Apply a server broadcast frame to a client-side doc (ignores
    awareness/stateless frames)."""
    _name, mtype, offset = parse_frame_header(data)
    if mtype not in (int(MessageType.Sync), int(MessageType.SyncReply)):
        return
    decoder = Decoder(data)
    decoder.pos = offset
    sub = decoder.read_var_uint()
    if sub in (MESSAGE_YJS_UPDATE, MESSAGE_YJS_SYNC_STEP2):
        apply_update(doc, decoder.read_var_uint8_array())


class FakeClient:
    """A real Connection + CallbackWebSocketTransport whose writer
    applies delivered frames to a client-side Doc. `gate` (when given)
    blocks the writer — the slow-consumer lever."""

    def __init__(self, document: Document, gate: asyncio.Event = None) -> None:
        self.doc = Doc()
        self.gate = gate
        self.frames: list[bytes] = []
        self.update_frames = 0

        async def send_async(data: bytes) -> None:
            if self.gate is not None:
                await self.gate.wait()
            self.frames.append(data)
            _name, mtype, _ = parse_frame_header(data)
            if mtype in (int(MessageType.Sync), int(MessageType.SyncReply)):
                self.update_frames += 1
            _apply_frame(self.doc, data)

        async def close_async(code: int, reason: str) -> None:
            pass

        self.transport = CallbackWebSocketTransport(send_async, close_async)
        self.connection = Connection(
            self.transport, None, document, f"sock-{id(self)}", {}
        )

    async def drained(self) -> None:
        while not self.transport.queue.empty():
            await asyncio.sleep(0.001)


@pytest.fixture
def low_watermark():
    wire = get_wire_telemetry()
    old = wire.backpressure_watermark
    wire.backpressure_watermark = 4
    yield wire
    wire.backpressure_watermark = old


# -- coalescing ------------------------------------------------------------


async def test_burst_coalesces_to_one_frame_per_tick():
    """N same-tick updates -> ONE update frame per connection, shared
    as the same bytes object across the audience."""
    document = Document("coalesce")
    clients = [FakeClient(document) for _ in range(3)]
    text = document.get_text("t")
    for i in range(5):
        text.insert(len(text), f"chunk-{i} ")
    await asyncio.sleep(0)  # tick flush
    for client in clients:
        await client.drained()
    for client in clients:
        assert client.update_frames == 1, "burst must coalesce to one frame"
        assert client.doc.get_text("t").to_string() == text.to_string()
    # the SAME frame object fans out to the whole audience (encode once)
    frames = {id(client.frames[-1]) for client in clients}
    assert len(frames) == 1


async def test_audience_snapshot_taken_once_per_tick():
    """One tick carrying updates AND awareness copies the registry
    exactly once."""
    document = Document("snapshot")
    FakeClient(document)
    calls = {"n": 0}
    real = document.get_connections

    def counting():
        calls["n"] += 1
        return real()

    document.get_connections = counting
    document.get_text("t").insert(0, "hello")
    document.awareness.set_local_state({"user": "a"})
    await asyncio.sleep(0)
    assert calls["n"] == 1, "update + awareness passes must share one snapshot"


async def test_broadcast_stateless_builds_frame_once():
    document = Document("stateless")
    clients = [FakeClient(document) for _ in range(4)]
    document.broadcast_stateless("server-push")
    for client in clients:
        await client.drained()
    payloads = [client.frames[-1] for client in clients]
    assert all(p is payloads[0] for p in payloads), "one shared frame object"
    _name, mtype, _ = parse_frame_header(payloads[0])
    assert mtype == int(MessageType.Stateless)


def test_coalesce_updates_merge_failure_returns_none():
    assert coalesce_updates([b"\x00garbage", b"\x01junk"]) is None


def test_no_loop_flush_is_immediate():
    """Direct/test use without a running loop: broadcast is synchronous
    (the old Document behavior)."""
    document = Document("direct")
    received = []

    class Conn:
        transport = object()

        def send(self, data):
            received.append(data)

    document.connections[Conn.transport] = {"clients": set(), "connection": Conn()}
    document.get_text("t").insert(0, "x")
    assert received, "no-loop path must fan out immediately"


# -- batched transport drains ---------------------------------------------


async def test_writer_drains_whole_queue_per_wake_as_batch():
    batches = []
    release = asyncio.Event()

    async def send_batch(frames):
        await release.wait()
        batches.append(list(frames))

    async def close_async(code, reason):
        pass

    transport = CallbackWebSocketTransport(
        lambda data: None, close_async, send_batch_async=send_batch
    )
    for i in range(6):
        transport.send(b"frame-%d" % i)
    release.set()
    await asyncio.sleep(0.01)
    # first wake may catch 1..6 frames; the union must be everything
    # and the batch count strictly less than the frame count
    assert sum(len(b) for b in batches) == 6
    assert len(batches) < 6
    transport.abort()


async def test_bounded_queue_overflow_closes_transport():
    wire = get_wire_telemetry()
    before = sum(wire.send_queue_overflows._values.values())
    closed = {}
    gate = asyncio.Event()

    async def send_async(data):
        await gate.wait()

    async def close_async(code, reason):
        closed["code"] = code
        closed["reason"] = reason

    transport = CallbackWebSocketTransport(send_async, close_async, max_queue=8)
    for i in range(20):
        transport.send(b"x" * 4)
    assert transport.is_closed, "overflow policy must close the transport"
    after = sum(wire.send_queue_overflows._values.values())
    assert after == before + 1
    gate.set()
    await asyncio.sleep(0.05)
    assert closed["code"] == 1013


async def test_drain_listener_fires_once_after_queue_empties():
    fired = []

    async def send_async(data):
        pass

    async def close_async(code, reason):
        pass

    transport = CallbackWebSocketTransport(send_async, close_async)
    transport.add_drain_listener(lambda: fired.append(1))
    transport.send(b"a")
    transport.send(b"b")
    await asyncio.sleep(0.05)
    assert fired == [1], "one-shot: exactly one notification"
    transport.send(b"c")
    await asyncio.sleep(0.05)
    assert fired == [1], "must re-register for another notification"
    transport.abort()


# -- catch-up tiering ------------------------------------------------------


async def test_slow_consumer_enters_and_exits_catchup_tier(low_watermark):
    """A stalled socket crosses the watermark -> tier entry (frames
    elided); on drain -> ONE SV-diff frame heals it."""
    document = Document("tier")
    gate = asyncio.Event()  # starts unset: writer stalls immediately
    slow = FakeClient(document, gate=gate)
    fast = FakeClient(document)
    text = document.get_text("t")
    for i in range(12):
        text.insert(len(text), f"word{i} ")
        await asyncio.sleep(0)  # one tick per update: 12 frames
    assert slow.connection.catchup.active, "watermark crossing must enter tier"
    queued_at_entry = slow.transport.queue.qsize()
    # while tiered, further broadcasts are elided for the slow socket
    for i in range(10):
        text.insert(len(text), f"late{i} ")
        await asyncio.sleep(0)
    assert slow.transport.queue.qsize() <= queued_at_entry + 1
    gate.set()  # socket recovers
    for _ in range(500):
        await asyncio.sleep(0.002)
        if not slow.connection.catchup.active and slow.transport.queue.empty():
            break
    assert not slow.connection.catchup.active, "drain must exit the tier"
    await fast.drained()
    await asyncio.sleep(0.01)
    server_bytes = encode_state_as_update(document)
    assert encode_state_as_update(slow.doc) == server_bytes
    assert encode_state_as_update(fast.doc) == server_bytes
    # the catch-up frame replaced the elided stream: far fewer frames
    assert slow.update_frames < fast.update_frames


async def test_tier_exit_covers_updates_whose_frames_never_fanned_out(low_watermark):
    """Regression: updates applied to the document but whose broadcast
    frames trail (plane-captured, window deferred to the flush timer)
    must still reach a tiered connection. A diff from an entry-time
    document SV would omit them forever; the full-state catch-up frame
    cannot."""

    class CapturingSource:
        """Plane stand-in: claims every update (suppressing CPU
        fan-out), never broadcasts — the worst-case deferral."""

        def try_capture(self, document, update, origin):
            return True

    document = Document("deferred")
    gate = asyncio.Event()
    slow = FakeClient(document, gate=gate)
    text = document.get_text("t")
    # stream enough frames to cross the watermark and enter the tier
    for i in range(10):
        text.insert(len(text), f"w{i} ")
        await asyncio.sleep(0)
    assert slow.connection.catchup.active
    # now an update lands that is CAPTURED (no frame ever fans out)
    document.broadcast_source = CapturingSource()
    text.insert(len(text), "CAPTURED-NEVER-BROADCAST ")
    await asyncio.sleep(0)
    document.broadcast_source = None
    gate.set()
    for _ in range(500):
        await asyncio.sleep(0.002)
        if (
            not slow.connection.catchup.active
            and slow.transport.queue.empty()
            and slow.connection.catchup._exit_task is None
        ):
            break
    assert encode_state_as_update(slow.doc) == encode_state_as_update(document)
    assert "CAPTURED-NEVER-BROADCAST" in slow.doc.get_text("t").to_string()


async def test_tier_counts_transitions(low_watermark):
    wire = get_wire_telemetry()
    wire.enable()
    try:
        entries0 = wire.catchup_tier_transitions.value(transition="enter")
        exits0 = wire.catchup_tier_transitions.value(transition="exit")
        document = Document("tier-count")
        gate = asyncio.Event()
        slow = FakeClient(document, gate=gate)
        text = document.get_text("t")
        for i in range(10):
            text.insert(len(text), "x" * 8)
            await asyncio.sleep(0)
        assert slow.connection.catchup.active
        gate.set()
        for _ in range(500):
            await asyncio.sleep(0.002)
            if not slow.connection.catchup.active:
                break
        assert wire.catchup_tier_transitions.value(transition="enter") == entries0 + 1
        assert wire.catchup_tier_transitions.value(transition="exit") == exits0 + 1
    finally:
        wire.disable()


# -- the convergence fuzz (acceptance criterion) ---------------------------


async def test_fuzz_coalesced_and_tiered_delivery_converges(low_watermark):
    """N clients under random bursty writes — one flapping into/out of
    catch-up tier mid-stream, one control applying every raw update
    per-frame — all converge to byte-identical state."""
    rng = random.Random(1234)
    document = Document("fuzz")
    gate = asyncio.Event()
    gate.set()
    clients = [FakeClient(document) for _ in range(5)]
    slow = FakeClient(document, gate=gate)

    # per-frame control: byte-identical convergence proves coalesced
    # delivery equivalent to the reference's per-update fan-out
    control = Doc()
    document.on(
        "update", lambda update, origin, doc, txn: apply_update(control, update)
    )

    text = document.get_text("t")
    for rnd in range(60):
        for _ in range(rng.randint(1, 5)):  # same-tick burst
            pos = rng.randint(0, len(text))
            text.insert(pos, rng.choice("abcdefgh") * rng.randint(1, 4))
            if len(text) > 6 and rng.random() < 0.35:
                text.delete(rng.randint(0, len(text) - 3), rng.randint(1, 2))
        if rnd in (10, 35):
            gate.clear()  # stall mid-burst -> tier entry
        if rnd in (25, 50):
            gate.set()  # recover -> SV-diff catch-up
        await asyncio.sleep(0)
        if rng.random() < 0.3:
            await asyncio.sleep(0)  # vary tick boundaries
    gate.set()
    for _ in range(1000):
        await asyncio.sleep(0.002)
        if (
            all(c.transport.queue.empty() for c in clients + [slow])
            and not slow.connection.catchup.active
        ):
            break

    server_bytes = encode_state_as_update(document)
    assert encode_state_as_update(control) == server_bytes
    for i, client in enumerate(clients + [slow]):
        assert encode_state_as_update(client.doc) == server_bytes, f"client {i}"
    assert slow.connection.catchup.active is False
    # coalescing saved real frames: every client saw fewer update
    # frames than raw updates were produced
    raw_updates = 60 * 3  # rough lower bound on average burst size
    assert clients[0].update_frames < raw_updates


async def test_plane_broadcast_rides_tick_and_closes_trace_at_last_enqueue():
    """Document.queue_broadcast defers to the tick and fires
    on_complete with the last-socket-enqueue timestamp."""
    import time

    document = Document("plane-tick")
    client = FakeClient(document)
    marks: list[float] = []
    update = None

    captured = []
    probe = Doc()
    probe.on("update", lambda u, *a: captured.append(u))
    probe.get_text("t").insert(0, "window")
    update = captured[0]

    t0 = time.perf_counter()
    document.queue_broadcast(update, on_complete=marks.append)
    assert not marks, "fan-out must defer to the tick, not run inline"
    await asyncio.sleep(0)
    assert len(marks) == 1 and marks[0] >= t0
    await client.drained()
    assert client.doc.get_text("t").to_string() == "window"


# -- durability gates: a commit delivers its own ticks -----------------------


def _logged(wal, name: str, order: list, audience: int = 1):
    """A document whose updates go to `wal` before any broadcast, as
    storage/extension.py wires it, with clients that note every enqueue."""
    document = Document(name)
    document.wal_sink = lambda update, origin: wal.append(name, update)
    clients = [FakeClient(document) for _ in range(audience)]
    for client in clients:
        send = client.connection.send

        def noting(data, _send=send):
            order.append(name)
            _send(data)

        client.connection.send = noting
    return document, clients


async def test_gated_ticks_are_enqueued_in_the_turn_their_commit_lands(tmp_path):
    """No frame of a gated tick is enqueued before `_commit` has returned,
    and every one of them is by the end of the loop callback in which the
    commit's completion lands: the same turn, not two later."""
    from hocuspocus_tpu.storage import WalManager
    from tests.utils import HoldingFaults, TurnCounter

    faults = HoldingFaults()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    events: list = []
    document, clients = _logged(wal, "gated", events, audience=3)
    turns = TurnCounter()
    real_commit, real_done = wal._commit, wal._commit_done
    sent_at: list = []

    def commit(pending):
        real_commit(pending)
        events.append("commit returned")

    def commit_done(gate, since):
        real_done(gate, since)
        sent_at.append((turns.turn, list(events)))

    wal._commit, wal._commit_done = commit, commit_done
    try:
        text = document.get_text("t")
        text.insert(0, "durable ")
        await faults.held()
        text.insert(len(text), "first")  # a second tick behind the same held commit
        await faults.held()
        assert events == [], "a frame outran the commit that covers it"
        assert wal.stats["ticks_released"] == 0
        faults.release.set()
        await asyncio.wait_for(wal.flush(), timeout=5)
    finally:
        turns.stop()
    # the first commit released the first tick, the second the second one;
    # each had every frame enqueued when its commit-done step returned
    assert sent_at[0][1] == ["commit returned"] + ["gated"] * 3
    assert sent_at[1][1] == sent_at[0][1] + ["commit returned"] + ["gated"] * 3
    assert sent_at[1][0] > sent_at[0][0]
    assert wal.stats["ticks_released"] == 2 and wal.stats["commit_batches"] == 2
    for client in clients:
        await client.drained()
        assert client.doc.get_text("t").to_string() == "durable first"


async def test_gated_ticks_of_two_commits_deliver_in_append_order(tmp_path):
    from hocuspocus_tpu.storage import WalManager
    from tests.utils import HoldingFaults

    faults = HoldingFaults()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    order: list = []
    doc_a, (client_a,) = _logged(wal, "a", order)
    doc_b, (client_b,) = _logged(wal, "b", order)
    text_a, text_b = doc_a.get_text("t"), doc_b.get_text("t")
    text_a.insert(0, "a1 ")  # first commit: one tick of each document
    text_b.insert(0, "b1 ")
    await faults.held()
    first = doc_a._wal_gate
    for text, step in ((text_a, "a2 "), (text_b, "b2 "), (text_a, "a3 ")):
        text.insert(len(text), step)  # behind the held commit: the second one's
        await asyncio.sleep(0)
    assert doc_a._wal_gate is doc_b._wal_gate is not first
    assert order == [] and len(doc_a.fanout._gated) == 3 and len(doc_b.fanout._gated) == 2
    faults.release.set()
    await asyncio.wait_for(wal.flush(), timeout=5)
    assert order == ["a", "b", "a", "b", "a"]
    assert doc_a.fanout._gated == [] and doc_b.fanout._gated == []
    assert wal.stats["ticks_released"] == 5 and wal.stats["commit_batches"] == 2
    await client_a.drained()
    await client_b.drained()
    assert client_a.doc.get_text("t").to_string() == "a1 a2 a3 "
    assert client_b.doc.get_text("t").to_string() == "b1 b2 "
    assert client_a.update_frames == 3 and client_b.update_frames == 2


async def test_a_document_destroyed_while_gated_delivers_nothing(tmp_path):
    from hocuspocus_tpu.storage import WalManager
    from tests.utils import HoldingFaults

    faults = HoldingFaults()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    order: list = []
    document, _clients = _logged(wal, "gone", order)
    survivor, _ = _logged(wal, "stays", order)
    document.get_text("t").insert(0, "never shown")
    survivor.get_text("t").insert(0, "shown")
    await faults.held()
    gate = document._wal_gate
    assert len(gate._on_release) == 2 and len(document.fanout._gated) == 1
    document.destroy()
    # nothing of the destroyed document stays registered on the gate
    assert len(gate._on_release) == 1 and document.fanout._gated == []
    faults.release.set()
    await asyncio.wait_for(wal.flush(), timeout=5)
    assert order == ["stays"]
    assert wal.stats["ticks_released"] == 1


async def test_ticks_released_counts_gated_ticks_only(tmp_path):
    from hocuspocus_tpu.storage import WalManager

    wal = WalManager(str(tmp_path), fsync="tick")
    order: list = []
    document, (client,) = _logged(wal, "counted", order)
    unlogged = Document("no-log")
    FakeClient(unlogged)
    document.get_text("t").insert(0, "gated")
    unlogged.get_text("t").insert(0, "no gate at all")
    await asyncio.wait_for(wal.flush(), timeout=5)
    assert wal.stats["ticks_released"] == 1 and order == ["counted"]
    # a plane window queued after its commit has landed finds the gate
    # done: delivered from the tick itself, and not counted
    probe = Doc()
    captured: list = []
    probe.on("update", lambda update, *rest: captured.append(update))
    probe.get_text("t").insert(0, "window")
    assert document._wal_gate.done()
    document.queue_broadcast(captured[0])
    document.awareness.set_local_state({"cursor": 1})
    await asyncio.sleep(0)
    assert order == ["counted"] * 3  # the window, and the awareness tick
    assert wal.stats["ticks_released"] == 1
    await document.wait_wal_durable()  # nothing open: returns at once
    await client.drained()


async def test_a_joiner_that_goes_away_does_not_cancel_the_gate_of_the_ticks(tmp_path):
    from hocuspocus_tpu.storage import WalManager
    from tests.utils import HoldingFaults

    faults = HoldingFaults()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    order: list = []
    document, (client,) = _logged(wal, "joined", order)
    document.get_text("t").insert(0, "kept")
    await faults.held()
    joiner = asyncio.ensure_future(document.wait_wal_durable())
    await asyncio.sleep(0)
    joiner.cancel()
    await asyncio.sleep(0)
    assert joiner.cancelled() and not document._wal_gate.done() and order == []
    faults.release.set()
    await asyncio.wait_for(document.wait_wal_durable(), timeout=5)
    assert order == ["joined"] and wal.stats["ticks_released"] == 1
    await client.drained()
    assert client.doc.get_text("t").to_string() == "kept"
