"""Write-through (server/transports.py): `send()` writes a frame itself, in
the caller's turn of the loop, when nothing could be ahead of it and the
socket is not held back; otherwise the frame takes the queue and the writer
task. Either way a connection's frames leave in the order `send` was called.
"""

from __future__ import annotations

import asyncio
import random
import types

import pytest

from hocuspocus_tpu.observability.tracing import get_tracer
from hocuspocus_tpu.observability.wire import get_wire_telemetry
from hocuspocus_tpu.server.connection import Connection
from hocuspocus_tpu.server.document import Document
from hocuspocus_tpu.server.transports import CallbackWebSocketTransport


class Socket:
    """A socket as aiohttp's writer presents one: `send` buffers the frame
    at once and suspends only while the socket is paused (the drain)."""

    def __init__(self, **options) -> None:
        self.wrote: list = []  # (frame, "inline" | "writer")
        self.closed_with = None
        self.paused = False
        self._resumed = asyncio.Event()
        self.transport = CallbackWebSocketTransport(
            self.send, self.close, writable=lambda: not self.paused, **options
        )

    async def send(self, data: bytes) -> None:
        by_writer = asyncio.current_task() is self.transport._writer_task
        self.wrote.append((data, "writer" if by_writer else "inline"))
        if self.paused:
            await self._resumed.wait()

    async def close(self, code: int, reason: str) -> None:
        self.closed_with = code

    def pause(self) -> None:
        self.paused = True
        self._resumed.clear()

    def resume(self) -> None:
        self.paused = False
        self._resumed.set()

    @property
    def frames(self) -> list:
        return [data for data, _by in self.wrote]

    async def parked(self) -> "Socket":
        """The writer task has started and waits on its empty queue."""
        for _ in range(1000):
            if self.transport._idle and self.transport.queue.empty():
                return self
            await asyncio.sleep(0)
        raise AssertionError("the writer never parked")


def _written() -> "tuple[int, int]":
    wire = get_wire_telemetry()
    return wire.frames_written_inline, wire.frames_written_queued


async def test_an_idle_transport_writes_in_the_callers_turn():
    socket = await Socket().parked()
    inline, queued = _written()
    socket.transport.send(b"now")
    assert socket.wrote == [(b"now", "inline")], "the frame waited for the writer task"
    assert socket.transport.queue.empty()
    assert _written() == (inline + 1, queued)
    socket.transport.abort()


async def test_a_frame_queues_behind_one_already_queued():
    socket = Socket()  # the writer task has not run yet: nothing is parked
    inline, queued = _written()
    socket.transport.send(b"first")
    socket.transport.send(b"second")
    assert socket.wrote == [] and socket.transport.queue.qsize() == 2
    await socket.parked()
    assert socket.wrote == [(b"first", "writer"), (b"second", "writer")]
    assert _written() == (inline, queued + 2)
    socket.transport.abort()


async def test_a_frame_queues_while_the_writer_is_in_the_middle_of_a_send():
    socket = Socket()
    socket.pause()
    socket.transport.send(b"held")  # queued: the socket is held back
    await asyncio.sleep(0.01)
    assert socket.wrote == [(b"held", "writer")] and socket.transport.queue.empty()
    socket.paused = False  # writable again, but the writer still sits in its send
    socket.transport.send(b"behind")
    assert socket.frames == [b"held"] and socket.transport.queue.qsize() == 1
    socket.resume()
    await socket.parked()
    assert socket.wrote == [(b"held", "writer"), (b"behind", "writer")]
    socket.transport.abort()


async def test_a_frame_queues_while_the_socket_is_paused():
    socket = await Socket().parked()
    socket.pause()
    socket.transport.send(b"later")
    assert socket.wrote == [] and socket.transport.queue.qsize() == 1
    socket.resume()
    await socket.parked()
    assert socket.wrote == [(b"later", "writer")]
    socket.transport.send(b"now")
    assert socket.wrote[-1] == (b"now", "inline")
    socket.transport.abort()


async def test_a_write_that_suspends_is_finished_by_the_writer_before_later_frames():
    """The socket pauses under the very write that fills it: the write's
    drain is handed to the writer task, and what is sent meanwhile waits,
    a reply sent from inside that write included."""
    socket = await Socket().parked()
    done: list = []

    async def send(data: bytes) -> None:
        by_writer = asyncio.current_task() is socket.transport._writer_task
        socket.wrote.append((data, "writer" if by_writer else "inline"))
        if data == b"fills":
            socket.transport.send(b"reply")
            await socket._resumed.wait()
        done.append(data)

    socket.transport._send_async = send
    socket.transport.send(b"fills")
    assert socket.wrote == [(b"fills", "inline")] and done == []
    socket.transport.send(b"after")
    await asyncio.sleep(0.01)
    assert socket.frames == [b"fills"], "a later frame passed a write that had not finished"
    socket._resumed.set()
    await socket.parked()
    assert done == [b"fills", b"reply", b"after"]
    assert socket.wrote == [(b"fills", "inline"), (b"reply", "writer"), (b"after", "writer")]
    socket.transport.abort()


@pytest.mark.parametrize("seed", [1, 20261004])
async def test_order_holds_over_1000_frames_and_a_socket_that_pauses(seed):
    rng = random.Random(seed)
    socket = await Socket(max_queue=0).parked()
    inline, queued = _written()
    sent = [b"%d" % n for n in range(1000)]
    for data in sent:
        if not socket.paused and rng.random() < 0.03:
            socket.pause()
            asyncio.get_running_loop().call_later(rng.uniform(0, 0.003), socket.resume)
        socket.transport.send(data)
        if rng.random() < 0.3:  # bursts of a few frames in one turn, then a turn or two
            await asyncio.sleep(0 if rng.random() < 0.7 else 0.001)
    for _ in range(2000):
        if len(socket.wrote) == len(sent) and not socket.paused:
            break
        await asyncio.sleep(0.001)
    await socket.parked()
    assert socket.frames == sent
    by = [path for _data, path in socket.wrote]
    assert by.count("inline") > 100 and by.count("writer") > 100, "one path was hardly taken"
    transitions = sum(1 for a, b in zip(by, by[1:]) if a != b)
    assert transitions > 20
    assert _written() == (inline + by.count("inline"), queued + by.count("writer"))
    socket.transport.abort()


async def test_the_close_marker_follows_the_frames_queued_before_it():
    socket = await Socket().parked()
    socket.transport.send(b"inline")
    socket.pause()
    socket.transport.send(b"queued-1")
    socket.transport.send(b"queued-2")
    socket.transport.close(4000, "bye")
    socket.transport.send(b"after the close")  # a closed transport takes nothing
    socket.resume()
    await asyncio.sleep(0.01)
    assert socket.frames == [b"inline", b"queued-1", b"queued-2"]
    assert socket.closed_with == 4000 and socket.transport._writer_task.done()


async def test_overflow_still_closes_with_1013():
    wire = get_wire_telemetry()
    before = sum(wire.send_queue_overflows._values.values())
    socket = await Socket(max_queue=8).parked()
    socket.transport.send(b"inline")
    socket.pause()
    for _ in range(20):
        socket.transport.send(b"x")
    assert socket.transport.is_closed
    assert sum(wire.send_queue_overflows._values.values()) == before + 1
    socket.resume()
    await asyncio.sleep(0.01)
    assert socket.closed_with == 1013
    assert socket.frames == [b"inline"] + [b"x"] * 8


async def test_a_drain_listener_fires_after_an_inline_write_that_leaves_it_drained():
    socket = await Socket().parked()
    fired: list = []
    socket.transport.add_drain_listener(lambda: fired.append(len(socket.wrote)))
    socket.transport.send(b"a")
    assert fired == [1], "an inline write into an empty queue is a drained moment"
    socket.transport.send(b"b")
    assert fired == [1], "one-shot"
    socket.transport.abort()


async def test_a_raising_write_closes_the_transport_and_spares_the_tick():
    """The audience's second socket is gone: its write raises inside the
    tick, the transport closes, and the tick delivers to the others."""
    document = Document("raising")
    sockets = [await Socket().parked() for _ in range(3)]

    async def gone(data: bytes) -> None:
        raise ConnectionResetError("Cannot write to closing transport")

    sockets[1].transport._send_async = gone
    fired: list = []
    sockets[1].transport.add_drain_listener(lambda: fired.append(1))
    for n, socket in enumerate(sockets):
        Connection(socket.transport, None, document, f"sock-{n}", {})
    document.get_text("t").insert(0, "still delivered")
    await asyncio.sleep(0)  # the tick
    assert sockets[1].transport.is_closed and sockets[1].transport._writer_task.cancelling()
    assert fired == [], "listeners are dropped, not fired, when a transport dies"
    for socket in (sockets[0], sockets[2]):
        assert [by for _data, by in socket.wrote] == ["inline"]
        assert not socket.transport.is_closed
        socket.transport.abort()


async def test_a_send_from_inside_a_send_keeps_the_order():
    """A binding whose write calls back into `send` (a hook that replies):
    the reply leaves after the frame whose write it interrupted."""
    socket = await Socket().parked()
    arrived: list = []

    async def send(data: bytes) -> None:
        if data == b"ask":
            socket.transport.send(b"reply")
        arrived.append(data)

    socket.transport._send_async = send
    socket.transport.send(b"ask")
    assert arrived == [b"ask"] and socket.transport.queue.qsize() == 1
    socket.transport.send(b"next")  # behind the reply, not past it
    await socket.parked()
    assert arrived == [b"ask", b"reply", b"next"]
    socket.transport.abort()


async def test_a_transport_that_declares_nothing_never_writes_inline():
    wrote: list = []

    async def send_async(data: bytes) -> None:
        wrote.append((data, asyncio.current_task()))

    async def close_async(code: int, reason: str) -> None:
        pass

    transport = CallbackWebSocketTransport(send_async, close_async)
    inline, queued = _written()
    for n in range(5):
        await asyncio.sleep(0.002)  # idle every time: queue empty, writer parked
        transport.send(b"%d" % n)
        assert transport.queue.qsize() == 1
    await asyncio.sleep(0.002)
    assert [task for _data, task in wrote] == [transport._writer_task] * 5
    assert _written() == (inline, queued + 5)
    transport.abort()


def _as_future(wrote: list, data: bytes) -> "asyncio.Future":
    future = asyncio.get_running_loop().create_future()
    wrote.append(data)
    future.set_result(None)
    return future


@types.coroutine
def _as_generator(wrote: list, data: bytes):
    wrote.append(data)
    yield from asyncio.sleep(0).__await__()


@pytest.mark.parametrize("awaitable_of", [_as_future, _as_generator], ids=["future", "generator"])
@pytest.mark.parametrize("declared", [False, True], ids=["undeclared", "declared"])
async def test_send_async_may_return_anything_await_accepts(awaitable_of, declared):
    """A future, a generator-based coroutine: what the parent's plain
    `await` took, both paths still take, and the transport stays open."""
    wrote: list = []

    async def close_async(code: int, reason: str) -> None:
        pass

    transport = CallbackWebSocketTransport(
        lambda data: awaitable_of(wrote, data),
        close_async,
        writable=(lambda: True) if declared else None,
    )
    await asyncio.sleep(0.002)
    inline, queued = _written()
    transport.send(b"one")
    transport.send(b"two")
    await asyncio.sleep(0.01)
    transport.send(b"three")
    await asyncio.sleep(0.01)
    assert wrote == [b"one", b"two", b"three"] and not transport.is_closed
    after_inline, after_queued = _written()
    assert after_inline + after_queued == inline + queued + 3
    assert (after_inline > inline) == declared
    transport.abort()


async def test_an_undeclared_transport_has_no_write_span_and_keeps_its_batch_call():
    """Without `writable` the writer awaits `send_batch_async` as it always
    did: one call a burst, and no span (a span may not wrap an await)."""
    batches: list = []

    async def send_async(data: bytes) -> None:
        raise AssertionError("the batch callable was declared")

    async def send_batch_async(frames: list) -> None:
        batches.append(list(frames))
        await asyncio.sleep(0)

    async def close_async(code: int, reason: str) -> None:
        pass

    tracer = get_tracer()
    was, tracer.enabled = tracer.enabled, True
    try:
        transport = CallbackWebSocketTransport(send_async, close_async, send_batch_async=send_batch_async)
        await asyncio.sleep(0.002)
        tracer.clear()
        for n in range(4):
            transport.send(b"%d" % n)
        await asyncio.sleep(0.01)
        assert batches == [[b"0", b"1", b"2", b"3"]]
        assert [span["name"] for span in tracer.export() if span["name"].startswith("transport.")] == []
        transport.abort()
    finally:
        tracer.enabled = was
        tracer.clear()  # the ring is the process's: leave it as found for the next test


async def test_each_path_has_its_span_around_the_write_alone():
    tracer = get_tracer()
    was, tracer.enabled = tracer.enabled, True
    try:
        socket = await Socket().parked()
        tracer.clear()
        socket.transport.send(b"inline")
        socket.pause()
        socket.transport.send(b"queued")
        await asyncio.sleep(0.02)
        # the writer still sits in the drain, and its span has closed: a
        # span is recorded when it ends, so none of them wraps the wait
        assert socket.paused and socket.frames == [b"inline", b"queued"]
        names = [span["name"] for span in tracer.export()]
        assert names == ["transport.write_inline", "transport.write_queued"]
        socket.resume()
        await socket.parked()
        socket.transport.abort()
    finally:
        tracer.enabled = was
        tracer.clear()  # the ring is the process's: leave it as found for the next test


async def test_a_durable_commits_tick_is_on_the_socket_when_commit_done_returns(tmp_path):
    """The served path: no turn of the loop between the commit's landing
    (`WalManager._commit_done`) and the audience's socket writes."""
    from hocuspocus_tpu.storage import WalManager
    from tests.utils import HoldingFaults, TurnCounter

    faults = HoldingFaults()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    document = Document("durable")
    document.wal_sink = lambda update, origin: wal.append("durable", update)
    sockets = [await Socket().parked() for _ in range(3)]
    for n, socket in enumerate(sockets):
        Connection(socket.transport, None, document, f"sock-{n}", {})
    turns = TurnCounter()
    real_done = wal._commit_done
    landed: list = []

    def commit_done(gate, since):
        before = turns.turn
        real_done(gate, since)
        landed.append((before, turns.turn, [list(socket.wrote) for socket in sockets]))

    wal._commit_done = commit_done
    try:
        document.get_text("t").insert(0, "durable")
        await faults.held()
        assert all(socket.wrote == [] for socket in sockets), "a frame outran its commit"
        faults.release.set()
        await asyncio.wait_for(wal.flush(), timeout=5)
    finally:
        turns.stop()
    before, after, wrote = landed[0]
    assert before == after, "the commit-done step spanned a turn of the loop"
    for frames in wrote:
        assert [by for _data, by in frames] == ["inline"], "the frame still waited for the writer task"
    assert wal.stats["ticks_released"] == 1
    for socket in sockets:
        socket.transport.abort()


async def test_the_aiohttp_binding_writes_through_and_queues_when_paused():
    """A real server and socket: frames to an idle peer are written by
    `send()`, and while asyncio has the protocol paused they take the queue."""
    import aiohttp

    from hocuspocus_tpu import Server
    from hocuspocus_tpu.server.server import AiohttpWebSocketTransport

    server = Server(quiet=True)
    await server.listen(port=0)
    try:
        async with aiohttp.ClientSession() as session:
            async with session.ws_connect(f"ws://127.0.0.1:{server.port}") as peer:
                for _ in range(200):
                    if server._transports:
                        break
                    await asyncio.sleep(0.005)
                (transport,) = server._transports
                assert isinstance(transport, AiohttpWebSocketTransport)
                await asyncio.sleep(0.01)
                inline, queued = _written()
                transport.send(b"one")
                transport.send(b"two")
                assert _written() == (inline + 2, queued) and transport.queue.empty()
                protocol = transport.ws._writer.protocol
                protocol.pause_writing()
                transport.send(b"three")
                assert transport.queue.qsize() == 1
                protocol.resume_writing()
                got = [(await peer.receive_bytes(timeout=5)) for _ in range(3)]
                assert got == [b"one", b"two", b"three"]
                assert _written() == (inline + 2, queued + 1)
    finally:
        await server.destroy()


async def test_the_aiohttp_binding_queues_what_the_sockets_own_reader_replies(tmp_path):
    """A reply sent from inside the dispatch of a frame (the SyncStep2 of
    the handshake, an update's ack) takes the queue; the same update's
    broadcast, sent by a tick, is written through to an idle socket."""
    from hocuspocus_tpu import Server
    from hocuspocus_tpu.provider import HocuspocusProvider
    from tests.utils import wait_for

    server = Server(quiet=True)
    await server.listen(port=0)
    url = f"ws://127.0.0.1:{server.port}"
    writer = HocuspocusProvider(name="replies", url=url)
    reader = HocuspocusProvider(name="replies", url=url)
    try:
        await wait_for(lambda: writer.synced and reader.synced)
        for transport in server._transports:
            assert transport._reader_task is not None and not transport._reader_task.done()
        inline, queued = _written()
        assert queued > 0, "the handshake's replies were written from inside their dispatch"
        text = writer.document.get_text("body")
        text.insert(0, "x")
        await wait_for(lambda: reader.document.get_text("body").to_string() == "x")
        await asyncio.sleep(0.05)
        after_inline, after_queued = _written()
        # no log here, so the tick runs in the turn of the dispatch: the
        # writer's own copy of the frame finds the ack still queued ahead of it
        assert after_queued - queued == 2, "the ack, from inside the update's dispatch, and the writer's copy behind it"
        assert after_inline - inline == 1, "the tick's frame to the peer's idle socket"
    finally:
        writer.destroy()
        reader.destroy()
        await asyncio.sleep(0.05)
        await server.destroy()
