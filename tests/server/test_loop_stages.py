"""The server loop's stages (bench/lib/loop_stages.py) as the program opens
them: on a served session with the log, the plane and the aiohttp host, no two
stages overlap on the loop thread, and the spans the older readers sum open
inside the stage that holds them. Also the reader's side of the live rule (the
plain await when nothing is looking) and the two wrappers' edges: a transport
without a read callback to wrap, a jax still being imported."""

from __future__ import annotations

import asyncio
import os
import sys
import threading
import types

import pytest

from hocuspocus_tpu.observability import get_tracer, tracing
from hocuspocus_tpu.observability.tracing import Tracer
from hocuspocus_tpu.observability.wire import get_wire_telemetry
from hocuspocus_tpu.server import server as server_module
from hocuspocus_tpu.storage import Durability
from hocuspocus_tpu.tpu import TpuMergeExtension
from tests.utils import new_hocuspocus, new_provider, retryable_assertion, wait_synced

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench", "lib"))

from loop_stages import LOOP_STAGES, NEW_STAGES  # noqa: E402


def inside(child, parents) -> bool:
    return any(p.start <= child.start and child.end <= p.end for p in parents)


async def serve_a_session(tmp_path) -> "tuple[list, dict]":
    """Two providers on one served document with the log: the sync handshake
    (a SyncStep1 each way), an awareness frame, and updates whose deliveries
    wait for their group commit. Returns the loop thread's spans of the ring
    and the log's counters."""
    durability = Durability(wal_dir=str(tmp_path / "wal"))
    plane_ext = TpuMergeExtension(num_docs=8, capacity=256, flush_interval_ms=1, serve=True)
    server = await new_hocuspocus(extensions=[durability, plane_ext])
    writer = new_provider(server, name="stages")
    other = new_provider(server, name="stages")
    try:
        await wait_synced(writer, other)
        writer.set_awareness_field("user", {"name": "writer"})
        body = writer.document.get_text("body")
        for text in ("typed", " and", " more"):
            body.insert(len(body.to_string()), text)

            def delivered(expected=body.to_string()) -> None:
                assert other.document.get_text("body").to_string() == expected
                assert plane_ext.plane.pending_ops() == 0 and not plane_ext._flush_inflight

            await retryable_assertion(delivered)
        await retryable_assertion(lambda: other.awareness.get_states().get(writer.document.client_id) is not None)
        await asyncio.sleep(0.05)
        loop_thread = threading.get_ident()
        spans = [sp for sp in list(get_tracer()._spans) if sp.tid == loop_thread and sp.end > sp.start]
        return spans, dict(durability.wal.stats)
    finally:
        writer.destroy()
        other.destroy()
        await server.destroy()


@pytest.fixture
def ring(monkeypatch):
    """The tracer enabled with the ring holding only `span` sites: the
    sections that await (`add_span`) are not synchronous and may overlap."""
    tracer = get_tracer()
    monkeypatch.setattr(Tracer, "add_span", lambda self, *args, **kwargs: None)
    was, tracer.enabled = tracer.enabled, True
    tracer.clear()
    try:
        yield tracer
    finally:
        tracer.enabled = was
        tracer.clear()


async def test_the_loop_stages_partition_a_served_session(ring, tmp_path):
    wire = get_wire_telemetry()
    frames_before = wire.frames_read
    spans, wal = await serve_a_session(tmp_path)
    # every frame a reader took in is counted, spanned or not
    assert wire.frames_read - frames_before >= len([sp for sp in spans if sp.name == "connection.dispatch"]) > 0
    assert len(ring) < ring._spans.maxlen  # the ring did not wrap: every span is here
    assert wal["ticks_released"] > 0  # a delivery waited for its commit
    names = {sp.name for sp in spans}
    assert set(NEW_STAGES) <= names, sorted(names)
    stages = sorted((sp for sp in spans if sp.name in LOOP_STAGES), key=lambda sp: sp.start)
    for before, after in zip(stages, stages[1:]):
        assert before.end <= after.start, (before.name, after.name)
    by_name = {name: [sp for sp in spans if sp.name == name] for name in names}
    receives = by_name["connection.receive"]
    for name in ("connection.dispatch", "message.update_apply"):
        assert by_name[name] and all(inside(sp, receives) for sp in by_name[name]), name
    assert all(inside(sp, by_name["plane.flush_turn"]) for sp in by_name["plane.post_flush"])
    assert by_name["plane.post_flush"]
    assert not any(inside(sp, by_name["wal.commit_done"]) for sp in by_name["fanout.tick"])
    # whatever else the loop opened lies inside a stage: the stages are the
    # whole of the loop's top level
    top_level = {sp.name for sp in spans if sp.name not in LOOP_STAGES and not inside(sp, stages)}
    assert top_level == set(), top_level


async def test_nothing_looking_awaits_the_frame_plainly(tmp_path, monkeypatch):
    """Tracer disabled, no capture: the reader awaits `handle_message` as it
    always did and no `Span` is made; enabled, the same traffic goes through
    `in_pieces`."""
    tracer = get_tracer()
    assert not tracer.enabled
    pieces, made = [], []
    real_in_pieces = Tracer.in_pieces

    def counting_in_pieces(self, name, awaitable):
        pieces.append(name)
        return real_in_pieces(self, name, awaitable)

    class CountingSpan(tracing.Span):
        __slots__ = ()

        def __init__(self, *args, **kwargs) -> None:
            made.append(args[0])
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(Tracer, "in_pieces", counting_in_pieces)
    monkeypatch.setattr(tracing, "Span", CountingSpan)
    monkeypatch.setattr(tracing, "_annotation", False)  # no capture can be running
    await serve_a_session(tmp_path / "off")
    assert pieces == [] and made == []
    tracer.enabled = True
    try:
        await serve_a_session(tmp_path / "on")
    finally:
        tracer.enabled = False
        tracer.clear()
    assert "connection.receive" in pieces and "plane.flush_turn" in pieces
    assert "transport.read" in made


def test_the_read_callback_is_there_to_wrap_on_this_python():
    """asyncio's selector transport reads through `_read_ready_cb`, a private
    attribute: where it is missing the connection reads with no span."""

    class Bare:
        pass

    bare = Bare()
    server_module._span_reads(bare)
    assert not hasattr(bare, "_read_ready_cb")

    async def connected() -> None:
        accepted = []
        server = await asyncio.start_server(lambda _r, w: accepted.append(w), "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        _reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            while not accepted:
                await asyncio.sleep(0.01)
            transport = writer.transport
            read = transport._read_ready_cb
            assert callable(read)
            server_module._span_reads(transport)
            assert transport._read_ready_cb is not read
        finally:
            for each in [writer, *accepted]:
                each.close()
            server.close()
            await server.wait_closed()

    asyncio.run(connected())


def test_a_jax_still_being_imported_is_not_imported_again(monkeypatch):
    """A span site on the loop while a plane's init thread imports jax must
    not import `jax.profiler` itself: the two imports race, and the init
    thread can be left with a half-initialised module (the plane then never
    attaches)."""
    half = types.ModuleType("jax")
    half.__spec__ = types.SimpleNamespace(_initializing=True)
    monkeypatch.setitem(sys.modules, "jax", half)
    monkeypatch.delitem(sys.modules, "jax.profiler", raising=False)
    monkeypatch.setattr(tracing, "_annotation", None)
    tracer = Tracer(enabled=False)
    assert tracer.span("anything") is tracing._NOOP_SPAN
    assert tracer.live() is False
    assert tracing._annotation is None and "jax.profiler" not in sys.modules
