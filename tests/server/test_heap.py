"""The heap steward (server/heap.py): the serving process decides when the
collector runs and what it walks. CPU only; every case runs under the
`heap_steward` fixture, which puts the process's collector back as found."""

from __future__ import annotations

import asyncio
import gc
import weakref

import pytest

from hocuspocus_tpu.cli import build_parser, build_server
from hocuspocus_tpu.server import Server
from hocuspocus_tpu.server.heap import NEVER, HeapStewardExtension
from tests.utils import new_hocuspocus, new_provider, wait_for, wait_synced


def frozen(*things) -> bool:
    """In the permanent generation: tracked, and `gc.get_objects()` (which
    leaves the frozen out) lists none of them."""
    listed = {id(other) for other in gc.get_objects()}
    return all(gc.is_tracked(thing) and id(thing) not in listed for thing in things)


async def stewarded(**options) -> Server:
    return await new_hocuspocus(extensions=[HeapStewardExtension()], **options)


def eager(steward) -> None:
    """Every tick of the extension's timer may run a pass."""
    steward.min_interval_s = 0.0
    steward.load_settle_s = 0.05


@pytest.mark.parametrize("order", ["one-server", "first-in-first-out", "last-in-first-out"])
async def test_install_and_destroy_leave_the_collector_as_found(heap_steward, order):
    before = (gc.get_threshold(), gc.get_freeze_count())
    servers = [await stewarded()]
    try:
        assert gc.get_threshold() == (heap_steward.gen0_threshold, before[0][1], NEVER)
        assert gc.get_freeze_count() > before[1] and heap_steward.last_pass["reason"] == "boot"
        if order != "one-server":
            servers.append(await stewarded())
            assert heap_steward.stats["heap_passes"] and heap_steward._installs == 2
            leaving = servers.pop(0 if order == "first-in-first-out" else 1)
            await leaving.destroy()
            # one server is still listening: the steward stays
            assert heap_steward.installed and gc.get_threshold()[2] == NEVER and gc.get_freeze_count() > 0
    finally:
        for server in servers:
            await server.destroy()
    assert not heap_steward.installed
    assert (gc.get_threshold(), gc.get_freeze_count()) == before


async def test_a_destroy_without_a_listen_touches_nothing(heap_steward):
    before = gc.get_threshold()
    listening = await stewarded()
    try:
        never_listened = Server(extensions=[HeapStewardExtension()], quiet=True)
        await never_listened.destroy()
        assert heap_steward.installed and gc.get_threshold() != before
    finally:
        await listening.destroy()
    assert gc.get_threshold() == before


async def test_a_pass_runs_after_a_load_burst_settles_and_freezes_the_documents(heap_steward):
    eager(heap_steward)
    server = await stewarded()
    try:
        passes = heap_steward.stats["heap_passes"]
        names = [f"loaded-{n}" for n in range(12)]
        direct = await asyncio.gather(*(server.open_direct_connection(name) for name in names))
        for connection in direct:
            await connection.transact(lambda document: document.get_text("body").insert(0, "frozen text"))
        assert heap_steward._loads_pending
        await wait_for(lambda: heap_steward.stats["heap_passes"] > passes and not heap_steward._loads_pending)
        assert heap_steward.last_pass["reason"] in ("loads", "growth")
        documents = [server.documents[name] for name in names]
        assert frozen(*documents, *(document.get_text("body") for document in documents))
        assert heap_steward.stats["heap_frozen_blocks"] == heap_steward.last_pass["blocks"] > 0
        assert heap_steward.stats["heap_unfreezes"] == 0
    finally:
        await server.destroy()


class Knot:
    """A reference cycle."""

    def __init__(self) -> None:
        self.me = self


@pytest.mark.parametrize("reason", ["growth", "timer"])
async def test_a_cycle_made_after_a_freeze_is_collected_by_the_next_chosen_pass(heap_steward, reason):
    eager(heap_steward)
    if reason == "growth":
        heap_steward.growth_share = 0.0  # any tick finds the heap grown enough
    else:
        heap_steward.growth_share = 1000.0  # never; but the heap has grown at all, and that long ago
        heap_steward.max_interval_s = 0.0
    server = await stewarded()
    try:
        knot = Knot()
        tied = weakref.ref(knot)
        ballast = [[] for _ in range(1000)]  # the heap is larger than at the boot pass
        del knot
        assert tied() is not None  # reference counting cannot free it
        passes = heap_steward.stats["heap_passes"]
        await wait_for(lambda: heap_steward.stats["heap_passes"] > passes)
        assert tied() is None
        assert heap_steward.last_pass["reason"] == reason and heap_steward.last_pass["collected"] >= 1
        assert heap_steward.stats["heap_unfreezes"] == 0 and len(ballast) == 1000
    finally:
        await server.destroy()


@pytest.mark.parametrize("departing", ["document", "connection"])
async def test_what_was_frozen_and_left_is_freed_by_the_unfreeze_pass(heap_steward, departing):
    heap_steward.churn_floor = 1
    heap_steward.churn_share = 0.0
    server = await stewarded(debounce=20, max_debounce=50)
    name = "stays" if departing == "connection" else "unloaded"
    keeper = new_provider(server, name="stays")
    leaver = None
    try:
        await wait_synced(keeper)
        stayed = set(server.documents["stays"].get_connections())
        leaver = new_provider(server, name=name)
        await wait_synced(leaver)
        leaver.document.get_text("body").insert(0, "left and right, item to item")
        await wait_for(lambda: server.documents[name].get_text("body").to_string() != "")
        left = server.documents[name]
        if departing == "connection":
            (left,) = set(left.get_connections()) - stayed
        heap_steward.run_pass("test")
        assert frozen(left) and not heap_steward.last_pass["unfreeze"]
        gone = weakref.ref(left)
        del left
        unfreezes = heap_steward.stats["heap_unfreezes"]
        leaver.destroy()
        # the hook counts a departure when the close begins; the pass must
        # not find the connection still held by the tasks that finish it, or
        # by the payload of its document's debounced store
        if departing == "document":
            await wait_for(lambda: "unloaded" not in server.documents)
        else:
            await wait_for(lambda: set(server.documents["stays"].get_connections()) == stayed)
        debouncer = server.hocuspocus.debouncer
        await wait_for(lambda: not (debouncer.is_debounced(f"onStoreDocument-{name}") or debouncer.in_flight(f"onStoreDocument-{name}")))
        await asyncio.sleep(0.2)
        assert heap_steward.thaw_due() and heap_steward.stats["heap_unfreezes"] == unfreezes
        eager(heap_steward)
        await wait_for(lambda: heap_steward.stats["heap_unfreezes"] > unfreezes and gone() is None)
        assert heap_steward.last_pass["unfreeze"] and heap_steward.last_pass["collected"] > 0
        assert "stays" in server.documents
    finally:
        keeper.destroy()
        if leaver is not None:
            leaver.destroy()
        await server.destroy()


def full_passes_during(burst) -> int:
    seen = []

    def on_gc(phase, info):
        if phase == "stop" and info["generation"] == 2:
            seen.append(info)

    gc.callbacks.append(on_gc)
    try:
        burst()
    finally:
        gc.callbacks.remove(on_gc)
    return len(seen)


@pytest.mark.parametrize("installed", [False, True])
async def test_no_automatic_full_pass_under_a_burst_that_trips_generation_2_today(heap_steward, installed):
    gc.set_threshold(700, 10, 10)  # CPython's own, whatever the worker was left with
    server = await (stewarded() if installed else new_hocuspocus())
    try:
        gc.collect()  # what survives the burst is now more than a quarter of the old generation
        survivors = len(gc.get_objects()) // 2 + 200_000
        kept = []
        automatic = heap_steward.stats["gc_auto_full_passes"]
        seen = full_passes_during(lambda: kept.extend([] for _ in range(survivors)))
        if installed:
            assert seen == 0 and heap_steward.stats["gc_auto_full_passes"] == automatic
        else:
            assert seen >= 1
    finally:
        await server.destroy()


async def test_an_automatic_full_pass_would_be_counted(heap_steward):
    server = await stewarded()
    try:
        automatic = heap_steward.stats["gc_auto_full_passes"]
        gc.collect()  # not the steward's: somebody else's full pass
        assert heap_steward.stats["gc_auto_full_passes"] == automatic + 1
        heap_steward.run_pass("test")
        assert heap_steward.stats["gc_auto_full_passes"] == automatic + 1
    finally:
        await server.destroy()


@pytest.mark.parametrize("entry", ["cli.build_server", "bare Server()"])
def test_the_cli_process_gets_the_steward_and_an_embedded_server_does_not(entry):
    if entry == "cli.build_server":
        server = build_server(build_parser().parse_args(["--port", "0"]))
    else:
        server = Server(quiet=True)
    carried = [e for e in server.configuration.extensions if isinstance(e, HeapStewardExtension)]
    assert len(carried) == (1 if entry == "cli.build_server" else 0)
