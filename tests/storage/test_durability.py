"""Durability plane integration: recovery, store retry/quarantine,
graceful drain, broadcast gating.

These run against real servers + real websocket providers (the repo's
standard harness), with the fault seams from `storage/faults.py`
driving the failure paths deterministically.
"""

import asyncio
import os

from tests.utils import (
    HoldingFaults,
    new_hocuspocus,
    new_provider,
    retryable_assertion,
    wait_for,
    wait_synced,
)

from hocuspocus_tpu.extensions import Database, IncrementalSQLite, SQLite
from hocuspocus_tpu.storage import Durability, FaultInjector, FlakyStore


def _assert(cond, message=""):
    assert cond, message


# -- crash recovery (in-process) ---------------------------------------------


async def test_wal_replays_over_stored_snapshot(tmp_path):
    """Snapshot + log-suffix: the store holds an OLD snapshot, the WAL
    holds the edits since; a restart reconstructs the union."""
    wal_dir = str(tmp_path / "wal")
    db = str(tmp_path / "docs.db")
    server = await new_hocuspocus(
        extensions=[Durability(wal_dir=wal_dir), SQLite(database=db)],
        debounce=50,
    )
    provider = new_provider(server, name="recover-me")
    await wait_synced(provider)
    text = provider.document.get_text("t")
    text.insert(0, "stored-part")
    # wait for the debounced store (WAL truncates when it lands)
    durability = server.configuration.extensions[0]
    await retryable_assertion(
        lambda: _assert(durability.wal.pending_records("recover-me") == 0)
    )
    # now edits that will NEVER be stored (debounce re-armed, crash next)
    text.insert(len(str(text)), " +wal-part")
    await wait_for(lambda: provider.unsynced_changes == 0)
    await retryable_assertion(
        lambda: _assert(durability.wal.pending_records("recover-me") >= 1)
    )
    # "crash": no destroy, no store — boot a fresh server on the same dirs
    server2 = await new_hocuspocus(
        extensions=[Durability(wal_dir=wal_dir), SQLite(database=db)],
        debounce=60000,
    )
    provider2 = new_provider(server2, name="recover-me")
    try:
        await wait_synced(provider2)
        await retryable_assertion(
            lambda: _assert(
                provider2.document.get_text("t").to_string()
                == "stored-part +wal-part"
            )
        )
        durability2 = server2.configuration.extensions[0]
        report = durability2.last_recovery["recover-me"]
        assert report["applied"] >= 1
        assert report["torn_tail_records"] == 0
    finally:
        provider2.destroy()
        provider.destroy()
        await server2.destroy()
        await server.destroy()


async def test_recovery_skips_torn_tail_and_counts_it(tmp_path):
    """A torn final record (the kill -9 signature) is skipped and
    counted; every intact record still applies."""
    from hocuspocus_tpu.crdt import Doc, encode_state_as_update

    wal_dir = str(tmp_path / "wal")
    seed = Doc()
    seed.get_text("t").insert(0, "intact")
    from hocuspocus_tpu.storage import WalManager

    wal = WalManager(wal_dir, fsync="tick")
    await wal.append("torn-doc", encode_state_as_update(seed))
    path = wal.doc("torn-doc").segments[-1].path
    wal.close()
    with open(path, "ab") as fh:
        fh.write(b"\x99" * 11)  # partial frame: a write cut by SIGKILL
    server = await new_hocuspocus(
        extensions=[Durability(wal_dir=wal_dir)], debounce=60000
    )
    provider = new_provider(server, name="torn-doc")
    try:
        await wait_synced(provider)
        assert provider.document.get_text("t").to_string() == "intact"
        durability = server.configuration.extensions[0]
        assert durability.last_recovery["torn-doc"]["torn_tail_records"] == 1
        assert durability.wal.stats["torn_tail_records"] == 1
    finally:
        provider.destroy()
        await server.destroy()


# -- store retry / quarantine state machine ----------------------------------


async def test_store_retries_then_succeeds(tmp_path):
    flaky = FlakyStore(failures=2)
    server = await new_hocuspocus(
        extensions=[Database(store=flaky)],
        debounce=20,
        store_retries=3,
        store_retry_base_ms=10,
        store_retry_max_ms=40,
    )
    provider = new_provider(server, name="flaky-doc")
    try:
        await wait_synced(provider)
        provider.document.get_text("t").insert(0, "x")
        await retryable_assertion(lambda: _assert(flaky.successes == 1))
        assert flaky.calls == 3  # two failures + the success
        assert "flaky-doc" not in server.hocuspocus.quarantine
    finally:
        provider.destroy()
        await server.destroy()


async def test_store_exhaustion_quarantines_not_drops(tmp_path):
    """Retries exhausted: the doc is quarantined — kept loaded, health
    degraded — and the sweep re-stores it once the backend heals."""
    flaky = FlakyStore(failures=4)
    server = await new_hocuspocus(
        extensions=[Database(store=flaky)],
        debounce=20,
        store_retries=1,  # 2 attempts per chain: first chain exhausts
        store_retry_base_ms=10,
        store_retry_max_ms=20,
        store_quarantine_sweep_ms=100,
    )
    provider = new_provider(server, name="doomed-doc")
    try:
        await wait_synced(provider)
        provider.document.get_text("t").insert(0, "precious")
        await retryable_assertion(
            lambda: _assert("doomed-doc" in server.hocuspocus.quarantine)
        )
        health = server.hocuspocus.get_health()
        assert health["status"] == "degraded"
        assert health["quarantined_documents"] == ["doomed-doc"]
        # the doc is KEPT LOADED even with zero connections
        provider.destroy()
        await asyncio.sleep(0.15)
        assert "doomed-doc" in server.hocuspocus.documents
        # backend heals (failures=4: attempts 1-4 fail) -> sweep stores
        await retryable_assertion(lambda: _assert(flaky.successes >= 1))
        await retryable_assertion(
            lambda: _assert("doomed-doc" not in server.hocuspocus.quarantine)
        )
        assert server.hocuspocus.get_health()["status"] == "ok"
    finally:
        provider.destroy()
        await server.destroy()


async def test_quarantined_doc_keeps_wal(tmp_path):
    """Quarantine + WAL: even while the store backend is down, every
    update stays recoverable from the log."""
    flaky = FlakyStore(failures=10**6)
    wal_dir = str(tmp_path / "wal")
    server = await new_hocuspocus(
        extensions=[Durability(wal_dir=wal_dir), Database(store=flaky)],
        debounce=20,
        store_retries=0,
        store_quarantine_sweep_ms=60000,
    )
    provider = new_provider(server, name="walled")
    try:
        await wait_synced(provider)
        provider.document.get_text("t").insert(0, "survives")
        await retryable_assertion(
            lambda: _assert("walled" in server.hocuspocus.quarantine)
        )
        durability = server.configuration.extensions[0]
        assert durability.wal.pending_records("walled") >= 1
        records, _report = await durability.wal.replay("walled")
        assert records, "WAL must retain the quarantined doc's updates"
    finally:
        provider.destroy()
        await server.destroy()


# -- graceful drain -----------------------------------------------------------


async def test_drain_stores_dirty_docs_and_closes_1012(tmp_path):
    db = str(tmp_path / "drain.db")
    server = await new_hocuspocus(
        extensions=[SQLite(database=db)], debounce=60000
    )
    provider = new_provider(server, name="drain-doc")
    closes = []
    provider.on("close", lambda payload: closes.append(payload["event"]["code"]))
    try:
        await wait_synced(provider)
        provider.document.get_text("t").insert(0, "dirty at SIGTERM")
        await wait_for(lambda: provider.unsynced_changes == 0)
        outcome = await server.drain(timeout_secs=5)
        assert outcome["stored"] >= 1
        assert not outcome["timed_out"]
        assert outcome["quarantined"] == []
        await retryable_assertion(lambda: _assert(1012 in closes))
        # new connections are refused while draining
        sqlite = server.configuration.extensions[0]
        row = sqlite.db.execute(
            'SELECT data FROM "documents" WHERE name = ?', ("drain-doc",)
        ).fetchone()
        assert row is not None and row[0], "dirty doc must be stored by drain"
    finally:
        provider.destroy()
        await server.destroy()


async def test_drain_deadline_quarantines_slow_store(tmp_path):
    """A store slower than the deadline: drain returns on time, the doc
    is reported quarantined (not lost) and its WAL holds the data."""
    wal_dir = str(tmp_path / "wal")
    slow_release = asyncio.Event()

    async def slow_store(data):
        await slow_release.wait()

    server = await new_hocuspocus(
        extensions=[Durability(wal_dir=wal_dir), Database(store=slow_store)],
        debounce=60000,
        store_retries=0,
    )
    provider = new_provider(server, name="slow-doc")
    try:
        await wait_synced(provider)
        provider.document.get_text("t").insert(0, "slow but safe")
        await wait_for(lambda: provider.unsynced_changes == 0)
        outcome = await server.drain(timeout_secs=0.3)
        assert "slow-doc" in outcome["timed_out"]
        assert "slow-doc" in outcome["quarantined"]
        assert outcome["wal_flushed"] is True
        durability = server.configuration.extensions[0]
        assert durability.wal.pending_records("slow-doc") >= 1
        health = server.hocuspocus.get_health()
        assert health["status"] == "degraded"
    finally:
        slow_release.set()
        provider.destroy()
        await server.destroy()


async def test_drain_refuses_new_connections(tmp_path):
    server = await new_hocuspocus(extensions=[], debounce=60000)
    provider = new_provider(server, name="pre-drain")
    try:
        await wait_synced(provider)
        await server.drain(timeout_secs=2)
        import aiohttp

        async with aiohttp.ClientSession() as session:
            try:
                ws = await session.ws_connect(server.web_socket_url)
            except aiohttp.WSServerHandshakeError as error:
                assert error.status == 503
            else:
                await ws.close()
                raise AssertionError("draining server accepted an upgrade")
    finally:
        provider.destroy()
        await server.destroy()


# -- broadcast gating ---------------------------------------------------------


async def test_broadcast_waits_for_group_commit(tmp_path):
    """No client may see an update whose WAL record is not yet durable:
    with an artificially slow commit, the observer's receipt must come
    after the tick's durability future resolved."""
    wal_dir = str(tmp_path / "wal")
    durability = Durability(wal_dir=wal_dir)
    committed = asyncio.Event()
    real_commit = durability.wal._commit

    def slow_commit(pending):
        import time as _time

        _time.sleep(0.15)  # executor thread: event loop stays live
        real_commit(pending)
        committed.set()

    durability.wal._commit = slow_commit
    server = await new_hocuspocus(extensions=[durability], debounce=60000)
    writer = new_provider(server, name="gated")
    observer = new_provider(server, name="gated")
    received_after_commit = []
    observer.document.on(
        "update",
        lambda *args: received_after_commit.append(committed.is_set()),
    )
    try:
        await wait_synced(writer, observer)
        received_after_commit.clear()  # drop handshake noise
        writer.document.get_text("t").insert(0, "gated-broadcast")
        await retryable_assertion(
            lambda: _assert(
                observer.document.get_text("t").to_string() == "gated-broadcast"
            )
        )
        assert received_after_commit, "observer never received the update"
        assert all(received_after_commit), (
            "a broadcast frame outran its WAL group commit"
        )
    finally:
        writer.destroy()
        observer.destroy()
        await server.destroy()


async def test_a_plane_window_is_delivered_by_the_commit_that_covers_it(tmp_path):
    """A plane-served document: the broadcast pass flushes the ticks it
    queued before it returns, the window waits on the log's gate, and the
    commit's completion delivers it — never before the commit returned."""
    from hocuspocus_tpu.tpu import TpuMergeExtension

    faults = HoldingFaults()
    durability = Durability(wal_dir=str(tmp_path / "wal"), faults=faults)
    ext = TpuMergeExtension(num_docs=8, capacity=1024, flush_interval_ms=1, serve=True)
    server = await new_hocuspocus(extensions=[durability, ext], debounce=60000)
    writer = new_provider(server, name="window")
    observer = new_provider(server, name="window")
    left_queued = []
    broadcast_served = ext._broadcast_served

    def served(*args, **kwargs):
        broadcast_served(*args, **kwargs)
        left_queued.extend(d.name for d in server.documents.values() if d.fanout._pending_updates)

    ext._broadcast_served = served
    try:
        await wait_synced(writer, observer)
        document = server.documents["window"]
        assert "window" in ext._docs
        writer.document.get_text("t").insert(0, "through the plane")
        await faults.held()
        await wait_for(lambda: document.fanout._gated)
        assert observer.document.get_text("t").to_string() == ""
        assert durability.wal.stats["ticks_released"] == 0
        faults.release.set()
        await retryable_assertion(
            lambda: _assert(observer.document.get_text("t").to_string() == "through the plane")
        )
        assert durability.wal.stats["ticks_released"] >= 1
        assert ext.plane.counters["plane_broadcasts"] >= 1
        assert ext.plane.counters["cpu_fallbacks"] == 0
        assert left_queued == [], "a pass returned with a tick it queued still pending"
    finally:
        faults.release.set()
        writer.destroy()
        observer.destroy()
        await server.destroy()


async def test_incremental_store_truncates_wal(tmp_path):
    """The incremental (delta) backend also covers the log: after its
    store lands, the WAL suffix is gone."""
    wal_dir = str(tmp_path / "wal")
    db = str(tmp_path / "incr.db")
    server = await new_hocuspocus(
        extensions=[
            Durability(wal_dir=wal_dir),
            IncrementalSQLite(database=db),
        ],
        debounce=30,
    )
    provider = new_provider(server, name="incr-doc")
    try:
        await wait_synced(provider)
        provider.document.get_text("t").insert(0, "delta")
        durability = server.configuration.extensions[0]
        # the update hits the log first, then the delta store covers it
        await retryable_assertion(
            lambda: _assert(
                durability.wal.stats["appended_records"] >= 1
                and durability.wal.pending_records("incr-doc") == 0
            )
        )
        assert durability.wal.stats["segments_truncated"] >= 1
    finally:
        provider.destroy()
        await server.destroy()
