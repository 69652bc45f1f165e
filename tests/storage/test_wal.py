"""WAL unit differentials: framing, group commit, torn tails, faults.

The recovery contract is bitwise: every committed record replays, a
torn tail is detected (CRC) and skipped — never applied, never fatal —
and a write failure leaves the segment chain in a state where the NEXT
append is still recoverable.
"""

import asyncio
import os
import time

import pytest

from hocuspocus_tpu.storage import (
    REC_SNAPSHOT,
    REC_UPDATE,
    FaultInjector,
    WalManager,
    decode_records,
    encode_record,
)
from tests.utils import HoldingFaults, TurnCounter


def _payloads(records):
    return [payload for _type, payload in records]


# -- framing -----------------------------------------------------------------


def test_record_roundtrip_and_types():
    blob = encode_record(b"hello", REC_UPDATE) + encode_record(b"snap", REC_SNAPSHOT)
    records, valid, bad = decode_records(blob)
    assert records == [(REC_UPDATE, b"hello"), (REC_SNAPSHOT, b"snap")]
    assert valid == len(blob)
    assert bad == 0


def test_decode_stops_at_corrupt_frame():
    good = encode_record(b"first")
    corrupt = bytearray(encode_record(b"second"))
    corrupt[-1] ^= 0xFF  # flip a payload bit: CRC mismatch
    unreachable = encode_record(b"third")
    records, valid, bad = decode_records(bytes(good + corrupt + unreachable))
    # frame boundaries are lost past a bad record: third is unreachable
    assert _payloads(records) == [b"first"]
    assert valid == len(good)
    assert bad == 1


def test_decode_tolerates_short_tail():
    good = encode_record(b"first")
    torn = encode_record(b"torn-away-payload")[:-5]
    records, valid, bad = decode_records(good + torn)
    assert _payloads(records) == [b"first"]
    assert bad == 1
    # a partial header alone is also a torn tail
    records, _valid, bad = decode_records(good + b"\x01\x02\x03")
    assert _payloads(records) == [b"first"]
    assert bad == 1


# -- group commit ------------------------------------------------------------


async def test_group_commit_one_fsync_per_tick(tmp_path):
    wal = WalManager(str(tmp_path), fsync="tick")
    futures = [wal.append("doc", b"u%d" % i) for i in range(8)]
    # all appends in one tick share ONE durability future
    assert all(f is futures[0] for f in futures)
    await futures[0]
    assert wal.stats["appended_records"] == 8
    assert wal.stats["fsyncs"] == 1
    assert wal.stats["commit_batch_records_last"] == 8
    records, report = await wal.replay("doc")
    # segment copies + the journal's redo copies (idempotent on replay)
    assert _payloads(records)[:8] == [b"u%d" % i for i in range(8)]
    assert report["journal_records"] == 8
    assert report["torn_tail_records"] == 0


async def test_one_journal_fsync_covers_many_docs(tmp_path):
    """The amortization that makes tick mode viable: N dirty docs in
    one tick cost ONE fsync (the shared commit journal), not N."""
    wal = WalManager(str(tmp_path), fsync="tick")
    futures = [wal.append(f"doc-{i}", b"payload") for i in range(32)]
    await futures[0]
    assert wal.stats["fsyncs"] == 1
    assert wal.stats["appended_records"] == 32
    # every doc's record is durable via the journal
    fresh = WalManager(str(tmp_path), fsync="tick")
    for i in range(32):
        records, report = await fresh.replay(f"doc-{i}")
        assert b"payload" in _payloads(records)


async def test_journal_rotation_settles_segments(tmp_path):
    """When the journal crosses its size bound, the dirty doc segments
    are batch-fsynced and the journal resets — replay is then exact
    again (no redo copies)."""
    wal = WalManager(str(tmp_path), fsync="tick", journal_max_bytes=256)
    for i in range(12):
        await wal.append("doc", b"payload-%02d" % i)
    assert wal.stats["journal_rotations"] >= 1
    # after a rotation the journal no longer re-covers settled records
    fresh = WalManager(str(tmp_path), fsync="tick")
    records, report = await fresh.replay("doc")
    payloads = _payloads(records)
    assert payloads[:12] == [b"payload-%02d" % i for i in range(12)]
    # only the unrotated tail window may appear twice
    assert len(payloads) < 24


async def test_fsync_always_and_off_modes(tmp_path):
    always = WalManager(str(tmp_path / "a"), fsync="always")
    await asyncio.gather(always.append("d", b"x"), always.append("d", b"y"))
    assert always.stats["fsyncs"] == 2
    off = WalManager(str(tmp_path / "b"), fsync="off")
    await off.append("d", b"x")
    assert off.stats["fsyncs"] == 0
    records, _ = await off.replay("d")
    assert _payloads(records) == [b"x"]
    with pytest.raises(ValueError):
        WalManager(str(tmp_path / "c"), fsync="sometimes")


async def test_appends_during_commit_join_next_batch(tmp_path):
    wal = WalManager(str(tmp_path), fsync="off")
    first = wal.append("doc", b"one")
    await first
    second = wal.append("doc", b"two")
    third = wal.append("doc", b"three")
    assert second is third and second is not first
    await second
    records, _ = await wal.replay("doc")
    assert _payloads(records) == [b"one", b"two", b"three"]
    assert wal.stats["commit_batches"] >= 2


# -- truncation / segments ---------------------------------------------------


async def test_truncate_through_drops_covered_segments(tmp_path):
    # fsync="off": no journal, so replay is segment-exact — this test
    # pins SEGMENT truncation, which is mode-independent
    wal = WalManager(str(tmp_path), fsync="off", segment_max_bytes=20)
    for i in range(6):
        await wal.append("doc", b"payload-%d" % i)  # tiny segments: rotation
    doc = wal.doc("doc")
    segment_count = len(doc.segments)
    assert segment_count >= 3
    position = wal.position("doc")
    assert position == 6
    removed = wal.truncate_through("doc", position - 1)
    assert removed == segment_count
    records, _ = await wal.replay("doc")
    assert records == []
    # appends after full truncation start a fresh chain
    await wal.append("doc", b"after")
    records, _ = await wal.replay("doc")
    assert _payloads(records) == [b"after"]


async def test_partial_coverage_keeps_segment(tmp_path):
    wal = WalManager(str(tmp_path), fsync="off", segment_max_bytes=1 << 20)
    await wal.append("doc", b"covered")
    await wal.append("doc", b"not-covered")
    # store covered only seq 0: the shared segment must survive
    assert wal.truncate_through("doc", 0) == 0
    records, _ = await wal.replay("doc")
    assert _payloads(records) == [b"covered", b"not-covered"]


async def test_checkpoint_subsumes_history(tmp_path):
    # tick mode on purpose: a checkpoint must rotate the journal so the
    # subsume-everything property holds ON DISK, not just in segments
    wal = WalManager(str(tmp_path), fsync="tick", segment_max_bytes=64)
    for i in range(5):
        await wal.append("doc", b"edit-%d" % i)
    await wal.checkpoint("doc", b"SNAPSHOT")
    records, _ = await wal.replay("doc")
    assert records == [(REC_SNAPSHOT, b"SNAPSHOT")]
    assert wal.stats["checkpoints"] == 1
    assert wal.stats["journal_rotations"] >= 1
    # post-checkpoint edits append after the snapshot record (the tail
    # also rides the fresh journal window: one redo copy)
    await wal.append("doc", b"tail")
    records, _ = await wal.replay("doc")
    assert records[:2] == [(REC_SNAPSHOT, b"SNAPSHOT"), (REC_UPDATE, b"tail")]


async def test_doc_names_are_path_safe(tmp_path):
    wal = WalManager(str(tmp_path), fsync="off")
    weird = "reports/../q3 2026?*"
    await wal.append(weird, b"payload")
    records, _ = await wal.replay(weird)
    assert _payloads(records) == [b"payload"]
    # nothing escaped the wal root
    assert not (tmp_path.parent / "q3 2026?*").exists()


# -- fault injection ---------------------------------------------------------


async def test_torn_write_recovery_differential(tmp_path):
    """A torn write (crash mid-record) loses ONLY the torn record; the
    tail is repaired so later appends stay reachable."""
    faults = FaultInjector()
    # `always` mode: no journal redo copies, so the differential is
    # record-exact (the torn-write repair itself is mode-independent)
    wal = WalManager(str(tmp_path), fsync="always", faults=faults)
    await wal.append("doc", b"before")
    faults.tear_next_write(0.4)
    await wal.append("doc", b"torn-record-payload-torn-record")
    assert wal.stats["append_errors"] == 1
    records, report = await wal.replay("doc")
    assert _payloads(records) == [b"before"]
    await wal.append("doc", b"after-heal")
    records, report = await wal.replay("doc")
    assert _payloads(records) == [b"before", b"after-heal"]
    assert report["torn_tail_records"] == 0  # tail was repaired
    assert faults.counters["torn_writes_injected"] == 1


async def test_unrepaired_torn_tail_counted_at_replay(tmp_path):
    """A crash AFTER the write but mid-flush leaves a torn tail on
    disk; a fresh manager (the restarted process) counts + skips it."""
    wal = WalManager(str(tmp_path), fsync="off")
    await wal.append("doc", b"durable")
    await wal.append("doc", b"casualty")
    doc = wal.doc("doc")
    path = doc.segments[-1].path
    wal.close()
    size = os.path.getsize(path)
    with open(path, "r+b") as fh:
        fh.truncate(size - 4)  # kill -9 mid-write: partial final record
    fresh = WalManager(str(tmp_path), fsync="off")
    records, report = await fresh.replay("doc")
    assert _payloads(records) == [b"durable"]
    assert report["torn_tail_records"] == 1
    assert fresh.stats["torn_tail_records"] == 1


async def test_journal_recovers_record_lost_from_torn_segment(tmp_path):
    """Tick mode's double-bookkeeping pays off: a record whose SEGMENT
    copy was torn off by the crash still recovers from the fsynced
    commit journal."""
    wal = WalManager(str(tmp_path), fsync="tick")
    await wal.append("doc", b"durable")
    await wal.append("doc", b"casualty")
    path = wal.doc("doc").segments[-1].path
    wal.close()
    with open(path, "r+b") as fh:
        fh.truncate(os.path.getsize(path) - 4)
    fresh = WalManager(str(tmp_path), fsync="tick")
    records, report = await fresh.replay("doc")
    assert b"casualty" in _payloads(records)
    assert report["torn_tail_records"] == 1
    assert report["journal_records"] == 2


async def test_fsync_failure_counted_not_fatal(tmp_path):
    faults = FaultInjector()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    faults.fail_fsync(1)
    await wal.append("doc", b"maybe-durable")
    assert wal.stats["append_errors"] == 1
    await wal.append("doc", b"durable")
    records, _ = await wal.replay("doc")
    # the written-but-unfsynced record is still readable in THIS world
    # (no actual crash happened); the error is surfaced for alerting
    assert _payloads(records)[:2] == [b"maybe-durable", b"durable"]


async def test_disk_full_then_heal(tmp_path):
    faults = FaultInjector()
    wal = WalManager(str(tmp_path), fsync="always", faults=faults)
    faults.fail_disk_full(2)
    await wal.append("doc", b"lost-to-enospc")
    await wal.append("doc", b"also-lost")
    assert wal.stats["append_errors"] == 2
    await wal.append("doc", b"disk-freed")
    records, _ = await wal.replay("doc")
    assert _payloads(records) == [b"disk-freed"]


async def test_gate_future_resolves_even_on_failure(tmp_path):
    """Broadcast gating must never hang on a dead disk: the tick future
    resolves (and the error is counted) even when every write fails."""
    faults = FaultInjector()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    faults.fail_disk_full(1)
    future = wal.append("doc", b"x")
    await asyncio.wait_for(future, timeout=5)
    assert wal.stats["append_errors"] == 1


async def test_checkpoint_fsync_failure_keeps_history(tmp_path):
    """The crash-ordering invariant behind checkpoints: older segments
    may only be dropped AFTER the snapshot is durable. With the
    journal fsync failing, the per-update history must survive."""
    faults = FaultInjector()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    for i in range(3):
        await wal.append("doc", b"edit-%d" % i)
    faults.fail_fsync(1)  # the checkpoint tick's journal fsync dies
    await wal.checkpoint("doc", b"SNAP")
    assert wal.stats["append_errors"] == 1
    records, _report = await wal.replay("doc")
    payloads = _payloads(records)
    for i in range(3):
        assert b"edit-%d" % i in payloads, (
            "history dropped before the snapshot became durable"
        )


async def test_rotation_settles_unloaded_docs(tmp_path):
    """A doc unloaded (handle released) while its window is journal-
    covered: rotation must settle its tail segment file without the
    doc being resident — and without losing the record."""
    wal = WalManager(str(tmp_path), fsync="tick", journal_max_bytes=128)
    await wal.append("gone", b"payload")
    wal.forget("gone")
    for i in range(20):  # push the journal past its bound
        await wal.append("busy", b"fill-%02d" % i)
    assert wal.stats["journal_rotations"] >= 1
    fresh = WalManager(str(tmp_path), fsync="tick")
    records, _report = await fresh.replay("gone")
    assert b"payload" in _payloads(records)


async def test_restart_append_after_torn_tail_is_recoverable(tmp_path):
    """The restart twin of repair_tail: scan() must cut a torn segment
    tail back to the valid boundary, or post-restart appends land
    after the corrupt frame and vanish at the NEXT recovery."""
    wal = WalManager(str(tmp_path), fsync="off")
    await wal.append("doc", b"good")
    path = wal.doc("doc").segments[-1].path
    wal.close()
    with open(path, "ab") as fh:
        fh.write(b"\xde\xad\xbe")  # the torn frame a crash leaves
    wal2 = WalManager(str(tmp_path), fsync="off")
    await wal2.append("doc", b"post-restart")
    fresh = WalManager(str(tmp_path), fsync="off")
    records, report = await fresh.replay("doc")
    assert _payloads(records) == [b"good", b"post-restart"]


async def test_restart_never_appends_to_torn_journal(tmp_path):
    """A journal surviving a crash may have a torn tail; the restarted
    process must open a NEW journal file — entries appended past a
    corrupt frame would be unreachable, and in tick mode the journal
    is the window's only durable copy."""
    import os as _os

    wal = WalManager(str(tmp_path), fsync="tick")
    await wal.append("doc", b"first")
    jdir = wal._journal_dir
    jfile = _os.path.join(jdir, sorted(_os.listdir(jdir))[0])
    wal.close()
    with open(jfile, "ab") as fh:
        fh.write(b"\x13\x37" * 5)
    wal2 = WalManager(str(tmp_path), fsync="tick")
    await wal2.append("doc", b"second")
    journals = [e for e in _os.listdir(jdir) if e.endswith(".journal")]
    assert len(journals) == 2, journals
    # a third process (crash before rotation) recovers BOTH records
    wal3 = WalManager(str(tmp_path), fsync="tick")
    records, report = await wal3.replay("doc")
    payloads = _payloads(records)
    assert b"first" in payloads and b"second" in payloads
    assert report["journal_torn_records"] == 1


async def test_failed_batch_burns_sequence_numbers(tmp_path):
    """A store captures its position while records are buffered; if
    that batch then fails, its sequence numbers must be BURNED — were
    they re-used by later records, the store's truncation would cover
    (and delete) updates that arrived after its encode."""
    faults = FaultInjector()
    wal = WalManager(str(tmp_path), fsync="off", faults=faults)
    await wal.append("doc", b"durable-0")
    future = wal.append("doc", b"doomed-1")
    wal.append("doc", b"doomed-2")
    captured = wal.position("doc")  # the store's coverage point
    assert captured == 3
    faults.fail_disk_full(1)
    await future
    assert wal.stats["append_errors"] == 1
    # a record landing after the store's encode must stay OUTSIDE the
    # captured coverage even though the doomed batch freed its slots
    await wal.append("doc", b"after-encode")
    wal.truncate_through("doc", captured - 1)
    records, _report = await wal.replay("doc")
    assert b"after-encode" in _payloads(records), (
        "post-encode record was truncated as store-covered"
    )


# -- the commit lane and its gate ----------------------------------------------


async def test_gate_resolves_in_the_turn_the_commit_lands(tmp_path):
    """One threadsafe call carries the gate from the lane thread to the
    loop: the commit-done step resolves the gate and runs what registered
    on it in ONE turn of the loop, in registration order, and nothing runs
    before `_commit` has returned."""
    faults = HoldingFaults()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    events = []
    real_commit, real_done = wal._commit, wal._commit_done
    turns = TurnCounter()

    def commit(pending):
        real_commit(pending)
        events.append("commit returned")

    def commit_done(gate, since):
        events.append(("commit done", turns.turn))
        real_done(gate, since)

    wal._commit, wal._commit_done = commit, commit_done
    try:
        gate = wal.append("doc", b"x")
        for tag in ("first", "second", "third"):
            gate.on_release(lambda tag=tag: events.append((tag, turns.turn, gate.done())))
        await faults.held()
        assert events == [] and not gate.done()
        faults.release.set()
        await asyncio.wait_for(gate, timeout=5)
    finally:
        turns.stop()
    landed = events[1][1]
    assert events == [
        "commit returned",
        ("commit done", landed),
        ("first", landed, True),
        ("second", landed, True),
        ("third", landed, True),
    ]
    assert wal.stats["ticks_released"] == 3
    assert wal.stats["durable_wait_ms_total"] > 0
    # registering on a resolved gate runs at once, and is not a release
    gate.on_release(lambda: events.append("late"))
    assert events[-1] == "late" and wal.stats["ticks_released"] == 3


async def test_commit_done_turns_the_lane_round_at_once(tmp_path):
    """Appends that land mid-commit join the next batch, and the
    commit-done step starts that batch itself, BEFORE it releases the
    finished batch's gate: the next write runs on the lane thread while
    the loop delivers. Commits stay serialised and in append order."""
    faults = HoldingFaults()
    wal = WalManager(str(tmp_path), fsync="off", faults=faults)
    first = wal.append("doc", b"one")
    await faults.held()
    second = wal.append("doc", b"two")
    third = wal.append("other", b"three")
    assert second is third and second is not first
    assert wal._inflight is first and wal._start_handle is None
    started = []
    first.on_release(lambda: started.append((wal._inflight, dict(wal._pending))))
    second.on_release(lambda: started.append("second released"))
    faults.release.set()
    await asyncio.wait_for(second, timeout=5)
    # inside the first gate's resolution the lane already holds the
    # second batch, and nothing is left buffered
    assert started == [(second, {}), "second released"]
    assert wal.stats["commit_batches"] == 2 and wal.stats["commits_turned_early"] == 1
    assert wal._inflight is None and wal._gate is None and not wal._pending
    records, _ = await wal.replay("doc")
    assert _payloads(records) == [b"one", b"two"]


@pytest.mark.parametrize("mode", ["tick", "always", "off"])
async def test_a_commit_that_returns_inside_the_last_resolution_waits_its_turn(tmp_path, mode):
    """The next batch's commit can return while the loop still delivers the
    last one: its gate is released by its own commit-done step, a later
    turn, never inside the resolution of the gate before it; gates release
    in batch order and each only after its own commit has returned."""
    faults = HoldingFaults()
    wal = WalManager(str(tmp_path), fsync=mode, faults=faults)
    returned = set()  # the docs whose commit is back from the lane thread
    real_commit = wal._commit

    def commit(pending):
        real_commit(pending)
        returned.update(pending)

    wal._commit = commit
    first = wal.append("a", b"one")
    await faults.held()
    second = wal.append("b", b"two")
    order = []

    def deliver_first():
        # hold the loop here until the second commit is back from the lane
        # thread: its completion is queued behind this resolution
        assert wal._inflight is second and "a" in returned
        deadline = time.monotonic() + 5
        while "b" not in returned and time.monotonic() < deadline:
            time.sleep(0.001)
        order.append(("first", "b" in returned, second.done()))

    first.on_release(deliver_first)
    second.on_release(lambda: order.append(("second", "b" in returned, second.done())))
    faults.release.set()
    await asyncio.wait_for(second, timeout=5)
    assert order == [("first", True, False), ("second", True, True)]
    assert wal.stats["commit_batches"] == 2 and wal.stats["commits_turned_early"] == 1
    for name, payload in (("a", b"one"), ("b", b"two")):
        records, _ = await wal.replay(name)
        assert _payloads(records) == [payload]


async def test_a_failed_commit_releases_its_gate_and_turns_the_lane(tmp_path):
    faults = HoldingFaults()
    faults.fail_disk_full(1)  # the held commit fails once it is let go
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    first = wal.append("doc", b"lost")
    await faults.held()
    second = wal.append("doc", b"kept")
    seen = []
    first.on_release(lambda: seen.append((first.done(), wal._inflight is second)))
    faults.release.set()
    await asyncio.wait_for(second, timeout=5)
    assert seen == [(True, True)]
    assert wal.stats["append_errors"] == 1 and wal.stats["commits_turned_early"] == 1
    records, _ = await wal.replay("doc")
    assert _payloads(records) == [b"kept"]


async def test_a_closed_manager_starts_nothing(tmp_path):
    faults = HoldingFaults()
    wal = WalManager(str(tmp_path), fsync="off", faults=faults)
    first = wal.append("doc", b"one")
    await faults.held()
    second = wal.append("doc", b"two")
    lane = wal._lane
    wal.close()
    faults.release.set()
    await asyncio.wait_for(first, timeout=5)  # the commit in flight still releases
    await asyncio.sleep(0.01)
    assert wal._inflight is None and wal._lane is None and not second.done()
    assert list(wal._pending) == ["doc"] and wal.stats["commits_turned_early"] == 0
    lane.shutdown(wait=True)


async def test_commits_turned_early_counts_only_steps_that_found_records(tmp_path):
    faults = HoldingFaults()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    first = wal.append("doc", b"one")  # alone: nothing buffered behind it
    await faults.held()
    faults.release.set()
    await asyncio.wait_for(first, timeout=5)
    assert wal.stats["commit_batches"] == 1 and wal.stats["commits_turned_early"] == 0
    faults.entered.clear()
    faults.release.clear()
    second = wal.append("doc", b"two")
    await faults.held()
    third = wal.append("doc", b"three")  # buffered behind the held commit
    faults.release.set()
    await asyncio.wait_for(third, timeout=5)
    assert second.done()
    assert wal.stats["commit_batches"] == 3 and wal.stats["commits_turned_early"] == 1
    await asyncio.wait_for(wal.append("doc", b"four"), timeout=5)
    assert wal.stats["commit_batches"] == 4 and wal.stats["commits_turned_early"] == 1


async def test_flush_waits_for_the_commit_in_flight_and_what_is_buffered(tmp_path):
    faults = HoldingFaults()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    wal.append("doc", b"one")
    await faults.held()
    wal.append("doc", b"two")  # buffered behind the held commit
    flushed = asyncio.ensure_future(wal.flush())
    await asyncio.sleep(0.01)
    assert not flushed.done()
    faults.release.set()
    await asyncio.wait_for(flushed, timeout=5)
    assert wal.stats["appended_records"] == 2 and wal.stats["commit_batches"] == 2
    await asyncio.wait_for(wal.flush(), timeout=5)  # nothing left: returns at once


def test_no_loop_append_is_durable_before_it_returns(tmp_path):
    """Direct use with no running loop: the commit runs inside `append`,
    and the gate it returns has nothing left to wait for."""
    wal = WalManager(str(tmp_path), fsync="tick")
    gate = wal.append("doc", b"sync")
    assert gate.done() and gate.result() is None
    assert wal.stats["appended_records"] == 1 and wal.stats["fsyncs"] == 1
    assert wal.stats["ticks_released"] == 0
    records, _ = asyncio.run(wal.replay("doc"))
    assert b"sync" in _payloads(records)
    assert wal.checkpoint("doc", b"snapshot").done()
    records, _ = asyncio.run(wal.replay("doc"))
    assert _payloads(records) == [b"snapshot"]  # the checkpoint subsumed the history


async def test_a_release_callback_that_raises_strands_nothing(tmp_path):
    wal = WalManager(str(tmp_path), fsync="off")
    ran = []
    gate = wal.append("doc", b"one")

    def boom():
        raise RuntimeError("a tick's delivery failed")

    gate.on_release(lambda: ran.append("before"))
    gate.on_release(boom)
    gate.on_release(lambda: ran.append("after"))
    gate.on_release(lambda: wal.append("doc", b"two"))  # buffered inside the resolution
    await asyncio.wait_for(gate, timeout=5)
    assert ran == ["before", "after"]
    assert wal.stats["ticks_released"] == 4
    await asyncio.wait_for(wal.flush(), timeout=5)
    records, _ = await wal.replay("doc")
    assert _payloads(records) == [b"one", b"two"]


async def test_a_failed_commit_releases_what_registered(tmp_path):
    faults = FaultInjector()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    faults.fail_disk_full(1)
    released = []
    gate = wal.append("doc", b"x")
    gate.on_release(lambda: released.append(gate.done()))
    discarded = lambda: released.append("discarded")  # noqa: E731
    gate.on_release(discarded)
    gate.discard(discarded)
    gate.discard(discarded)  # forgetting twice is harmless
    await asyncio.wait_for(gate, timeout=5)
    assert released == [True]
    assert wal.stats["append_errors"] == 1 and wal.stats["ticks_released"] == 1


async def test_a_commit_that_raises_still_releases_and_the_lane_goes_on(tmp_path):
    wal = WalManager(str(tmp_path), fsync="off")
    real_commit = wal._commit
    calls = []

    def commit(pending):
        calls.append(sorted(pending))
        if len(calls) == 1:
            raise RuntimeError("not a disk error")
        real_commit(pending)

    wal._commit = commit
    await asyncio.wait_for(wal.append("doc", b"lost"), timeout=5)
    await asyncio.wait_for(wal.append("doc", b"kept"), timeout=5)
    assert calls == [["doc"], ["doc"]]
    records, _ = await wal.replay("doc")
    assert _payloads(records) == [b"kept"]


async def test_a_waiter_that_gives_up_does_not_cancel_the_shared_gate(tmp_path):
    """`flush()` under a timeout (the shutdown path) awaits the batch's gate;
    giving up must leave the gate to the ticks registered on it."""
    faults = HoldingFaults()
    wal = WalManager(str(tmp_path), fsync="tick", faults=faults)
    gate = wal.append("doc", b"x")
    released = []
    gate.on_release(lambda: released.append(gate.cancelled()))
    await faults.held()
    with pytest.raises(asyncio.TimeoutError):
        await asyncio.wait_for(wal.flush(), timeout=0.05)
    assert not gate.done() and released == []
    faults.release.set()
    await asyncio.wait_for(wal.flush(), timeout=5)
    assert released == [False] and wal.stats["appended_records"] == 1
