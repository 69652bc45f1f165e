"""What the run-length arena's integrate path says of itself: the entries the
device took (`rle_entries_appended`, from one more scalar of the cycle's
health readback), the entries its batches swept (`integrate_row_entries`,
where the unit arena counts `integrate_row_units`), the `arena` and
`row_entries` attributes of the `merge_plane.integrate` span, and the wait
for the device as a span of its own inside `merge_plane.readback`.

A scripted flush on `MergePlane(arena="rle")`, on the CPU. No cell of the
benchmark runs this arena yet (PERF.md section 7, Open question 9).
"""

import json
import os
import sys

import numpy as np
import pytest

from hocuspocus_tpu.crdt import Doc
from hocuspocus_tpu.observability.tracing import get_tracer
from hocuspocus_tpu.tpu.merge_plane import MergePlane

FIRST_TEXT = "the first text of a long paper, typed by an earlier author"


class Planted:
    """A plane with one document whose author's updates reach it a flush at a time."""

    def __init__(self, arena: str = "rle", capacity: int = 64) -> None:
        self.plane = MergePlane(num_docs=8, capacity=capacity, arena=arena)
        self.plane.register("paper")
        self.doc, self.made = Doc(), []
        self.doc.client_id = 1 << 31 | 6
        self.doc.on("update", lambda update, origin, *rest: self.made.append(update))
        self.body = self.doc.get_text("t")
        self.body.insert(0, FIRST_TEXT)
        self.flush()

    def flush(self) -> dict:
        """Hand every update made since the last flush to the plane, run one
        cycle, and return what the plane's counters moved by."""
        before = dict(self.plane.counters)
        for update in self.made:
            self.plane.enqueue_update("paper", update)
        self.made.clear()
        self.plane.flush()
        return {key: value - before[key] for key, value in self.plane.counters.items()}

    def entries_on_device(self) -> int:
        return int(np.asarray(self.plane.state.num_runs).sum())


def test_the_entries_the_device_took_are_counted_from_the_health_readback():
    planted = Planted()
    plane = planted.plane
    assert planted.entries_on_device() == 1  # the first text is one run
    assert plane.counters["rle_entries_appended"] == 1
    body = planted.body

    body.insert(10, "x")  # inside the first text's run: the run splits, and the unit is an entry
    moved = planted.flush()
    assert moved["flush_slow_ops"] == 1 and moved["rle_entries_appended"] == 2
    assert moved["integrate_row_entries"] == 1 * plane.capacity  # a bucket of one row, swept whole
    assert moved["integrate_row_units"] == 0  # the unit arena's counter

    body.insert(11, "y")  # typed on: after the unit just typed, at a run's boundary
    moved = planted.flush()
    assert moved["flush_slow_ops"] == 1 and moved["rle_entries_appended"] == 1

    body.delete(30, 1)  # one unit inside a run: a split before it and one after it
    assert planted.flush()["rle_entries_appended"] == 2
    body.delete(11, 1)  # a unit that is an entry of its own: a tombstone, no entry
    moved = planted.flush()
    assert moved["slow_ops_delete"] == 1 and moved["rle_entries_appended"] == 0

    body.insert(20, "p")  # three operations in one cycle: 2 + 1 + 1
    body.insert(21, "q")
    body.insert(22, "r")
    moved = planted.flush()
    assert moved["flush_slow_ops"] == 3 and moved["rle_entries_appended"] == 4

    # the counter is the change of num_runs as read back, to the entry
    assert plane.counters["rle_entries_appended"] == planted.entries_on_device() == 10
    assert plane.text("paper") == body.to_string()
    assert not np.asarray(plane.state.overflow).any()


def test_a_row_taken_away_takes_its_entries_and_no_other_row_s():
    """A released row's entries go with it: counted neither below zero nor
    as the next document's, and what another row's ops took in the same
    cycle is still counted."""
    planted = Planted()
    plane = planted.plane
    plane.register("beside")
    beside = Doc()
    beside.client_id = 1 << 30 | 9
    beside.on("update", lambda update, origin, *rest: plane.enqueue_update("beside", update))
    beside.get_text("t").insert(0, "another text")
    planted.body.insert(5, "x")
    planted.flush()
    counted = plane.counters["rle_entries_appended"]
    assert counted == planted.entries_on_device() == 3 + 1
    beside.get_text("t").insert(4, "y")  # queued: integrated in the cycle after the release
    plane.release("paper")
    assert planted.entries_on_device() == 1
    plane.register("other")
    plane.flush()
    assert plane.counters["rle_entries_appended"] == counted + 2 == planted.entries_on_device() + 3


def test_a_hydrated_row_counts_what_its_snapshot_s_ops_took():
    """A document that comes back (residency, recovery) reaches its row as
    ops of its snapshot through the same integrate: an entry an op there too,
    so entries over ops flushed stays what an op costs."""
    from hocuspocus_tpu.crdt import encode_state_as_update

    planted = Planted()
    plane = planted.plane
    planted.body.insert(10, "x")
    planted.flush()
    snapshot = encode_state_as_update(planted.doc)
    plane.release("paper")
    before = dict(plane.counters)
    plane.register("paper")
    plane.enqueue_update("paper", snapshot, presync=True)
    plane.flush()
    moved = {key: plane.counters[key] - before[key] for key in before}
    ops = moved["flush_fast_ops"] + moved["flush_slow_ops"]
    assert ops > 0 and moved["rle_entries_appended"] == planted.entries_on_device() <= 2 * ops
    assert plane.text("paper") == planted.body.to_string()


def test_a_defragmented_row_is_counted_from_what_the_compaction_left():
    """`compact_doc_rows_rle` merges entries away: the count does not go
    down for it, and the next op is counted from the row as compacted."""
    from hocuspocus_tpu.tpu.residency import ResidencyManager

    planted = Planted()
    plane = planted.plane
    for at in (10, 11, 12):  # a split, then two typed continuations: entries that merge back
        planted.body.insert(at, "x")
        planted.flush()
    counted = plane.counters["rle_entries_appended"]
    assert counted == planted.entries_on_device() == 5
    residency = ResidencyManager(plane=plane)
    assert residency._compact_rle_locked(plane.docs["paper"], min_reclaim=1)
    left = planted.entries_on_device()
    assert left < 5 and int(plane._rle_row_entries.sum()) == left
    planted.body.insert(30, "z")  # inside a run: two entries
    assert planted.flush()["rle_entries_appended"] == 2
    assert plane.counters["rle_entries_appended"] == counted + 2 and planted.entries_on_device() == left + 2
    assert plane.text("paper") == planted.body.to_string()


def test_the_unit_arena_counts_units_and_no_entries():
    planted = Planted(arena="unit", capacity=512)
    planted.body.insert(10, "x")
    moved = planted.flush()
    assert moved["integrate_row_units"] == 512 and moved["integrate_row_entries"] == 0
    assert planted.plane.counters["rle_entries_appended"] == 0


@pytest.mark.parametrize("arena, row_key, other", [("rle", "row_entries", "row_units"), ("unit", "row_units", "row_entries")])
def test_the_integrate_span_names_the_arena_and_the_wait_is_a_span_of_its_own(arena, row_key, other):
    planted = Planted(arena=arena, capacity=128)
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    try:
        planted.body.insert(3, "q")
        planted.flush()
        spans = tracer.export()
    finally:
        tracer.enabled = was
    integrate = [s for s in spans if s["name"] == "merge_plane.integrate"][-1]
    assert integrate["attributes"]["arena"] == arena
    assert integrate["attributes"][row_key] == planted.plane.capacity and other not in integrate["attributes"]
    readback = [s for s in spans if s["name"] == "merge_plane.readback"][-1]
    wait = [s for s in spans if s["name"] == "merge_plane.device_wait"][-1]
    ends = {s["name"]: s["start"] + s["duration_ms"] / 1000.0 for s in (readback, wait)}
    assert readback["start"] <= wait["start"] and ends["merge_plane.device_wait"] <= ends["merge_plane.readback"]


def test_a_run_length_step_that_drops_a_split_is_not_correct(monkeypatch, capsys):
    """The benchmark's rehearsal of the cell `paper-cursor-edit-rle` (two
    planes of 64 x 2,048 entries) in this process, with a fault under the timed path
    that sets in when the traffic starts: where a keystroke split a run,
    the step leaves the run whole and the unit lands behind it (the tail of
    the split and the unit trade places). Every unit is there, lengths,
    overflow flags and entry counts read as before, so the program's own
    probe passes; the text read back from the arena is in another order."""
    from hocuspocus_tpu.tpu import pallas_kernels_rle

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "bench")
    sys.path.insert(0, os.path.join(bench, "lib"))
    sys.path.insert(0, bench)
    import run as bench_run

    armed = []
    tell = bench_run.Clients.tell

    async def telling(self, line):
        if line.startswith("go"):
            armed.append(True)
        await tell(self, line)

    step = pallas_kernels_rle.integrate_op_slots_rle_sparse_fast

    def integrate(state, ops, slots):
        rows = np.asarray(slots)
        rows = rows[rows < state.num_runs.shape[0]]
        lanes = np.asarray(state.num_runs)[rows]  # read before the step donates the state
        state, count = step(state, ops, slots)
        if armed:
            rank, length = np.asarray(state.run_rank), np.asarray(state.run_len)
            took_two = np.asarray(state.num_runs)[rows] >= lanes + 2
            rows, tail = rows[took_two], lanes[took_two]  # a split's tail, then the unit that split the run
            split = rank[rows, tail] == rank[rows, tail + 1] + length[rows, tail + 1]
            rows, tail = rows[split], tail[split]
            undone = state.run_rank.at[rows, tail].set(rank[rows, tail + 1])
            undone = undone.at[rows, tail + 1].set(rank[rows, tail + 1] + length[rows, tail])
            state = state._replace(run_rank=undone)
            armed.append(len(rows))
        return state, count

    monkeypatch.setattr(bench_run.Clients, "tell", telling)
    monkeypatch.setattr(bench_run, "GRACE_SECONDS", 3.0)
    monkeypatch.setattr(pallas_kernels_rle, "integrate_op_slots_rle_sparse_fast", integrate)
    code = bench_run.main(["--workload", "paper-cursor-edit-rle", "--seed", "77", "--seconds", "2", "--trace", "0", "--rehearse"])
    captured = capsys.readouterr()
    lines = captured.out.splitlines()
    assert sum(armed[1:]) > 0, "no run was split in the window"
    assert code == 0 and lines, "no result that says it is not correct: " + captured.err[-600:]
    result = json.loads(lines[-1])
    assert result["correct"] is False
    assert result["compared"]["device_texts_differing"][0] > 0 == result["compared"]["server_texts_differing"][0]
