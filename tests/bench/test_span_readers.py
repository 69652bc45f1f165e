"""The readers of the program's own spans and counters (bench/metrics/, with
bench/lib/spans.py), each on a trace and a counter set made by hand: the
self-time subtraction, what is left unattributed, and None, never 0, where a
program has no such span or counter (a parent commit, a rehearsal)."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench", "lib"))

import tracereduce  # noqa: E402
from manifest import Manifest  # noqa: E402

MS = 1_000_000
# a window of 100 ms: the loop asleep for 20, under the program's spans for 28
# (children inside their parents), awake under none for 52; an executor thread
# in the plane's flush for 12 and in the log's commit for 6
LOOP = [
    ("bench.loop_asleep", 0, 20 * MS),
    ("connection.dispatch", 20 * MS, 2 * MS),
    ("message.update_apply", 22 * MS, 10 * MS),
    ("wal.append", 23 * MS, 1 * MS),
    ("plane.capture", 24 * MS, 4 * MS),
    ("plane.lower", 25 * MS, 2 * MS),
    ("plane.broadcast", 40 * MS, 5 * MS),
    ("fanout.tick", 50 * MS, 8 * MS),
    ("fanout.tick", 60 * MS, 2 * MS),
    ("plane.post_flush", 70 * MS, 1 * MS),
]
EXECUTOR = [
    ("merge_plane.flush", 30 * MS, 12 * MS),
    ("merge_plane.drain", 30 * MS, 1 * MS),
    ("merge_plane.integrate", 32 * MS, 2 * MS),
    ("merge_plane.readback", 35 * MS, 7 * MS),
    ("wal.commit", 50 * MS, 6 * MS),
    ("wal.fsync", 51 * MS, 5 * MS),
]
DEVICE = ("/device:TPU:0", [("XLA Ops", [("fusion", 33 * MS, MS)])])
EXPECTED = {
    "loop_apply_share": 2 + 10 - 1 - 4,
    "loop_wal_append_share": 1,
    "loop_capture_share": 4,
    "loop_broadcast_share": 5,
    "loop_fanout_share": 10,
    "loop_post_flush_share": 1,
    "loop_unattributed_share": 100 - 20 - (2 + 10 + 5 + 10 + 1),
    "executor_flush_share": 12,
}
COUNTED = {
    "bucket_fill_share": 100 * 37 / 64,
    "broadcast_wait_ms": 90.0 / 30,
    "wal_commit_ms": 48.0 / 12,
    "wal_durable_wait_ms": 66.0 / 12,
}
PLANE_DELTA = {"flush_busy_rows": 37, "flush_bucket_rows": 64, "broadcast_passes": 30, "broadcast_wait_ms_total": 90.0}
WAL_DELTA = {"commit_batches": 12, "commit_ms_total": 48.0, "durable_wait_ms_total": 66.0, "appended_records": 40}


def traced(loop=LOOP, executor=EXECUTOR) -> dict:
    planes = [("/host:CPU", [("loop", loop), ("executor", executor)]), DEVICE]
    return {"trace": tracereduce.reduce(planes, 0.1), "plane_delta": PLANE_DELTA, "wal_delta": WAL_DELTA}


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_a_span_reader_gives_the_share_of_the_window(manifest, metric):
    read = manifest.reader(metric)
    assert read(traced()) == pytest.approx(EXPECTED[metric])
    # a program without the spans (the parent commit; a rehearsal, whose host
    # plane stands in for the device): nothing to read, and never 0
    only_the_harness = traced(loop=LOOP[:1], executor=[])
    assert only_the_harness["trace"]["span_seconds"] == {"bench.loop_asleep": pytest.approx(0.02)}
    assert read(only_the_harness) is None
    assert read({"trace": None, "plane_delta": {}, "wal_delta": {}}) is None
    assert read({"plane_delta": {}, "wal_delta": {}}) is None


@pytest.mark.parametrize("metric", sorted(COUNTED))
def test_a_counter_reader_gives_the_mean_per_batch(manifest, metric):
    read = manifest.reader(metric)
    assert read(traced()) == pytest.approx(COUNTED[metric])
    assert read({"plane_delta": {"flush_fast_ops": 3}, "wal_delta": {"commit_batches": 12}}) is None  # the parent's counters
    nothing_counted = {"plane_delta": dict.fromkeys(PLANE_DELTA, 0), "wal_delta": dict.fromkeys(WAL_DELTA, 0)}
    assert read(nothing_counted) is None


def test_the_loop_shares_and_the_sleep_partition_the_window(manifest):
    run = traced()
    stages = [name for name in EXPECTED if name.startswith("loop_")]
    asleep = 100 * run["trace"]["span_seconds"]["bench.loop_asleep"] / run["trace"]["window_s"]
    assert sum(manifest.reader(name)(run) for name in stages) + asleep == pytest.approx(100.0)


def test_unattributed_is_printed_as_computed_never_clipped(manifest):
    # spans that interleave or count twice must show: 60 ms under each of two
    # top-level spans and 20 asleep in a window of 100 leave -40
    twice = [("bench.loop_asleep", 0, 20 * MS), ("fanout.tick", 20 * MS, 60 * MS), ("plane.broadcast", 20 * MS, 60 * MS)]
    assert manifest.reader("loop_unattributed_share")(traced(loop=twice)) == pytest.approx(-40.0)
    # a loop that never slept in its selector has no such span: all of the rest is unattributed
    awake = [("fanout.tick", 0, 25 * MS)]
    assert manifest.reader("loop_unattributed_share")(traced(loop=awake)) == pytest.approx(75.0)


def test_a_stage_without_its_children_is_all_its_own(manifest):
    # a deployment with no log and no plane: nothing opens inside the apply
    bare = [("connection.dispatch", 0, 2 * MS), ("message.update_apply", 2 * MS, 10 * MS)]
    run = traced(loop=bare)
    assert manifest.reader("loop_apply_share")(run) == pytest.approx(12.0)
    assert manifest.reader("loop_wal_append_share")(run) is None
    assert manifest.reader("loop_capture_share")(run) is None


def test_every_new_metric_is_reported_by_both_cells_and_moves_what_both_report(manifest):
    for cell in manifest.cells:
        end_to_end = {m["name"] for m in manifest.metrics_of(cell, "end_to_end")}
        for name in list(EXPECTED) + list(COUNTED):
            (entry,) = [m for m in manifest.data["per_layer"] if m["name"] == name]
            assert entry["moves"] == "update_to_peer_p95_ms" and "workloads" not in entry
            # where the cell reports another end-to-end metric, a twin that lists it
            reported = manifest.reported_as(cell, name)
            assert reported["moves"] in end_to_end
            assert reported is entry or (cell in reported["workloads"] and reported["name"].startswith(name + "."))
            assert reported["source"] == ("program_span" if name in EXPECTED else "program_counter")
