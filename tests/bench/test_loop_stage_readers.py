"""The readers of the loop's stages (`loop_read_share`, `loop_receive_share`,
`loop_flush_turn_share`, `loop_unnamed_share`, with bench/lib/loop_stages.py)
on traces made by hand: None on a program without the stages, the own-time
subtractions, the window partitioned by sleep, polls, stages and the unnamed
rest, and the older partition left as it was. Runs on the CPU; loads no libtpu."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench", "lib"))

import spans  # noqa: E402
import tracereduce  # noqa: E402
from loop_stages import LOOP_STAGES, POLL  # noqa: E402
from manifest import Manifest  # noqa: E402

MS = 1_000_000
# a window of 100 ms on the loop: asleep 20, every stage once (children inside
# their parents), 47 awake under no stage
LOOP = [
    ("bench.loop_asleep", 0, 20 * MS),
    ("transport.read", 20 * MS, 2 * MS),
    ("connection.receive", 22 * MS, 10 * MS),
    ("connection.dispatch", 22 * MS, 2 * MS),
    ("message.update_apply", 25 * MS, 5 * MS),
    ("wal.append", 26 * MS, 1 * MS),
    ("transport.write_queued", 33 * MS, 3 * MS),
    ("plane.broadcast", 40 * MS, 4 * MS),
    ("fanout.tick", 45 * MS, 6 * MS),
    ("transport.write_inline", 46 * MS, 2 * MS),
    ("plane.flush_turn", 60 * MS, 5 * MS),
    ("plane.post_flush", 62 * MS, 2 * MS),
    ("wal.commit_done", 70 * MS, 1 * MS),
    ("heap.pass", 80 * MS, 2 * MS),
]
EXPECTED = {
    "loop_read_share": 2,
    "loop_receive_share": 10 - 2 - 5,
    "loop_flush_turn_share": 5 - 2,
    "loop_unnamed_share": 100 - 20 - (2 + 10 + 3 + 4 + 6 + 5 + 1 + 2),
}
# what the parent commit's program opens: the older spans alone
OLDER = [name for name in LOOP if name[0] not in ("transport.read", "connection.receive", "plane.flush_turn", "wal.commit_done")]


def traced(loop=LOOP) -> dict:
    device = ("/device:TPU:0", [("XLA Ops", [("fusion", 33 * MS, MS)])])
    return {"trace": tracereduce.reduce([("/host:CPU", [("loop", loop)]), device], 0.1), "plane_delta": {}, "wal_delta": {}}


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_a_stage_reader_gives_its_own_time_and_none_on_an_older_program(manifest, metric):
    read = manifest.reader(metric)
    assert read(traced()) == pytest.approx(EXPECTED[metric])
    assert read(traced(OLDER)) is None  # the parent commit: no such stage, no fall that is only a new yardstick
    assert read(traced(LOOP[:1])) is None
    assert read({"trace": None, "plane_delta": {}, "wal_delta": {}}) is None


def test_sleep_polls_stages_and_the_unnamed_rest_partition_the_window(manifest):
    polled = traced(LOOP + [(POLL, 90 * MS, 4 * MS)])
    have = polled["trace"]["span_seconds"]
    shares = {name: 100 * have.get(name, 0.0) / 0.1 for name in ("bench.loop_asleep", POLL) + LOOP_STAGES}
    unnamed = manifest.reader("loop_unnamed_share")(polled)
    assert unnamed == pytest.approx(EXPECTED["loop_unnamed_share"] - 4)
    assert sum(shares.values()) + unnamed == pytest.approx(100.0)
    # a stage's share is its own time plus its children's readings
    apply = manifest.reader("loop_apply_share")(polled)
    assert shares["connection.receive"] == pytest.approx(manifest.reader("loop_receive_share")(polled) + apply + 1)
    post_flush = manifest.reader("loop_post_flush_share")(polled)
    assert shares["plane.flush_turn"] == pytest.approx(manifest.reader("loop_flush_turn_share")(polled) + post_flush)


def test_unnamed_is_printed_as_computed_never_clipped(manifest):
    twice = [("bench.loop_asleep", 0, 20 * MS), ("connection.receive", 20 * MS, 60 * MS), ("fanout.tick", 20 * MS, 60 * MS)]
    assert manifest.reader("loop_unnamed_share")(traced(twice)) == pytest.approx(-40.0)


def test_the_older_partition_is_left_as_it_was(manifest):
    assert spans.LOOP_TOP_LEVEL == (
        "connection.dispatch", "message.update_apply", "plane.broadcast", "fanout.tick", "plane.post_flush",
    )
    # the older rest still counts what the new stages name as unattributed
    assert manifest.reader("loop_unattributed_share")(traced()) == pytest.approx(100 - 20 - (2 + 5 + 4 + 6 + 2))
    assert manifest.reader("loop_unattributed_share")(traced(OLDER)) == pytest.approx(100 - 20 - (2 + 5 + 4 + 6 + 2))


@pytest.mark.parametrize("name, layer", [
    ("loop_read_share", "server event loop and wire codec"),
    ("loop_receive_share", "server event loop and wire codec"),
    ("loop_flush_turn_share", "serving and flush triage"),
    ("loop_unnamed_share", "server event loop and wire codec"),
])
def test_every_cell_reports_them(manifest, name, layer):
    (entry,) = [m for m in manifest.data["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "%", "better": "lower", "source": "program_span",
        "layer": layer, "moves": "update_to_peer_p95_ms",
    }
    manifest.check_names()
    for cell in manifest.cells:
        assert_reported(manifest, cell, entry)


def test_device_idle_split_by_loop_stage_sums_to_the_idle_time():
    """`tracereduce.reduce` labelled by the loop's stages: each idle gap of the
    device is split among the stages that cover it, the rest is host busy, and
    nothing a metric reads (busy, programs, spans) moves."""
    from loop_stages import IDLE_LABELS

    device = ("/device:TPU:0", [("XLA Ops", [("fusion", 33 * MS, MS), ("copy", 61 * MS, 2 * MS)])])
    # `reduce` prints ten entries of idle time and longest gap: four stages and
    # the host's rest fill them, so every label's idle time is printed here
    four = [span for span in LOOP if span[1] < 33 * MS or span[0].startswith("plane.")]
    planes = [("/host:CPU", [("loop", four)]), device]
    plain = tracereduce.reduce(planes, 0.1)
    by_stage = tracereduce.reduce(planes, 0.1, label_prefix=IDLE_LABELS)
    for key in ("busy_s", "program_seconds", "span_seconds", "device_ops", "window_s"):
        assert by_stage[key] == plain[key], key
    idle = {name[: -len(": idle time")]: s for name, s in by_stage["idle_gaps"] if name.endswith(": idle time")}
    edges = 0.065  # the trace's first and last event: 0 to 65 ms
    assert sum(idle.values()) == pytest.approx(edges - by_stage["busy_s"])
    assert set(idle) == {"bench.loop_asleep", "transport.read", "connection.receive", "plane.broadcast", "plane.flush_turn", tracereduce.HOST_BUSY}
    # the device idle all through the read callback and the receive; of the flush
    # turn's 5 ms the device's second op takes 2
    assert idle["transport.read"] == pytest.approx(0.002)
    assert idle["connection.receive"] == pytest.approx(0.010)
    assert idle["plane.flush_turn"] == pytest.approx(0.003)
    assert idle["bench.loop_asleep"] == pytest.approx(0.020)
    assert "connection.dispatch" not in idle  # a child names no gap: its stage does


def assert_reported(manifest, cell, entry):
    """The cell reports the quantity: under the entry itself, or where it
    reports another end-to-end metric than the entry moves, under its twin
    that lists the cell and moves what the cell reports."""
    reported = manifest.reported_as(cell, entry["name"])
    assert reported["moves"] in {m["name"] for m in manifest.metrics_of(cell, "end_to_end")}
    same = {key: value for key, value in entry.items() if key not in ("name", "moves")}
    assert {key: value for key, value in reported.items() if key not in ("name", "moves", "workloads")} == same
    assert reported["name"] == entry["name"] or reported["workloads"] == [cell]
