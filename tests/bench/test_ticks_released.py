"""`ticks_released_per_commit`: the reader on hand-made runs, its entry in the
manifest, and the program's counter it reads. Runs on the CPU; loads no libtpu."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench", "lib"))

from manifest import Manifest  # noqa: E402

METRIC = "ticks_released_per_commit"


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.mark.parametrize(
    "wal_delta, expected",
    [
        ({"ticks_released": 30, "commit_batches": 12, "appended_records": 40}, 2.5),
        ({"ticks_released": 0, "commit_batches": 12}, 0.0),  # commits, and no tick was ever gated
        ({"commit_batches": 12, "durable_wait_ms_total": 66.0}, None),  # the parent commit: no such counter
        ({"ticks_released": 0, "commit_batches": 0}, None),  # nothing committed in the window
        ({}, None),  # a server without a log
    ],
)
def test_the_reader_on_a_hand_made_run(manifest, wal_delta, expected):
    found = manifest.reader(METRIC)({"wal_delta": wal_delta, "plane_delta": {}, "trace": None})
    assert found == expected


def test_every_cell_reports_it_under_the_log_layer(manifest):
    (entry,) = [m for m in manifest.data["per_layer"] if m["name"] == METRIC]  # by name: later PRs append
    assert entry == {
        "name": METRIC, "unit": "ticks", "better": "higher", "source": "program_counter",
        "layer": "write-ahead log", "moves": "update_to_peer_p95_ms",
    }
    manifest.check_names()
    for cell in manifest.cells:
        assert_reported(manifest, cell, entry)


def test_the_log_keeps_the_counter_the_reader_reads(tmp_path):
    """`wal_delta` (bench/run.py) is the difference of `wal.stats` at the window's
    edges, key by key: the counter has to be there from the start, as a number."""
    sys.path.insert(1, ROOT)
    from hocuspocus_tpu.storage import WalManager

    stats = WalManager(str(tmp_path)).stats
    assert stats["ticks_released"] == 0 and stats["commit_batches"] == 0


def assert_reported(manifest, cell, entry):
    """The cell reports the quantity: under the entry itself, or where it
    reports another end-to-end metric than the entry moves, under its twin
    that lists the cell and moves what the cell reports."""
    reported = manifest.reported_as(cell, entry["name"])
    assert reported["moves"] in {m["name"] for m in manifest.metrics_of(cell, "end_to_end")}
    same = {key: value for key, value in entry.items() if key not in ("name", "moves")}
    assert {key: value for key, value in reported.items() if key not in ("name", "moves", "workloads")} == same
    assert reported["name"] == entry["name"] or cell in reported["workloads"]
