"""`wal_early_turn_share`: the reader on hand-made runs, its entry in the
manifest, and the program's counter it reads. Runs on the CPU; loads no libtpu."""

from __future__ import annotations

import asyncio
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench", "lib"))

from manifest import Manifest  # noqa: E402

METRIC = "wal_early_turn_share"


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.mark.parametrize(
    "wal_delta, expected",
    [
        ({"commits_turned_early": 23, "commit_batches": 25, "ticks_released": 700}, 92.0),
        ({"commits_turned_early": 25, "commit_batches": 25}, 100.0),  # records behind every commit
        ({"commits_turned_early": 0, "commit_batches": 12}, 0.0),  # no commit ever found records buffered
        ({"commit_batches": 12, "ticks_released": 30}, None),  # the parent commit: no such counter
        ({"commits_turned_early": 0, "commit_batches": 0}, None),  # nothing committed in the window
        ({}, None),  # a server without a log
    ],
)
@pytest.mark.parametrize("name", [METRIC, METRIC + ".open"])  # the split entry is read by the same file
def test_the_reader_on_a_hand_made_run(manifest, name, wal_delta, expected):
    found = manifest.reader(name)({"wal_delta": wal_delta, "plane_delta": {}, "trace": None})
    assert found == expected


def test_every_cell_reports_it_under_the_log_layer(manifest):
    """One counter, three entries: a cell that reports a per-layer metric has
    to report the end-to-end metric it moves, so the share moves the throughput
    in the closed loop, the tail in the open loops that bound it and the median
    in `typing-append`, read by the same file."""
    entries = {m["name"]: m for m in manifest.data["per_layer"] if m["name"].split(".")[0] == METRIC}
    common = {"unit": "%", "better": "higher", "source": "program_counter", "layer": "write-ahead log"}
    assert entries == {
        METRIC: {"name": METRIC, **common, "moves": "updates_delivered_per_s", "workloads": ["conflict-midinsert"]},
        METRIC + ".open": {
            "name": METRIC + ".open", **common, "moves": "update_to_peer_p95_ms",
            "workloads": ["cells4-typing", "paper-cursor-edit", "paper-cursor-edit-rle"],
        },
        METRIC + ".typing": {
            "name": METRIC + ".typing", **common, "moves": "update_to_peer_p50_ms", "workloads": ["typing-append"],
        },
    }
    manifest.check_names()
    for cell in manifest.cells:
        reported = [m for m in manifest.metrics_of(cell, "per_layer") if m["name"] in entries]
        assert len(reported) == 1, cell
        assert manifest.reader(reported[0]["name"]) is not None


def test_the_log_keeps_the_counter_the_reader_reads(manifest, tmp_path):
    """The counter is there from the start, as a number (`wal_delta` differences
    `wal.stats` key by key), and a window with records behind one commit of two
    reads half."""
    sys.path.insert(1, ROOT)
    from hocuspocus_tpu.storage import WalManager
    from tests.utils import HoldingFaults

    fresh = WalManager(str(tmp_path / "fresh")).stats
    assert fresh["commits_turned_early"] == 0 and fresh["commit_batches"] == 0

    async def window():
        faults = HoldingFaults()
        wal = WalManager(str(tmp_path / "window"), fsync="off", faults=faults)
        before = dict(wal.stats)
        wal.append("doc", b"one")
        await faults.held()
        second = wal.append("doc", b"two")  # buffered behind the held commit
        faults.release.set()
        await asyncio.wait_for(second, timeout=5)
        return {k: v - before[k] for k, v in wal.stats.items()}

    assert manifest.reader(METRIC)({"wal_delta": asyncio.run(window())}) == 50.0
