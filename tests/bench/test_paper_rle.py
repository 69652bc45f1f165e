"""The long document on the run-length arena: the configuration
`text-b4-paper-105k-rle` against bench/README.md's rule for a run-length
configuration and against its twin, the growth check of its cell, one
rehearsal of the cell with both controls, and the two readers the cell brings
(`integrate_roofline_rle`, `rle_entries_per_op`). Runs on the CPU; loads no
libtpu."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, os.path.join(BENCH, "lib"))
sys.path.insert(1, ROOT)

import clients  # noqa: E402
import room  # noqa: E402
from manifest import Manifest  # noqa: E402

CELL, TWIN_CELL = "paper-cursor-edit-rle", "paper-cursor-edit"
CONFIG, TWIN = "text-b4-paper-105k-rle", "text-b4-paper-105k"


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def test_the_configuration_is_its_twin_on_the_run_length_arena(manifest):
    config, twin = manifest.config(CONFIG), manifest.config(TWIN)
    # what a run-length configuration's file states (bench/README.md)
    assert room.layout(config["flags"]) == (8192, "rle", 13)
    arena = config["arena"]
    assert arena == {
        "planes": 13, "rows_per_plane": 448, "capacity_entries": 8192, "entry_bytes": 21,
        "bytes_reserved": 13 * 448 * 8192 * 21,
    }
    assert "capacity_units" not in arena and "unit_bytes" not in arena
    assert room.layout(config["rehearse"]["flags"]) == (2048, "rle", 2)
    assert config["doc_units"] == 104852  # the text a document starts with, whatever the arena counts
    # the twin's deployment but for the arena: the same documents, residents, driven set, guarantees
    restated = {"name", "flags", "arena", "source", "reduced", "assumed", "rehearse"}
    assert set(config) == set(twin)
    for key in set(twin) - restated:
        assert config[key] == twin[key], key
    assert set(config["reduced"]) == set(twin["reduced"]) and all("entr" in why or "twin" in why for why in config["reduced"].values())
    assert {k: v for k, v in config["rehearse"].items() if k != "flags"} == {k: v for k, v in twin["rehearse"].items() if k != "flags"}
    assert len(config["assumed"]) == len(twin["assumed"]) and any("8,192 entries" in line for line in config["assumed"])
    assert len(config["guarantees"]) == 4
    # two deployments from one public benchmark: the sources differ, and say what differs
    listed, listed_twin = manifest.configs[CONFIG], manifest.configs[TWIN]
    assert listed["source"] == config["source"] != listed_twin["source"]
    assert "B4" in config["source"] and "--tpu-arena rle" in config["source"] and len(config["source"]) <= 200
    assert listed["reduced"] == listed_twin["reduced"] and listed["file"] != listed_twin["file"]


def test_the_cell_differs_from_its_twin_by_the_configuration_alone(manifest):
    cell, twin = manifest.cell(CELL), manifest.cell(TWIN_CELL)
    assert cell["traffic"] == twin["traffic"] == "paper-cursor-edit" and cell["chips"] == twin["chips"] == 1
    assert cell["config"] == CONFIG
    assert not os.path.exists(os.path.join(BENCH, "traffic", CELL + ".json"))  # the mix file as it stands, not a copy
    mix = manifest.traffic(cell["traffic"])
    assert room.refusal(manifest.config(CONFIG), mix, 20.0) is None
    # a window adds some 400 entries at the most to a row of 8,192: compaction is bypassed, as the cell's `why` says
    most = clients.load_generator(mix["generator"]).most_entries_added(mix, 12 * 13, 20.0)
    assert most <= 400 and "no compaction" in cell["why"]
    # what the twin's split entries read there, this cell's read here, by the same files
    ours = {m["name"] for m in manifest.metrics_of(CELL, "per_layer")}
    theirs = {m["name"] for m in manifest.metrics_of(TWIN_CELL, "per_layer")}
    assert ours - theirs == {
        "integrate_roofline_rle", "integrate_device_ms_per_batch.rle", "ops_per_flush.rle", "pallas_width_share.rle",
        "slow_path_mid_row_share.rle", "offered_updates_per_s.rle", "gen_late_p95_ms.rle", "rle_entries_per_op",
    }
    assert theirs - ours == {
        "integrate_roofline.paper", "ops_per_flush.paper", "pallas_width_share.paper", "offered_updates_per_s.paper",
        "gen_late_p95_ms.paper", "slow_path_mid_row_share", "integrate_device_ms_per_batch",
    }
    for metric in manifest.data["per_layer"]:
        if metric["name"] in ours - theirs:
            assert metric["workloads"] == [CELL] and metric["moves"] == "update_to_peer_p50_ms"


def test_the_cell_rehearses_correct_and_both_controls_do_not(tmp_path):
    # a traced run removes and rewrites <root>/bench_out/trace: the rehearsal gets a root of its own, with the
    # package reachable from it and the repository's compile cache (as tests/bench/test_room.py does)
    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    os.symlink(os.path.join(ROOT, "hocuspocus_tpu"), tmp_path / "hocuspocus_tpu")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3",
           "JAX_COMPILATION_CACHE_DIR": os.path.join(ROOT, ".jax_cache")}
    rehearsal = subprocess.run(
        [sys.executable, str(tmp_path / "bench/run.py"), "--workload", CELL, "--seed", "2900000017", "--seconds", "2",
         "--trace", "1", "--rehearse", "--control", "drop-last-update", "--control", "wal-drop-last-record"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert rehearsal.returncode == 0, rehearsal.stderr[-2000:]
    result = json.loads(rehearsal.stdout.splitlines()[-1])
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    assert len(result["compared"]) == 9 and all(pair == [0, 0] for pair in result["compared"].values())
    assert "2 plane(s) of 64 x 2048" in rehearsal.stderr and result["metrics"] == {}
    for name in ("drop-last-update", "wal-drop-last-record"):
        control = result["controls"][name]
        assert control["correct"] is False
        assert max(value for value, _limit in control["compared"].values()) == 6  # every driven document differs
    # the rehearsal read the new counter: some entries an op, as a number with the metric's unit and not its name
    numbers = json.loads(rehearsal.stderr.split("rehearsal numbers, not metrics: ")[1].splitlines()[0])
    assert any(n["unit"] == "entries" and 0 < n["value"] <= 2 for n in numbers)


# -- the two readers the cell brings, on hand-made runs ----------------------


def traced_run(**changes) -> dict:
    before = {("integrate_sparse", "16x1"): 40, ("integrate_sparse", "16x4"): 9, ("integrate_sparse", "16x16"): 3, ("health_probe", "16"): 7}
    after = {**before, ("integrate_sparse", "16x1"): 140, ("integrate_sparse", "16x4"): 59, ("integrate_sparse", "16x16"): 13, ("health_probe", "16"): 99}
    run = {
        "trace": {"program_seconds": {"jit_integrate_op_slots_rle_sparse": 0.03, "jit__integrate_sparse_pallas_rle": 0.01, "jit_health_probe_rle": 0.5}},
        "traced_dispatch": (before, after), "doc_units": 104852, "arena": "rle", "row_capacity": 8192, "planes": 13,
        "peaks": {"hbm_bytes_per_s": 819e9},
    }
    return {**run, **changes}


def test_the_run_length_roofline_counts_a_row_s_entries(manifest):
    read = manifest.reader("integrate_roofline_rle")
    # 100 batches of one row, 50 of bucket 4 (2 rows at the least), 10 of bucket 16 (5): 250 rows, read and written whole
    moved = 2 * (100 * 1 + 50 * 2 + 10 * 5) * 8192 * 21
    assert read(traced_run()) == pytest.approx(100 * moved / 819e9 / 0.04)
    assert 0 < read(traced_run()) < 100
    assert read(traced_run(arena="unit", row_capacity=106496)) is None  # lib/roofline.py counts that arena
    assert read(traced_run(trace=None)) is None
    assert read({k: v for k, v in traced_run().items() if k != "arena"}) is None  # a harness that does not say the arena
    nothing_ran = traced_run()
    nothing_ran["traced_dispatch"] = (nothing_ran["traced_dispatch"][1],) * 2
    assert read(nothing_ran) is None  # no number, never 0


def test_entries_an_op_from_the_plane_s_counter(manifest):
    read = manifest.reader("rle_entries_per_op")
    counted = {"flush_fast_ops": 0, "flush_slow_ops": 8000, "rle_entries_appended": 6000}
    assert read({"arena": "rle", "plane_delta": counted}) == pytest.approx(0.75)
    assert read({"arena": "rle", "plane_delta": {**counted, "flush_fast_ops": 2000}}) == pytest.approx(0.6)
    assert read({"arena": "unit", "plane_delta": {**counted, "rle_entries_appended": 0}}) is None
    assert read({"arena": "rle", "plane_delta": {"flush_fast_ops": 0, "flush_slow_ops": 8000}}) is None  # the parent: no counter
    assert read({"arena": "rle", "plane_delta": {**counted, "flush_slow_ops": 0}}) is None  # nothing flushed
    assert read({"plane_delta": counted}) is None
