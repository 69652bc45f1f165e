"""The growth check (bench/lib/room.py): the flags read as the program reads
them, a row's room in the arena's own unit, the generators' bounds on a
document's growth in entries against the run-length kernel itself, what a
later reader finds in `readings`, and the two readers of the collector's
pauses. Runs on the CPU; loads no libtpu."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, os.path.join(BENCH, "lib"))
sys.path.insert(1, ROOT)

import clients  # noqa: E402
import room  # noqa: E402
import seeded  # noqa: E402
from kinds import load as load_kind  # noqa: E402
from manifest import Manifest  # noqa: E402

PAPER = "text-b4-paper-105k"
RLE_FLAGS = ["--tpu-serve", "--tpu-shards", "13", "--tpu-docs", "448", "--tpu-capacity", "8192", "--tpu-arena", "rle"]
RLE_REHEARSAL = ["--tpu-serve", "--tpu-shards", "2", "--tpu-docs", "64", "--tpu-capacity", "2048", "--tpu-arena", "rle"]


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def with_flag(config: dict, name: str, value) -> dict:
    """`config` with the value of one of its flags replaced."""
    flags = list(config["flags"])
    flags[flags.index(name) + 1] = str(value)
    return {**config, "flags": flags}


def as_rle(config: dict, capacity: int) -> dict:
    return {**config, "flags": [*with_flag(config, "--tpu-capacity", capacity)["flags"], "--tpu-arena", "rle"]}


@pytest.mark.parametrize(
    "flags, expected",
    [
        (["--tpu-serve"], (4096, "unit", 1)),  # every flag left out: the CLI's defaults
        (["--tpu-serve", "--tpu-docs", "64", "--tpu-capacity", "512"], (512, "unit", 1)),  # no --tpu-shards
        (["--tpu-serve", "--tpu-capacity", "5", "--tpu-shards", "3", "--tpu-capacity", "7"], (7, "unit", 3)),  # given twice
        (RLE_FLAGS, (8192, "rle", 13)),
        (["--tpu-serve", "--tpu-devices", "4", "--tpu-shards", "1", "--tpu-docs", "32768", "--tpu-capacity", "5632"], (5632, "unit", 4)),
        (["--tpu-serve", "--tpu-devices", "0"], (4096, "unit", 0)),
    ],
)
def test_the_flags_are_read_as_the_program_reads_them(flags, expected):
    assert room.layout(flags) == expected


def test_the_committed_cells_have_today_s_verdict(manifest):
    seconds = float(manifest.data["run_seconds"])
    for cell in manifest.cells.values():
        config, mix = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
        assert room.refusal(config, mix, seconds) is None, cell["name"]
        assert room.layout(config["flags"])[2] == config["arena"]["planes"]  # cells4: four planes, `--tpu-shards 1` written out


def rehearsal(config: dict, mix: dict) -> "tuple[dict, dict]":
    return {**config, **config["rehearse"]}, {**mix, **mix["rehearse"]}


# name: (cell, (configuration, mix) as the case changes them, the refusal or None)
CASES = {
    # the unit arena: capacity - doc_units against most_units_added, in today's words
    "unit: the hottest paper grows by 192 units in 25 s and fits 192":
        ("paper-cursor-edit", lambda c, m: (with_flag(c, "--tpu-capacity", 104852 + 192), m), None),
    "unit: one unit short of room":
        ("paper-cursor-edit", lambda c, m: (with_flag(c, "--tpu-capacity", 104852 + 191), m),
         "a document could grow by 192 units and its row has room for 191"),
    "unit: the rehearsal of typing-append at 20 s, as the parent refuses it":
        ("typing-append", rehearsal, "a document could grow by 351 units and its row has room for 256"),
    "unit: cells4 spreads the rate over 4 x 192 documents":
        ("cells4-typing", lambda c, m: (with_flag(c, "--tpu-capacity", 5120 + 116), m),
         "a document could grow by 117 units and its row has room for 116"),
    "unit: no --tpu-shards is one plane, 24 documents for the whole rate":
        ("typing-append", lambda c, m: ({**c, "flags": ["--tpu-serve", "--tpu-docs", "8192", "--tpu-capacity", "5632"]}, m),
         "a document could grow by 3750 units and its row has room for 512"),
    # the run-length arena: capacity counts entries, the first text is one
    "rle: 8,192 entries a row under a document of 104,852 units":
        ("paper-cursor-edit", lambda c, m: (as_rle(c, 8192), m), None),
    "rle: the first text's entry and two an operation, to the entry":
        ("paper-cursor-edit", lambda c, m: (as_rle(c, 1 + 384), m), None),
    "rle: one entry short of room":
        ("paper-cursor-edit", lambda c, m: (as_rle(c, 384), m),
         "a document could grow by 384 entries and its row has room for 383"),
    "rle: appended runs of typing-append cost two entries an update":
        ("typing-append", lambda c, m: (as_rle(c, 192), m),
         "a document could grow by 192 entries and its row has room for 191"),
    "rle: a generator that cannot count entries is refused by name":
        ("paper-cursor-edit", lambda c, m: (as_rle(c, 8192), {**m, "generator": "unit-only"}),
         "--tpu-arena rle counts a row in entries and the generator 'unit-only' has no "
         "most_entries_added(mix, all_docs, seconds) to bound a document's growth in them"),
    "cells: --tpu-devices 0 states no number of planes":
        ("cells4-typing", lambda c, m: (with_flag(c, "--tpu-devices", 0), m),
         "--tpu-devices 0 leaves the number of cells to the machine: a configuration states it"),
    "a closed loop is not checked, whatever its rows hold":
        ("conflict-midinsert", lambda c, m: (with_flag(c, "--tpu-capacity", 1), m), None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_a_row_s_room_is_counted_in_the_arena_s_own_unit(manifest, monkeypatch, case):
    cell, change, expected = CASES[case]
    cell = manifest.cell(cell)
    config, mix = change(manifest.config(cell["config"]), manifest.traffic(cell["traffic"]))
    unit_only = types.SimpleNamespace(most_units_added=lambda *_: 0)
    load = room.load_generator
    monkeypatch.setattr(room, "load_generator", lambda name: unit_only if name == "unit-only" else load(name))
    assert room.refusal(config, mix, 20.0) == expected


# -- the generators' bounds against the run-length kernel itself ------------


def entries_after(updates: "list[bytes]", capacity: int) -> "tuple[int, bool]":
    """(occupied entries, overflow) of one run-length row of `capacity`
    entries after `updates`, lowered as the plane lowers them and integrated
    one operation a step by the scan kernel (`tpu/kernels_rle.py`)."""
    from hocuspocus_tpu.tpu.kernels import OpBatch
    from hocuspocus_tpu.tpu.kernels_rle import integrate_op_slots_rle, make_empty_rle_state
    from hocuspocus_tpu.tpu.lowering import DocLowerer

    lowerer, ops = DocLowerer(), []
    for update in updates:
        seq_ops, map_ops, tombs = lowerer.lower_update(update)
        assert not lowerer.unsupported and not map_ops and not tombs
        ops += [op for column in seq_ops.values() for op in column]
    fields = {
        "kind": np.int32, "client": np.uint32, "clock": np.int32, "run_len": np.int32,
        "left_client": np.uint32, "left_clock": np.int32, "right_client": np.uint32, "right_clock": np.int32,
    }
    batch = OpBatch(**{name: np.array([[getattr(op, name)] for op in ops], dtype) for name, dtype in fields.items()})
    state, count = integrate_op_slots_rle(make_empty_rle_state(1, capacity), batch)
    assert int(count) == len(ops)
    return int(np.asarray(state.num_runs)[0]), bool(np.asarray(state.overflow)[0])


def hottest_document(generator_name: str, mix: dict, docs: int, seconds: float, seed: int, units: int):
    """The updates of the hottest document of an open loop, made by the
    generator's own `send` on a document wired to no server: (the first
    text's update, the updates, the bound on its growth in entries)."""
    from hocuspocus_tpu.crdt import Doc, apply_update

    generator = clients.load_generator(generator_name)
    writers = getattr(generator, "writers", generator)
    rates = writers.doc_rates(mix, docs, seed)
    hottest = max(range(docs), key=rates.__getitem__)
    events = writers.open_schedule(mix, docs, [hottest], seconds, seed)
    spec = {
        "mix": {**mix, "doc_units": units}, "document": "text", "url": "", "seed": seed, "seconds": seconds, "all_docs": docs,
        "clients_per_doc": 1, "writers_per_doc": 1, "docs": [{"index": hottest, "name": "d"}],
    }
    driven = generator.Generator(spec)
    first = seeded.text_update(seeded.first_client(seed, hottest), seeded.first_texts(seed, 1, units)[0])
    document = Doc()
    document.client_id = 1 << 30 | 5
    provider = types.SimpleNamespace(document=document)
    apply_update(document, first, provider)
    document.on("update", driven._on_update(hottest, 0, provider))
    driven.providers[hottest] = [provider]
    driven.client_ids[hottest] = [document.client_id]
    driven.records[hottest] = [[]]
    driven.pointers[hottest] = [[0]]
    for due, _doc in events:
        driven.send(hottest, 0, due)
    updates = [update for _doc, update, *_rest in driven.log]
    assert len(updates) == len(events) and all(updates)
    return first, updates, generator.most_entries_added(mix, docs, seconds)


@pytest.mark.parametrize(
    "generator, cell, mix_change",
    [
        ("editors", "paper-cursor-edit", {}),  # one unit an update inside the text: inserts and deletes split runs
        ("writers", "typing-append", {}),  # runs appended at the end: an entry each
        # typed-over selections in the middle of the text, as conflict-midinsert has them, on an open loop
        ("writers", "typing-append", {"position_mix": {"uniform": 1}, "replace_share": 0.5, "delete_units": [1, 4], "run_units": [1, 4]}),
    ],
)
@pytest.mark.parametrize("seed", [3, 2_900_000_011])
def test_a_row_of_the_first_text_s_entry_and_the_bound_never_overflows(manifest, generator, cell, mix_change, seed):
    mix = {**manifest.traffic(cell), "rate_updates_per_s": 48, "warmup_seconds": 1, **mix_change}
    first, updates, bound = hottest_document(generator, mix, 6, 3.0, seed, 1536)
    assert 40 <= len(updates) <= bound  # the hottest of six documents: three times the mean rate, for 4 s
    first_entries = load_kind("text").first_in_row({"doc_units": 1536}, "rle")
    entries, overflow = entries_after([first, *updates], first_entries + bound)
    assert not overflow and len(updates) // 2 < entries - first_entries <= bound
    # and the kernel does flag a row that is too short for what the document took
    assert entries_after([first, *updates], entries - 1)[1]


# -- a run-length copy of the long document, as a later PR would add it ------


def test_a_run_length_configuration_is_data_and_rehearses_correct(tmp_path):
    """New files alone, in a root of their own with the package reachable
    from it: at deployment flags the growth check passes and the run stops for
    want of a chip (3, not 2); the rehearsal boots two run-length planes and
    ends `correct`, and a reader a later PR adds finds the arena, a row's
    capacity and the planes in `readings`."""
    shutil.copytree(BENCH, tmp_path / "bench")
    os.symlink(os.path.join(ROOT, "hocuspocus_tpu"), tmp_path / "hocuspocus_tpu")
    data = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = json.load(open(tmp_path / f"bench/configs/{PAPER}.json"))
    config.update(name=PAPER + "-rle", flags=RLE_FLAGS, rehearse={**config["rehearse"], "flags": RLE_REHEARSAL})
    (tmp_path / f"bench/configs/{PAPER}-rle.json").write_text(json.dumps(config))
    (tmp_path / "bench/metrics/later_arena_bytes.py").write_text(
        'SOURCE = "program_counter"\n\n\ndef read(run):\n'
        '    return run["planes"] * run["row_capacity"] * 21 if run["arena"] == "rle" else None\n'
    )
    listed = next(c for c in data["configs"] if c["name"] == PAPER)
    data["configs"].append({**listed, "name": PAPER + "-rle", "file": f"bench/configs/{PAPER}-rle.json"})
    data["workloads"].append(
        {"name": "paper-cursor-edit-rle", "config": PAPER + "-rle", "traffic": "paper-cursor-edit", "chips": 1, "why": "a later PR's"}
    )
    data["per_layer"].append(
        {"name": "later_arena_bytes", "unit": "B/rle", "better": "lower", "source": "program_counter",
         "layer": "kernels", "moves": "update_to_peer_p95_ms", "workloads": ["paper-cursor-edit-rle"]}
    )
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    # the repository's own compile cache: the package's place for it follows the root
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3",
           "JAX_COMPILATION_CACHE_DIR": os.path.join(ROOT, ".jax_cache")}

    def run_bench(*argv: str) -> subprocess.CompletedProcess:
        return subprocess.run(
            [sys.executable, str(tmp_path / "bench/run.py"), "--workload", "paper-cursor-edit-rle", *argv],
            cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
        )

    refused = run_bench("--seed", "1", "--seconds", "20")
    assert refused.returncode == 3 and refused.stdout == "" and "needs 1 TPU chip" in refused.stderr, refused.stderr[-2000:]
    rehearsal = run_bench("--seed", "3000000001", "--seconds", "2", "--trace", "1", "--rehearse")
    assert rehearsal.returncode == 0, rehearsal.stderr[-2000:]
    result = json.loads(rehearsal.stdout.splitlines()[-1])
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    assert len(result["compared"]) == 9 and all(pair == [0, 0] for pair in result["compared"].values())
    assert "2 plane(s) of 64 x 2048" in rehearsal.stderr
    assert json.dumps({"value": 2 * 2048 * 21, "unit": "B/rle"}) in rehearsal.stderr


# -- the collector's pauses: 0 is a reading, nothing is not ------------------


@pytest.mark.parametrize(
    "run, longest_ms, share",
    [
        ({"seconds": 20.0, "gc_pause_s": []}, 0.0, 0.0),  # the collector never ran in the window
        ({"seconds": 20.0}, None, None),  # a run that took no readings of it
        ({"seconds": 20.0, "gc_pause_s": [0.031, 0.014]}, 31.0, 0.225),
    ],
)
def test_the_collector_s_readers(manifest, run, longest_ms, share):
    assert manifest.reader("gc_pause_max_ms")(run) == pytest.approx(longest_ms)
    assert manifest.reader("gc_pause_share")(run) == pytest.approx(share)
