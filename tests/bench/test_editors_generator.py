"""The generator of cursor editing (bench/generators/editors.py), the mix and
the configuration of the cell `paper-cursor-edit`, and the two per-layer
readers the cell adds: the draw of operations from the seed, what the logged
updates say on the wire, when a peer counts a delete as applied, the
manifest's entries, and one rehearsal of the whole cell on the CPU with its
two controls."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, os.path.join(BENCH, "lib"))

import clients  # noqa: E402
import compare  # noqa: E402
import seeded  # noqa: E402
from kinds import load as load_kind  # noqa: E402
from manifest import Manifest  # noqa: E402
from reference import decode_update  # noqa: E402

CELL = "paper-cursor-edit"
UNITS = 1536


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.fixture(scope="module")
def editors():
    return clients.load_generator("editors")


@pytest.fixture(scope="module")
def mix(manifest):
    return {**manifest.traffic(CELL), "doc_units": UNITS}


def drawn(editors, mix, seed: int, doc: int, operations: int, length: int = 104852) -> list:
    """`operations` draws of one document's cursor: (position, letter, text length before)."""
    rng = editors.writers.doc_rng(seed, doc)
    cursor = editors.Cursor(mix, rng, length)
    out = []
    for _ in range(operations):
        at, letter = cursor.draw(rng, length)
        out.append((at, letter, length))
        length += 1 if letter else -1
    return out


def test_the_same_seed_draws_the_same_operations(editors, mix):
    assert drawn(editors, mix, 7, 3, 500) == drawn(editors, mix, 7, 3, 500)
    assert drawn(editors, mix, 7, 3, 500) != drawn(editors, mix, 8, 3, 500)
    assert drawn(editors, mix, 7, 3, 500) != drawn(editors, mix, 7, 4, 500)


def test_delete_share_and_run_length_are_the_mix_s(editors, mix):
    operations = drawn(editors, mix, 2_900_000_011, 0, 20_000)
    deletes = sum(1 for _at, letter, _length in operations if not letter)
    assert deletes / len(operations) == pytest.approx(mix["delete_share"], abs=0.01)
    # a run goes on while each operation starts where the last one left the cursor
    runs, cursor = 1, None
    for at, letter, _length in operations:
        start = at if letter else at + 1  # a delete takes the unit before the cursor
        runs += cursor is not None and start != cursor
        cursor = at + 1 if letter else at
    assert len(operations) / runs == pytest.approx(mix["cursor_run_mean_ops"], abs=2)


def test_every_operation_is_one_unit_inside_the_text(editors, mix):
    first = {drawn(editors, mix, 11, doc, 1, UNITS)[0][0] for doc in range(64)}
    assert len(first) > 32 and min(first) < UNITS // 4 and max(first) > 3 * UNITS // 4  # uniform over the text
    for at, letter, length in drawn(editors, mix, 11, 5, 5_000, UNITS):
        assert (0 <= at <= length and len(letter) == 1) if letter else 0 <= at < length
    # a cursor at the head of the text inserts where the mix would have deleted
    all_deletes = {**mix, "delete_share": 1.0, "cursor_run_mean_ops": 1e9}
    rng = editors.writers.doc_rng(1, 1)
    cursor = editors.Cursor(all_deletes, rng, 4)
    cursor.at = 1
    assert cursor.draw(rng, 4) == (0, "")
    at, letter = cursor.draw(rng, 3)
    assert at == 0 and len(letter) == 1


def test_most_units_added_bounds_what_a_run_adds(editors, mix):
    docs, seconds = 156, 20.0
    bound = editors.most_units_added(mix, docs, seconds)
    rates = editors.writers.doc_rates(mix, docs, 0)
    assert bound == round(max(rates) * (seconds + mix["warmup_seconds"]))
    for seed in (1, 2_900_000_011):
        events = editors.writers.open_schedule(mix, docs, list(range(docs)), seconds, seed)
        per_doc = [sum(1 for _due, doc in events if doc == d) for d in range(docs)]
        assert max(per_doc) <= bound  # had every operation of the hottest document been an insert
    assert bound <= 1644  # the room a row of 106,496 units leaves a document of 104,852


class Wired:
    """One document of a Generator with its writer and its reader, wired as
    `connect` wires them but to no server: an update reaches the reader when
    the test hands it over."""

    def __init__(self, editors, mix, seed: int = 5) -> None:
        from hocuspocus_tpu.crdt import Doc, apply_update

        self.apply_update = apply_update
        spec = {
            "mix": mix, "document": "text", "url": "", "seed": seed, "seconds": 1.0, "all_docs": 1,
            "clients_per_doc": 2, "writers_per_doc": 1, "docs": [{"index": 0, "name": "d"}],
        }
        self.generator = generator = editors.Generator(spec)
        self.first = (seeded.first_client(seed, 0), seeded.first_texts(seed, 1, UNITS)[0])
        row = []
        for client, cid in enumerate((1 << 30 | 5, 1 << 31 | 1 << 30 | 6)):
            document = Doc()
            document.client_id = cid
            provider = types.SimpleNamespace(document=document)
            apply_update(document, seeded.text_update(*self.first), provider)
            document.on("update", generator._on_update(0, client, provider))
            row.append(provider)
        generator.providers[0] = row
        generator.client_ids[0] = [p.document.client_id for p in row]
        generator.records[0] = [[]]
        generator.pointers[0] = [[0], [0]]
        generator.window_start, generator.window_end = 0.0, float("inf")

    def deliver(self, update: bytes) -> None:
        reader = self.generator.providers[0][1]
        self.apply_update(reader.document, update, reader)


def test_logged_updates_say_one_unit_by_their_own_client(editors, mix):
    wired = Wired(editors, mix)
    generator = wired.generator
    for nth in range(400):
        generator.send(0, 0, float(nth))
    log = generator.log
    assert len(log) == 400 and load_kind("text").not_as_meant(log) == 0
    writer = generator.client_ids[0][0]
    kinds = set()
    for _doc, update, client, run, cut in log:
        inserts, deletes = decode_update(update)
        assert client == writer and (len(run), cut) in ((1, 0), (0, 1))
        assert [text for *_ids, text in inserts] == ([run] if run else [])
        assert sum(length for *_id, length in deletes) == cut
        kinds.add("insert" if run else "delete")
        for _author, _clock, left, right, _text in inserts:
            kinds.add("mid" if right is not None else "tail")
    assert {"insert", "delete", "mid"} <= kinds
    # the reference merges the first text and the log to the writer's own text
    reference = compare.merged([wired.first], log)[0]
    assert reference.text() == generator.providers[0][0].document.get_text("body").to_string()
    assert len(reference.text()) == UNITS + sum(1 if run else -1 for *_head, run, _cut in log)


def test_a_delete_counts_as_applied_when_the_peer_holds_its_tombstone(editors, mix):
    """A delete moves no clock: the peer's state vector has reached it before
    it arrives. The record is done only when the peer's tombstones are."""
    wired = Wired(editors, {**mix, "cursor_run_mean_ops": 1e9})
    generator = wired.generator
    kinds = []
    while kinds[-2:] != ["insert", "delete"]:  # until a delete follows an insert
        generator.send(0, 0, 0.0)
        kinds.append("insert" if generator.log[-1][3] else "delete")
    records = generator.records[0][0]
    assert generator.outstanding == len(records) and all(r.done is None for r in records)
    for nth, (_doc, update, *_rest) in enumerate(generator.log[:-1]):
        wired.deliver(update)
        assert records[nth].done is not None
    assert records[-1].done is None and generator.outstanding == 1  # its clock is reached, its tombstone is not there
    wired.deliver(generator.log[-1][1])
    assert records[-1].done is not None and generator.outstanding == 0


def test_editors_take_one_writer_a_document(editors, mix):
    spec = {"mix": mix, "document": "text", "url": "", "seed": 1, "seconds": 1.0, "all_docs": 1, "clients_per_doc": 3,
            "writers_per_doc": 2, "docs": []}
    with pytest.raises(ValueError, match="one writer a document"):
        editors.Generator(spec)


def test_the_cell_its_configuration_and_its_metrics_as_the_manifest_has_them(manifest):
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("text-b4-paper-105k", CELL, 1)
    config = manifest.config(cell["config"])
    listed = manifest.configs[cell["config"]]
    assert config["source"] == listed["source"] and len(listed["source"]) <= 200 and "B4" in listed["source"]
    assert config["flags"] == ["--tpu-serve", "--tpu-shards", "13", "--tpu-docs", "448", "--tpu-capacity", "106496"]
    arena = config["arena"]
    assert arena["bytes_reserved"] == 13 * 448 * 106496 * 17 == 10_543_955_968
    assert config["doc_units"] == 104852 and arena["room_units_per_row"] == 106496 - 104852
    assert (config["clients_per_doc"], config["writers_per_doc"]) == (2, 1)
    assert (config["resident_docs_per_plane"], config["driven_docs_per_plane"]) == (48, 12)
    assert config["guarantees"] == manifest.config("text-100k-10kb")["guarantees"]
    mix = manifest.traffic(CELL)
    assert (mix["generator"], mix["loop"], mix["run_units"], mix["delete_units"]) == ("editors", "open", [1, 1], [1, 1])
    assert mix["delete_share"] == 0.298 and mix["position_mix"] == {"cursor": 1} and mix["replace_share"] == 0
    assert mix["rate_updates_per_s"] % 10 == 0
    assumed = {key for key, why in mix["sources"].items() if why.startswith("assumed")}
    assert {"cursor_run_mean_ops", "cursor_jump", "cursor_local_span_units"} <= assumed
    assert set(mix) - {"who", "sources", "rehearse", "loop", "client_processes", "warmup_seconds"} <= set(mix["sources"])
    reported = {m["name"]: m for m in manifest.metrics_of(CELL, "per_layer")}
    split = ["integrate_roofline.paper", "ops_per_flush.paper", "pallas_width_share.paper",
             "offered_updates_per_s.paper", "gen_late_p95_ms.paper"]
    for name in split:  # read by the file of the name before the dot, in the layer of the accepted entry
        accepted = next(m for m in manifest.data["per_layer"] if m["name"] == name.split(".")[0])
        assert reported[name]["layer"] == accepted["layer"] and reported[name]["source"] == accepted["source"]
        assert not os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    assert reported["slow_path_mid_row_share"]["layer"] == "merge plane batching"
    assert reported["integrate_device_ms_per_batch"]["layer"] == "kernels"
    for name in split + ["slow_path_mid_row_share", "integrate_device_ms_per_batch"]:
        assert reported[name]["workloads"] == [CELL] and reported[name]["moves"] == "update_to_peer_p50_ms"
    for other in ("typing-append", "conflict-midinsert", "cells4-typing"):  # the accepted cells report none of them
        assert not set(split) & {m["name"] for m in manifest.metrics_of(other, "per_layer")}
        assert "slow_path_mid_row_share" not in {m["name"] for m in manifest.metrics_of(other, "per_layer")}
    # judged on the median: the host's stalls leave its tail unbounded (PERF.md section 2); the tail is per layer
    assert {m["name"] for m in manifest.metrics_of(CELL, "end_to_end")} == {"update_to_peer_p50_ms", "setup_s"}
    assert "update_to_peer_p95_ms.b4" in reported
    for quantity in ("fast_path_share", "device_idle_share", "loop_apply_share", "executor_flush_share"):
        assert manifest.reported_as(CELL, quantity)["name"] == quantity + ".b4"  # the twin that moves the median


def test_the_mid_row_share_reads_its_counter_and_none_without_it(manifest):
    read = manifest.reader("slow_path_mid_row_share")
    counted = {"flush_fast_ops": 10, "flush_slow_ops": 190, "slow_ops_mid_row": 140, "slow_ops_delete": 50}
    assert read({"plane_delta": counted}) == pytest.approx(70.0)
    assert read({"plane_delta": {**counted, "slow_ops_mid_row": 0}}) == 0.0  # none was mid-row: a reading
    assert read({"plane_delta": {"flush_fast_ops": 10, "flush_slow_ops": 190}}) is None  # the parent commit
    assert read({"plane_delta": {"flush_fast_ops": 0, "flush_slow_ops": 0, "slow_ops_mid_row": 0}}) is None


def test_device_milliseconds_per_integrate_batch(manifest):
    read = manifest.reader("integrate_device_ms_per_batch")
    before = {("integrate_sparse", "16x1"): 100, ("integrate_sparse", "16x4"): 10, ("append_sparse", "16x1"): 7}
    after = {("integrate_sparse", "16x1"): 160, ("integrate_sparse", "16x4"): 30, ("integrate_dense", "1x448"): 20,
             ("append_sparse", "16x1"): 50}
    seconds = {"jit_integrate_op_slots_sparse": 0.15, "jit__integrate_sparse_pallas": 0.05, "jit_append_run_slots_sparse": 9.0}
    run = {"trace": {"program_seconds": seconds}, "traced_dispatch": (before, after)}
    assert read(run) == pytest.approx(1000 * 0.2 / 100)  # 60 + 20 + 20 batches
    assert read({"trace": None}) is None and read({}) is None
    assert read({"trace": {"program_seconds": {"jit_append_run_slots_sparse": 1.0}}, "traced_dispatch": (before, after)}) is None
    assert read({"trace": {"program_seconds": seconds}, "traced_dispatch": (after, after)}) is None


def test_a_rehearsal_of_the_cell_ends_correct_and_its_controls_do_not():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3"}
    rehearsal = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "3000000029", "--seconds", "2",
         "--trace", "0", "--rehearse", "--control", "drop-last-update", "--control", "wal-drop-last-record"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert rehearsal.returncode == 0, rehearsal.stderr[-2000:]
    assert "READY: 2 plane(s) of 64 x 2048" in rehearsal.stderr
    result = json.loads(rehearsal.stdout.splitlines()[-1])
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"] == {}
    assert len(result["compared"]) == 9 and all(value == 0 for value, _limit in result["compared"].values())
    for control in ("drop-last-update", "wal-drop-last-record"):
        assert result["controls"][control]["correct"] is False
