"""The benchmark's own tests: the manifest, the data-driven lookup, one
rehearsal of a whole run, the trace reduction, the roofline's byte count, and
the controls and planted faults that `correct` has to fail.

Everything here runs on the CPU; nothing loads libtpu at import.
"""

from __future__ import annotations

import importlib.util
import json
import os
import random
import shutil
import struct
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, os.path.join(BENCH, "lib"))

import clients  # noqa: E402
import compare  # noqa: E402
import roofline  # noqa: E402
import seeded  # noqa: E402
import tracereduce  # noqa: E402
from kinds import load as load_kind  # noqa: E402
from manifest import Manifest, ManifestError  # noqa: E402

TEXT = load_kind("text")

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]
# the lines of standard error that the harness writes, its timed log among them
HARNESS_LINES = ("bench:", "compared:", "correct:", "[bench +")


def run_bench(*argv: str, timeout: int = 300) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3"}
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *argv],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_manifest_names_files_and_what_each_cell_reports():
    manifest = Manifest()
    manifest.check_names()
    for config in manifest.configs.values():
        assert set(manifest.config(config["name"])["reduced"]) == set(config["reduced"])
    for name, cell in manifest.cells.items():
        mix = manifest.traffic(cell["traffic"])
        assert mix["loop"] in ("open", "closed")
        assert callable(clients.load_generator(mix["generator"]).Generator)
        end_to_end = {m["name"] for m in manifest.metrics_of(name, "end_to_end")}
        assert "setup_s" in end_to_end and len(end_to_end) >= 2
        per_layer = manifest.metrics_of(name, "per_layer")
        assert per_layer
        for metric in per_layer:
            assert metric["moves"] in end_to_end, (name, metric["name"])
            assert callable(manifest.reader(metric["name"]))


MEDIAN_CELLS = [
    name for name in Manifest().cells
    if "update_to_peer_p95_ms" not in {m["name"] for m in Manifest().metrics_of(name, "end_to_end")}
]


def test_a_metric_without_a_list_goes_to_the_cells_that_report_what_it_moves():
    manifest = Manifest()
    assert MEDIAN_CELLS  # the host's stalls leave these cells' tail unbounded (PERF.md section 2)
    universal = [m for m in manifest.data["per_layer"] if "workloads" not in m]
    for name in manifest.cells:
        end_to_end = {m["name"] for m in manifest.metrics_of(name, "end_to_end")}
        reported = {m["name"] for m in manifest.metrics_of(name, "per_layer")}
        for metric in universal:
            assert (metric["name"] in reported) == (metric["moves"] in end_to_end), (name, metric["name"])


@pytest.mark.parametrize("cell", MEDIAN_CELLS)
def test_a_cell_that_reports_the_median_keeps_every_quantity_and_its_tail(cell):
    manifest = Manifest()
    assert {m["name"] for m in manifest.metrics_of(cell, "end_to_end")} == {"update_to_peer_p50_ms", "setup_s"}
    per_layer = manifest.metrics_of(cell, "per_layer")
    assert all(m["moves"] == "update_to_peer_p50_ms" and cell in m["workloads"] for m in per_layer)
    # every quantity the other open-loop cells read, under a twin that moves the median
    for metric in manifest.data["per_layer"]:
        if "workloads" not in metric:
            twin = manifest.reported_as(cell, metric["name"])
            assert {k: v for k, v in twin.items() if k not in ("name", "moves", "workloads")} == {
                k: v for k, v in metric.items() if k not in ("name", "moves")
            }
    tail = manifest.reported_as(cell, "update_to_peer_p95_ms")
    assert tail["source"] == "host_clock" and tail["unit"] == "ms" and tail["better"] == "lower"
    latency = [0.001 * (i % 100) for i in range(1000)]  # 0..99 ms, ten of each
    assert manifest.reader(tail["name"])({"latency_s": latency}) == pytest.approx(94.0)
    assert manifest.reader(tail["name"])({"latency_s": []}) is None


def test_a_cell_a_configuration_and_a_metric_are_added_as_new_files_only(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench")
    data = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    before = {
        path: open(path, "rb").read()
        for folder, _dirs, files in os.walk(tmp_path / "bench")
        for path in (os.path.join(folder, f) for f in files)
    }
    config = json.load(open(tmp_path / "bench/configs/text-100k-10kb.json"))
    config.update(name="text-later", driven_docs_per_plane=2, document="later")
    (tmp_path / "bench/configs/text-later.json").write_text(json.dumps(config))
    (tmp_path / "bench/documents/later.py").write_text('VIEWS = "trees"\n')
    mix = json.load(open(tmp_path / "bench/traffic/typing-append.json"))
    mix["rate_updates_per_s"] = 7
    (tmp_path / "bench/traffic/typing-later.json").write_text(json.dumps(mix))
    mix["generator"] = "joiners"
    (tmp_path / "bench/traffic/typing-later.json").write_text(json.dumps(mix))
    (tmp_path / "bench/generators/joiners.py").write_text("class Generator:\n    kind = 'a later generator'\n")
    (tmp_path / "bench/metrics/later_ops.py").write_text(
        'SOURCE = "program_counter"\n\n\ndef read(run):\n    return run["plane_delta"]["flush_fast_ops"] * 2\n'
    )
    (tmp_path / "bench/metrics/later_span_ms.py").write_text(
        'SOURCE = "program_span"\n\n\ndef read(run):\n'
        '    return run["trace"]["span_seconds"].get("merge_plane.flush", 0) * 1000 or None\n'
    )
    data["configs"].append({**data["configs"][0], "name": "text-later", "file": "bench/configs/text-later.json"})
    data["workloads"].append(
        {"name": "later", "config": "text-later", "traffic": "typing-later", "chips": 1, "why": "a later PR's"}
    )
    data["per_layer"].append(
        {"name": "later_ops", "unit": "ops", "better": "higher", "source": "program_counter",
         "layer": "merge plane batching", "moves": "update_to_peer_p95_ms", "workloads": ["later"]}
    )
    data["per_layer"].append(
        {"name": "later_span_ms", "unit": "ms", "better": "lower", "source": "program_span",
         "layer": "merge plane batching", "moves": "update_to_peer_p95_ms", "workloads": ["later"]}
    )
    data["per_layer"].append(
        {"name": "later_ops.typing", "unit": "ops", "better": "higher", "source": "program_counter",
         "layer": "merge plane batching", "moves": "update_to_peer_p95_ms", "workloads": ["later"]}
    )
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    manifest = Manifest(str(tmp_path))
    manifest.check_names()
    cell = manifest.cell("later")
    assert manifest.config(cell["config"])["driven_docs_per_plane"] == 2
    assert manifest.kind(manifest.config(cell["config"])).VIEWS == "trees"
    assert manifest.kind(manifest.config("text-100k-10kb")).VIEWS == "texts"  # no "document": the kind text
    assert manifest.traffic(cell["traffic"])["rate_updates_per_s"] == 7
    generators = importlib.util.spec_from_file_location("later_clients", tmp_path / "bench/lib/clients.py")
    later_clients = importlib.util.module_from_spec(generators)
    generators.loader.exec_module(later_clients)
    assert later_clients.load_generator("joiners").Generator.kind == "a later generator"
    assert "later_ops" in {m["name"] for m in manifest.metrics_of("later", "per_layer")}
    assert "later_ops" not in {m["name"] for m in manifest.metrics_of("typing-append", "per_layer")}
    assert manifest.reader("later_ops")({"plane_delta": {"flush_fast_ops": 21}}) == 42
    assert manifest.reader("later_ops.typing")({"plane_delta": {"flush_fast_ops": 4}}) == 8  # the file before the dot
    ms = 1_000_000
    trace = tracereduce.reduce(
        [("/host:CPU", [("t", [("merge_plane.flush", 0, 3 * ms), ("merge_plane.flush", 5 * ms, 4 * ms)])]),
         ("/device:TPU:0", [("XLA Ops", [("fusion", ms, ms)])])], 0.01)
    assert manifest.reader("later_span_ms")({"trace": trace}) == pytest.approx(7.0)
    assert manifest.reader("later_span_ms")({"trace": {"span_seconds": {}}}) is None
    # a configuration that names a kind with no file is refused by name
    (tmp_path / "bench/configs/text-sooner.json").write_text(json.dumps({**config, "document": "sooner"}))
    data["configs"].append({**data["configs"][0], "name": "text-sooner", "file": "bench/configs/text-sooner.json"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    with pytest.raises(ManifestError, match="'text-sooner' names the document kind 'sooner', which has no file"):
        Manifest(str(tmp_path)).check_names()
    assert all(open(path, "rb").read() == content for path, content in before.items())


# The tests of tests/bench that hold every cell of BENCHMARK.json, and how many
# cases they pass on this tree (five cells, four open, three judged on the median).
CELL_WIDE_TESTS = [
    "test_bench_harness.py::test_manifest_names_files_and_what_each_cell_reports",
    "test_bench_harness.py::test_a_metric_without_a_list_goes_to_the_cells_that_report_what_it_moves",
    "test_bench_harness.py::test_a_cell_that_reports_the_median_keeps_every_quantity_and_its_tail",
    "test_wal_early_turn.py::test_every_cell_reports_it_under_the_log_layer",
    "test_ticks_released.py::test_every_cell_reports_it_under_the_log_layer",
    "test_loop_stage_readers.py::test_every_cell_reports_them",
    "test_socket_write_metrics.py::test_every_cell_reports_them_under_the_loop_layer",
    "test_span_readers.py::test_every_new_metric_is_reported_by_both_cells_and_moves_what_both_report",
    "test_open_schedule.py::test_every_seed_offers_the_same_due_times",
    "test_open_schedule.py::test_each_plane_is_loaded_alike",
    "test_room.py::test_the_committed_cells_have_today_s_verdict",
]
CELL_WIDE_CASES = 23


def test_a_later_cell_with_its_own_kind_generator_and_mix_passes_every_cell_wide_test(tmp_path):
    """A tail cell added as a later PR adds one: a kind, a generator, a
    configuration and a mix of its own as new files, its entries, and its name
    appended to the lists it joins. The cell-wide tests of the copy pass, and
    no file that was there changed but BENCHMARK.json."""
    tests = os.path.join(ROOT, "tests", "bench")
    unbuilt = shutil.ignore_patterns("__pycache__", "*.pyc")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=unbuilt)
    shutil.copytree(tests, tmp_path / "tests/bench", ignore=unbuilt)
    for linked in ("hocuspocus_tpu", "tests/conftest.py", "pyproject.toml"):
        os.symlink(os.path.join(ROOT, linked), tmp_path / linked)
    added = {
        "bench/documents/later.py": (
            '"""A later kind: the kind `text` under another name."""\n\n'
            "from kinds import load  # bench/lib\n\n"
            'globals().update({k: v for k, v in vars(load("text")).items() if not k.startswith("__")})\n'
        ),
        "bench/generators/later.py": (
            '"""A later generator: `writers`\' own under another name."""\n\n'
            "from clients import load_generator  # bench/lib\n\n"
            'writers = load_generator("writers")\n'
            "Generator = writers.Generator\n"
            "most_units_added = writers.most_units_added\n"
            "open_schedule = writers.open_schedule\n"
            "doc_rates = writers.doc_rates\n"
        ),
        "bench/configs/tree-later.json": json.dumps(
            {**json.load(open(os.path.join(BENCH, "configs/text-b4-paper-105k.json"))), "name": "tree-later",
             "document": "later"}
        ),
        "bench/traffic/later-burst.json": json.dumps(
            {**json.load(open(os.path.join(BENCH, "traffic/paper-cursor-edit.json"))), "generator": "later"}
        ),
    }
    for path, content in added.items():
        (tmp_path / path).write_text(content)
    data = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = next(c for c in data["configs"] if c["name"] == "text-b4-paper-105k")
    data["configs"].append({**listed, "name": "tree-later", "file": "bench/configs/tree-later.json"})
    data["workloads"].append(
        {"name": "later-tail", "config": "tree-later", "traffic": "later-burst", "chips": 1, "why": "a later PR's"}
    )
    data["per_layer"].append(
        {"name": "ops_per_flush.later", "unit": "ops", "better": "higher", "source": "program_counter",
         "layer": "merge plane batching", "moves": "update_to_peer_p95_ms", "workloads": ["later-tail"]}
    )
    for metric in data["end_to_end"] + data["per_layer"]:
        if metric["name"] in ("update_to_peer_p95_ms", "wal_early_turn_share.open"):
            metric["workloads"].append("later-tail")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3", "PYTHONDONTWRITEBYTECODE": "1",
           "JAX_COMPILATION_CACHE_DIR": os.path.join(ROOT, ".jax_cache")}
    ran = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider", "-p", "no:randomly",
         *("tests/bench/" + test for test in CELL_WIDE_TESTS)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert ran.returncode == 0, ran.stdout[-3000:] + ran.stderr[-1000:]
    passed = [line[len("PASSED ") :] for line in ran.stdout.splitlines() if line.startswith("PASSED ")]
    assert len(passed) >= CELL_WIDE_CASES, passed
    assert {test for test in passed if "later-tail" in test} == {
        "tests/bench/test_open_schedule.py::test_every_seed_offers_the_same_due_times[later-tail]",
        "tests/bench/test_open_schedule.py::test_each_plane_is_loaded_alike[later-tail]",
    }
    # every file of the copy is the original's, but BENCHMARK.json and the added ones
    copied = {
        os.path.relpath(os.path.join(folder, file), tmp_path)
        for top in ("bench", "tests/bench")
        for folder, _dirs, files in os.walk(tmp_path / top)
        for file in files
    }
    assert copied - set(added) == {
        os.path.relpath(os.path.join(folder, file), ROOT)
        for top in (BENCH, tests)
        for folder, _dirs, files in os.walk(top)
        if "__pycache__" not in folder
        for file in files
        if not file.endswith(".pyc")
    }
    for path in copied - set(added):
        assert open(tmp_path / path, "rb").read() == open(os.path.join(ROOT, path), "rb").read(), path


def test_no_chip_is_a_failed_run_and_a_rehearsal_names_no_metric():
    refused = run_bench("--workload", "typing-append", "--seed", "1", "--seconds", "2", "--trace", "0")
    assert refused.returncode == 3 and refused.stdout == ""
    rehearsal = run_bench(
        "--workload", "typing-append", "--seed", "3000000001", "--seconds", "2", "--trace", "0", "--rehearse"
    )
    assert rehearsal.returncode == 0, rehearsal.stderr[-2000:]
    result = json.loads(rehearsal.stdout.splitlines()[-1])
    assert list(result) == RESULT_KEYS
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    # the harness's own words: the server's log may name a signal of its overload
    # ladder that is also a metric's name (`wal_commit_ms`, a slow group commit)
    harness = [line for line in rehearsal.stderr.splitlines() if line.startswith(HARNESS_LINES)]
    assert any("rehearsal numbers, not metrics: " in line for line in harness)
    for metric in Manifest().data["end_to_end"] + Manifest().data["per_layer"]:
        assert metric["name"] not in rehearsal.stdout + "\n".join(harness)
    assert all(value <= limit for value, limit in result["compared"].values())
    assert "compared: device_texts_differing = 0 (limit 0)" in rehearsal.stderr
    assert list(result["compared"]) == list(compare.limits(TEXT))
    # every end-to-end metric of the cell is read, under no name
    (numbers,) = [line for line in rehearsal.stderr.splitlines() if "rehearsal numbers, not metrics: " in line]
    read = json.loads(numbers.split("rehearsal numbers, not metrics: ", 1)[1])
    assert len(read) == len(Manifest().metrics_of("typing-append", "end_to_end"))


# SHA-256 of the first updates and of the log that `Served.write_log` wrote
# for the rehearsal sizes, before the harness read them through a kind
TEXT_DIGESTS = {
    ("text-1k-10clients", 0): (
        "5acffa3aca6e715c6f18cab5ec6ab30a34b7393377c42cfdcce1481a3cd37a41",
        "0341c039d278b15897d9e588a014ebdc11c5521bd307e3000d95851e0ae67469",
    ),
    ("text-1k-10clients", 1): (
        "2fe9c0d170b1ae709fcfa8b3112239c59ee1590bd5e796f1b5588d65051d3eeb",
        "2d536db90a5ed5bebc9bd13c260791511aa73be846783cda09c50e30145711d8",
    ),
    ("text-b4-paper-105k", 0): (
        "5142cd91e94a05e005ea03ebdfe9c6af116f73d4dbd7f98311dfa53c10a07fa5",
        "de0b155dcbcc0c8c184df41f59450a890847f6e1cb7e402db6a0ebe5a08f65bd",
    ),
    ("text-b4-paper-105k", 1): (
        "cb7d62fd9cd8d07cfbd301348d5a0333f68207ef983be89319c948251c7a80e8",
        "b991f607de2c09fbbc56c211d24a12ea5f5192c74b7cd63f84d2513c655e9b70",
    ),
}


@pytest.mark.parametrize("config_name, seed", list(TEXT_DIGESTS))
def test_the_text_kind_writes_the_same_first_updates_and_log(config_name, seed, tmp_path):
    import asyncio
    import hashlib
    import types

    from serve import Served

    config = Manifest().config(config_name)
    config = {**config, **config["rehearse"]}
    flags = config["flags"]
    docs = int(flags[flags.index("--tpu-shards") + 1]) * int(config["resident_docs_per_plane"])
    firsts = TEXT.first_states(seed, docs, config)
    writes = [update for first in firsts for _client, update in TEXT.first_writes(first)]
    updates = hashlib.sha256(b"".join(len(u).to_bytes(4, "little") + u for u in writes))
    names = [f"bench-{seed}-{i}" for i in range(docs)]
    asyncio.run(Served.write_log(types.SimpleNamespace(wal_dir=str(tmp_path)), names, firsts, TEXT))
    log = hashlib.sha256()
    for folder, folders, files in sorted(os.walk(tmp_path)):
        folders.sort()
        for file in sorted(files):
            path = os.path.join(folder, file)
            log.update(os.path.relpath(path, tmp_path).encode() + b"\0" + open(path, "rb").read())
    assert (updates.hexdigest(), log.hexdigest()) == TEXT_DIGESTS[(config_name, seed)]
    assert [TEXT.first_view(f) for f in firsts] == seeded.first_texts(seed, docs, int(config["doc_units"]))


def test_the_device_update_is_the_plane_s_joiner_serve(capsys):
    """`Served.device_update` on a small server booted on the CPU: a tree
    document (three paragraphs, one with a bold mark and an attribute), made
    by the program's CPU crdt, and a text document of the kind `text`, both
    recovered from the log. The plane cannot materialise the tree, and the
    update it serves a joiner gives the server's fragment; on the text the
    update, read by the kind's reference, agrees with `plane.text`."""
    import asyncio
    import types

    from hocuspocus_tpu.crdt import Doc, YXmlElement, YXmlText, apply_update, encode_state_as_update
    from serve import Served

    tree = Doc()
    tree.client_id = 7
    fragment = tree.get_xml_fragment("prosemirror")
    fragment.insert(0, [YXmlElement("paragraph") for _ in range(3)])
    for paragraph, words in zip(fragment.to_array(), ("one", "two bold", "three")):
        paragraph.insert(0, [YXmlText()])
        paragraph.get(0).insert(0, words)
    fragment.get(1).get(0).format(4, 4, {"bold": True})
    fragment.get(1).set_attribute("textAlign", "center")
    text = TEXT.first_states(5, 1, {"doc_units": 64})[0]

    async def read() -> dict:
        served = Served(["--tpu-serve", "--tpu-docs", "8", "--tpu-capacity", "256"])
        try:
            await served.boot()
            # the tree's first state is its one update, written by client 7
            tree_kind = types.SimpleNamespace(first_writes=list)
            await served.write_log(["tree"], [[(7, encode_state_as_update(tree))]], tree_kind)
            await served.write_log(["text"], [text], TEXT)
            await served.recover(["tree", "text"])
            return {
                "server tree": served.server.hocuspocus.documents["tree"].get_xml_fragment("prosemirror").to_string(),
                "plane tree": await served.on_plane("tree", lambda plane: plane.text("tree")),
                "tree": await served.device_update("tree"),
                "text": await served.device_update("text"),
                "device text": await TEXT.device_view(served, "text"),
                "none": await served.device_update("never-loaded"),
            }
        finally:
            await served.close()

    got = asyncio.run(read())
    capsys.readouterr()
    assert got["server tree"] == fragment.to_string()
    assert '<paragraph textAlign="center">two <bold>bold</bold></paragraph>' in got["server tree"]
    assert got["plane tree"] is None and got["none"] is None  # byte-served only
    joiner = Doc()
    apply_update(joiner, got["tree"])
    assert joiner.get_xml_fragment("prosemirror").to_string() == got["server tree"]
    assert got["device text"] == TEXT.first_view(text)
    reference = TEXT.Reference()
    reference.apply_updates([got["text"]])
    assert TEXT.reference_view(reference) == got["device text"]


def test_trace_reduction_gives_known_busy_and_idle():
    ms = 1_000_000
    planes = [
        ("/host:CPU", [("loop", [("bench.loop_asleep", 0, 10 * ms), ("bench.loop_asleep", 40 * ms, 45 * ms), ("other", 0, 100 * ms)])]),
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_append_run_slots_sparse(123)", 10 * ms, 20 * ms), ("jit__integrate_sparse_pallas(9)", 90 * ms, 10 * ms)]),
            # two ops overlap: the union counts the overlap once
            ("XLA Ops", [("fusion.1", 10 * ms, 15 * ms), ("copy.2", 20 * ms, 10 * ms), ("custom-call.3", 90 * ms, 10 * ms)]),
        ]),
    ]
    reduced = tracereduce.reduce(planes, 0.1)
    assert reduced["busy_s"] == pytest.approx(0.030)
    assert reduced["window_s"] == 0.1
    assert reduced["device_ops"][0] == ["jit_append_run_slots_sparse", pytest.approx(0.020)]
    assert reduced["program_seconds"]["jit__integrate_sparse_pallas"] == pytest.approx(0.010)
    assert reduced["span_seconds"] == {"bench.loop_asleep": pytest.approx(0.055), "other": pytest.approx(0.1)}
    gaps = dict(reduced["idle_gaps"])
    # 0-10 ms idle, the loop asleep all through; 30-90 ms idle, asleep for 45 of the 60
    assert gaps["bench.loop_asleep: idle time"] == pytest.approx(0.055)
    assert gaps["bench.loop_asleep: longest gap"] == pytest.approx(0.060)
    assert gaps[tracereduce.HOST_BUSY + ": idle time"] == pytest.approx(0.015)
    busy_host = [("/host:CPU", [("loop", [("other", 0, 100 * ms)])]), planes[1]]
    assert dict(tracereduce.reduce(busy_host, 0.1)["idle_gaps"]) == {
        tracereduce.HOST_BUSY + ": idle time": pytest.approx(0.070),
        tracereduce.HOST_BUSY + ": longest gap": pytest.approx(0.060),
    }
    with pytest.raises(ValueError):
        tracereduce.reduce(planes[:1], 0.1)


def test_roofline_bytes_are_a_pure_function_of_counts_and_shapes():
    before = {("integrate_sparse", "8x16"): 5, ("integrate_sparse", "8x1"): 1, ("integrate_sparse", "8x4"): 1, ("append_sparse", "8x64"): 1}
    after = {**before, ("integrate_sparse", "8x16"): 15, ("integrate_sparse", "8x64"): 2, ("append_sparse", "8x64"): 4}
    shapes = roofline.dispatches_of(before, after, "integrate_sparse")
    assert shapes == {"8x16": 10, "8x64": 2}
    buckets = roofline.buckets_of(after, "integrate_sparse")
    assert buckets == [1, 4, 16, 64]
    # a batch of bucket 16 has 5 busy rows at the least, one of 64 has 17, one of 1 has 1
    assert [roofline.rows_at_least(b, buckets) for b in buckets] == [1, 2, 5, 17]
    assert roofline.batch_bytes(shapes, 5120, buckets) == 2 * (10 * 5 + 2 * 17) * 5120 * 17
    run = {
        "trace": {"program_seconds": {"jit__integrate_sparse_pallas": 0.002, "jit_append_run_slots_sparse": 1.0}},
        "traced_dispatch": (before, after), "doc_units": 5120, "peaks": {"hbm_bytes_per_s": 819e9},
    }
    assert roofline.share(run, "integrate_sparse", "integrate") == pytest.approx(
        100 * 2 * 84 * 5120 * 17 / 819e9 / 0.002
    )
    assert roofline.share({**run, "trace": None}, "integrate_sparse", "integrate") is None
    assert roofline.share(run, "integrate_dense", "integrate") is None  # nothing ran: no number, never 0


def _conflict_log(seed: int, docs: int = 3, clients: int = 4, steps: int = 120):
    """Clients of the program's own CRDT editing concurrently on top of a
    first text made by the benchmark, with updates exchanged late and out of
    step: (first texts with their authors, log in the order made, final texts)."""
    from hocuspocus_tpu.crdt import Doc, apply_update

    rng = random.Random(seed)
    first = [(seeded.first_client(seed, doc), text) for doc, text in enumerate(seeded.first_texts(seed, docs, 40))]
    log, texts = [], []
    for doc in range(docs):
        peers = [Doc() for _ in range(clients)]
        inbox = [[] for _ in peers]
        made = []
        for index, peer in enumerate(peers):
            peer.client_id = rng.getrandbits(30) | 1 << 30 | (index % 2) << 31
            apply_update(peer, seeded.text_update(*first[doc]), "remote")

            def on_update(update, origin, *_rest, index=index):
                if origin != "remote":
                    made.append(update)
                    for other in range(clients):
                        if other != index:
                            inbox[other].append(update)

            peer.on("update", on_update)
        for _ in range(steps):
            index = rng.randrange(clients)
            body = peers[index].get_text("body")
            if inbox[index] and rng.random() < 0.4:
                for update in inbox[index]:
                    apply_update(peers[index], update, "remote")
                inbox[index].clear()
                continue
            at = len(body) // 2 if rng.random() < 0.5 else rng.randrange(len(body) + 1)
            cut = min(rng.randrange(3), len(body) - at)
            run = "xy"[: rng.randint(1, 2)]
            peers[index].transact(lambda _t: (cut and body.delete(at, cut), body.insert(at, run)))
            log.append((doc, made[-1], peers[index].client_id, run, cut))
        for index, peer in enumerate(peers):
            for update in inbox[index]:
                apply_update(peer, update, "remote")
        assert len({peer.get_text("body").to_string() for peer in peers}) == 1
        texts.append(peers[0].get_text("body").to_string())
    return first, log, texts


@pytest.mark.parametrize("seed", [11, 2_147_483_659, 4_000_000_007])
def test_the_reference_reads_what_the_program_reads_and_each_control_does_not(seed, tmp_path):
    first, log, texts = _conflict_log(seed)
    reference = compare.merged(first, log)
    assert [r.text() for r in reference] == texts
    # the log as the program would leave it: the first update in the document's
    # own segment, the rest there too, and the newest also in the commit journal
    names = [f"doc/{n}" for n in range(len(first))]
    seeded.write_wal(str(tmp_path), names, [[seeded.text_update(*f)] for f in first])
    os.mkdir(tmp_path / "journal%")
    for doc, name in enumerate(names):
        mine = [entry[1] for entry in log if entry[0] == doc]
        with open(os.path.join(seeded.doc_dir(str(tmp_path), name), "00000000.wal"), "ab") as fh:
            fh.write(b"".join(seeded.wal_record(update) for update in mine) + b"\x07torn")
        entry = struct.pack("<HB", len(name.encode()), seeded.REC_UPDATE) + name.encode() + mine[-1]
        with open(tmp_path / "journal%" / "00000000.journal", "ab") as fh:
            fh.write(seeded.wal_record(entry, seeded.REC_JOURNAL_ENTRY))
    logs = [payloads for payloads in seeded.read_wal(str(tmp_path), names).values()]
    assert [len(payloads) for payloads in logs] == [2 + sum(e[0] == doc for e in log) for doc in range(len(first))]
    judged = compare.compare(reference, compare.as_observed(reference, logs), first, log)
    assert compare.correct(judged), judged
    for control in compare.CONTROLS:
        broken = compare.as_observed(compare.merged(first, log, control), logs, control)
        judged = compare.compare(reference, broken, first, log)
        assert not compare.correct(judged), control
        number = "wal_texts_differing" if control.startswith("wal") else "device_texts_differing"
        assert judged[number][0] > 0
    # an update that does not say what its client meant is the encoder's fault, and is counted
    doc, update, client, run, cut = log[0]
    assert TEXT.not_as_meant([(doc, update, client, run + "z", cut), (doc, update, client, run, cut + 1), log[1]]) == 2


def _unchanged_state(monkeypatch, armed):
    """The device step returns its whole state unchanged. The program's own
    health probe sees that and retires the documents from the plane, so the
    harness refuses the run outright: no result line at all."""
    from hocuspocus_tpu.tpu import pallas_kernels

    for name in ("append_run_slots_sparse_fast", "integrate_op_slots_sparse_fast"):
        step = getattr(pallas_kernels, name)
        monkeypatch.setattr(
            pallas_kernels, name, lambda state, *a, step=step: (state, 0) if armed else step(state, *a)
        )


def _unchanged_tombstones(monkeypatch, armed):
    """The integrate step leaves one part of its state as it was: no
    tombstone lands. That passes under the program's own probe."""
    from hocuspocus_tpu.tpu import pallas_kernels

    step = pallas_kernels.integrate_op_slots_sparse_fast

    def integrate(state, *args):
        before = state.deleted + 0
        state, count = step(state, *args)
        return (state._replace(deleted=before) if armed else state), count

    monkeypatch.setattr(pallas_kernels, "integrate_op_slots_sparse_fast", integrate)


def _half_the_batch(monkeypatch, armed):
    """Every other document's window is left out of a broadcast."""
    from hocuspocus_tpu.tpu.serving import PlaneServing

    whole = PlaneServing.build_broadcast_pairs

    def half(self, names):
        pairs, failed = whole(self, names)
        if armed:
            pairs = [(name, None if index % 2 else pair) for index, (name, pair) in enumerate(pairs)]
        return pairs, failed

    monkeypatch.setattr(PlaneServing, "build_broadcast_pairs", half)


def _altered_answer(monkeypatch, armed):
    """The text read back from the device arena has one unit altered."""
    from hocuspocus_tpu.tpu.merge_plane import MergePlane

    whole = MergePlane.text

    def altered(self, name):
        text = whole(self, name)
        return ("#" if text[0] != "#" else "?") + text[1:] if armed and text and name.startswith("bench") else text

    monkeypatch.setattr(MergePlane, "text", altered)


def _lost_log_record(monkeypatch, armed):
    """The write-ahead log acknowledges a record and does not keep it."""
    from hocuspocus_tpu.storage.wal import WalManager

    whole = WalManager.append

    def append(self, name, payload, *rest):
        if armed and not armed.count("lost"):
            armed.append("lost")
            payload = payload[:0]
        return whole(self, name, payload, *rest)

    monkeypatch.setattr(WalManager, "append", append)


def _altered_answer_of_a_named_kind(monkeypatch, armed):
    """`_altered_answer`, in a run whose configuration names its kind of
    document, `"document": "text"`, where the others leave it to the default."""
    config = Manifest.config
    monkeypatch.setattr(Manifest, "config", lambda self, name: {**config(self, name), "document": "text"})
    _altered_answer(monkeypatch, armed)


@pytest.mark.parametrize(
    "fault, number",
    [
        (_unchanged_state, None),
        (_unchanged_tombstones, "device_texts_differing"),
        (_half_the_batch, "updates_undelivered"),
        (_altered_answer, "device_texts_differing"),
        (_lost_log_record, "wal_texts_differing"),
        (_altered_answer_of_a_named_kind, "device_texts_differing"),
    ],
)
def test_a_run_with_the_timed_path_broken_is_not_correct(fault, number, monkeypatch, capsys):
    """Skips the harness's look for a chip (a rehearsal) and drives the rest
    of a run in this process, with a fault under the timed path that sets in
    once set-up is over, when the traffic starts."""
    sys.path.insert(0, BENCH)
    import run as bench_run

    armed = []
    tell = bench_run.Clients.tell

    async def telling(self, line):
        if line.startswith("go"):
            armed.append(True)
        await tell(self, line)

    monkeypatch.setattr(bench_run.Clients, "tell", telling)
    monkeypatch.setattr(bench_run, "GRACE_SECONDS", 3.0)
    fault(monkeypatch, armed)
    code = bench_run.main(
        ["--workload", "conflict-midinsert", "--seed", "77", "--seconds", "2", "--trace", "0", "--rehearse"]
    )
    captured = capsys.readouterr()
    lines = captured.out.splitlines()
    assert armed
    if number is None:
        assert code != 0 and not lines
        return
    assert code == 0 and lines, "no result that says it is not correct: " + captured.err[-600:]
    result = json.loads(lines[-1])
    assert result["correct"] is False
    assert result["compared"][number][0] > result["compared"][number][1]
