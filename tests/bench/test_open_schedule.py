"""The open loop's schedule (bench/generators/writers.py `open_schedule`,
`doc_rates`) for every open-loop cell of BENCHMARK.json, at the cell's own
size: every seed offers the same due times and the same numbers of updates,
deals them to the documents in another order, and loads each plane alike."""

from __future__ import annotations

import os
import statistics
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench", "lib"))

import clients  # noqa: E402
import room  # noqa: E402
from manifest import Manifest  # noqa: E402

SEEDS = (1, 2_718_281_829, 4_000_000_007)
SECONDS = 20.0
MANIFEST = Manifest()
OPEN_CELLS = [
    cell["name"] for cell in MANIFEST.data["workloads"] if MANIFEST.traffic(cell["traffic"])["loop"] == "open"
]


def cell_mix(name: str) -> "tuple[dict, int, int]":
    """(the mix as run.py hands it to a client process, driven documents, planes)."""
    cell = MANIFEST.cell(name)
    config = MANIFEST.config(cell["config"])
    mix = {**MANIFEST.traffic(cell["traffic"]), "docs_per_plane": config["driven_docs_per_plane"]}
    planes = room.layout(config["flags"])[2]
    return mix, int(config["driven_docs_per_plane"]) * planes, planes


@pytest.mark.parametrize("cell", OPEN_CELLS)
def test_every_seed_offers_the_same_due_times(cell):
    mix, docs, _planes = cell_mix(cell)
    writers = clients.load_generator("writers")
    schedules = [writers.open_schedule(mix, docs, list(range(docs)), SECONDS, seed) for seed in SEEDS]
    dues = [[due for due, _doc in events] for events in schedules]
    assert dues[0] == sorted(dues[0]) and all(d == dues[0] for d in dues)
    assert -mix["warmup_seconds"] <= dues[0][0] and dues[0][-1] < SECONDS
    counts = [sorted(sum(1 for _due, doc in events if doc == d) for d in range(docs)) for events in schedules]
    assert all(c == counts[0] for c in counts)
    owners = [[doc for _due, doc in events] for events in schedules]
    assert owners[0] != owners[1] != owners[2]
    # a client process that drives some of the documents sends just their share
    mine = list(range(0, docs, 3))
    assert writers.open_schedule(mix, docs, mine, SECONDS, SEEDS[1]) == [e for e in schedules[1] if e[1] in set(mine)]


@pytest.mark.parametrize("cell", OPEN_CELLS)
def test_each_plane_is_loaded_alike(cell):
    mix, docs, planes = cell_mix(cell)
    writers = clients.load_generator("writers")
    per_plane = docs // planes
    loads, dealt = [], []
    for seed in SEEDS:
        rates = writers.doc_rates(mix, docs, seed)
        dealt.append(rates)
        loads.append([sum(rates[p * per_plane : (p + 1) * per_plane]) for p in range(planes)])
        assert sum(loads[-1]) == pytest.approx(mix["rate_updates_per_s"])
        assert (max(loads[-1]) - min(loads[-1])) / statistics.mean(loads[-1]) < 0.05
    # the same loads for every seed, dealt to other planes
    assert all(sorted(each) == pytest.approx(sorted(loads[0])) for each in loads)
    assert dealt[0] != dealt[1] != dealt[2]
    ungrouped = {key: value for key, value in mix.items() if key != "docs_per_plane"}
    assert sorted(writers.doc_rates(ungrouped, docs, SEEDS[0])) == pytest.approx(sorted(writers.doc_rates(mix, docs, SEEDS[0])))
