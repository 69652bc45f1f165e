"""BENCHMARK.json and the files it names: everything the harness knows about
a cell, a configuration, its kind of document, a traffic mix or a per-layer
metric it finds here, by name. A later PR adds files and entries; it edits
nothing."""

from __future__ import annotations

import importlib.util
import json
import os
import re

import kinds

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(Exception):
    pass


def _read_json(path: str) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError) as error:
        raise ManifestError(f"{path}: {error}") from None


class Manifest:
    def __init__(self, root: str = ROOT) -> None:
        self.root = root
        self.data = _read_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.data["paths"][0])
        self.cells = {cell["name"]: cell for cell in self.data["workloads"]}
        self.configs = {config["name"]: config for config in self.data["configs"]}

    def check_names(self) -> None:
        """Every name and unit is made of the characters the contract allows."""
        metrics = self.data["end_to_end"] + self.data["per_layer"]
        names = [m["name"] for m in metrics] + list(self.cells) + list(self.configs)
        for cell in self.cells.values():
            names += [cell["config"], cell["traffic"]]
        for config in self.configs.values():
            names += config["reduced"]
        bad = [n for n in names if not NAME.match(n)]
        bad += [m["unit"] for m in metrics if not UNIT.match(m["unit"])]
        bad += [m["name"] for m in metrics if m["source"] not in SOURCES]
        bad += [m["name"] for m in metrics if m["better"] not in ("lower", "higher")]
        if bad:
            raise ManifestError(f"names, units or sources outside the contract: {bad}")
        for name in self.configs:
            kind = kinds.name_of(self.config(name))
            if not NAME.match(kind) or not os.path.isfile(kinds.path_of(kind, self.bench_dir)):
                raise ManifestError(f"configuration {name!r} names the document kind {kind!r}, which has no file")

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise ManifestError(f"no workload {name!r} in BENCHMARK.json (has {sorted(self.cells)})")
        return self.cells[name]

    def config(self, name: str) -> dict:
        """The configuration as it is run: its file's content."""
        if name not in self.configs:
            raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")
        return _read_json(os.path.join(self.root, self.configs[name]["file"]))

    def kind(self, config: dict):
        """The module of the document kind `config` names (`lib/kinds.py`)."""
        return kinds.load(kinds.name_of(config), self.bench_dir)

    def traffic(self, name: str) -> dict:
        return _read_json(os.path.join(self.bench_dir, "traffic", name + ".json"))

    def metrics_of(self, cell: str, group: str) -> "list[dict]":
        """The metrics of `end_to_end` or `per_layer` that this cell reports:
        one with a `workloads` list in the cells it lists; an end-to-end
        metric without one in every cell, and a per-layer metric without one
        in every cell that reports the end-to-end metric it moves."""
        if group == "end_to_end":
            return [m for m in self.data[group] if "workloads" not in m or cell in m["workloads"]]
        reported = {m["name"] for m in self.metrics_of(cell, "end_to_end")}
        return [
            metric
            for metric in self.data[group]
            if cell in metric.get("workloads", ()) or ("workloads" not in metric and metric["moves"] in reported)
        ]

    def reported_as(self, cell: str, quantity: str) -> dict:
        """The one entry under which this cell reports a per-layer quantity:
        the entry of that name, or its twin split by what it moves
        (`server_loop_busy_share.typing`, where the cell reports another
        end-to-end metric than the entry of that name moves)."""
        (entry,) = [m for m in self.metrics_of(cell, "per_layer") if m["name"].split(".")[0] == quantity]
        return entry

    def reader(self, metric: str):
        """The `read(run)` of bench/metrics/<metric>.py. A metric split by
        the end-to-end metric it moves (`ops_per_flush.typing`) that has no
        file of its own is read by the file of the name before the dot."""
        names = [metric] + ([metric.split(".")[0]] if "." in metric else [])
        paths = [os.path.join(self.bench_dir, "metrics", name + ".py") for name in names]
        path = next((p for p in paths if os.path.isfile(p)), None)
        if path is None:
            raise ManifestError(f"per-layer metric {metric!r} has no reader at {paths[0]}")
        spec = importlib.util.spec_from_file_location("bench_metric_" + re.sub(r"\W", "_", metric), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        declared = next(m["source"] for m in self.data["per_layer"] if m["name"] == metric)
        if module.SOURCE != declared:
            raise ManifestError(f"{metric}: the reader says {module.SOURCE!r}, BENCHMARK.json {declared!r}")
        return module.read
