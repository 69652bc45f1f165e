"""From a profiler trace to numbers: device busy and idle time, the device
programs that took most of it, the longest idle gaps by what the host was
doing, the time of the programs a roofline is about, and the time under
every span of the host by its name, for the per-layer readers.

`load` turns the profiler's `.xplane.pb` into plain tuples,
[(plane name, [(line name, [(event name, start ns, duration ns)])])];
`reduce` works on those alone, so a test can hand it a trace made by hand.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops", "XLA Modules")  # the first of these that a device plane has
MODULE_LINE = "XLA Modules"
HOST_BUSY = "host busy: the loop runs the server's callbacks"
_SUFFIX = re.compile(r"\(\d+\)$")


def load(trace_dir: str) -> list:
    import jax

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [
        (
            plane.name,
            [
                (line.name, [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events])
                for line in plane.lines
            ],
        )
        for plane in data.planes
    ]


def stable(name: str) -> str:
    """A program's name without the fingerprint the runtime appends."""
    return _SUFFIX.sub("", name)


def union(intervals: "list[tuple[int, int]]") -> "list[tuple[int, int]]":
    merged: "list[list[int]]" = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(start, end) for start, end in merged]


def _overlap(span: "tuple[int, int]", sorted_spans: "list[tuple[int, int]]") -> int:
    """How much of `span` the disjoint, sorted `sorted_spans` cover."""
    at = bisect.bisect_right(sorted_spans, (span[0],)) - 1
    covered = 0
    for start, end in sorted_spans[max(at, 0) :]:
        if start >= span[1]:
            break
        covered += max(0, min(span[1], end) - max(span[0], start))
    return covered


def reduce(planes: list, window_s: float, device_plane=DEVICE_PLANE, label_prefix: str = "bench.") -> dict:
    """busy_s (mean over the device planes), window_s, device_ops and
    idle_gaps (at most 10 each, [name, seconds]), `program_seconds`,
    {stable program name: seconds} summed over the devices, and
    `span_seconds`, {span name: seconds} summed over every other plane.
    The device's idle time is split among the spans whose name starts with
    `label_prefix` by how much of it each covers, the rest being HOST_BUSY;
    a gap counts as the longest of the label that covers most of it."""
    devices = [(name, dict(lines)) for name, lines in planes if device_plane.match(name)]
    if not devices:
        raise ValueError(f"no device plane in the trace (planes: {[name for name, _ in planes]})")
    labelling: dict = {}
    span_ns: dict = {}
    edges = []
    for name, lines in planes:
        on_host = not device_plane.match(name)
        for _line, events in lines:
            for event, start, duration in events:
                edges += [start, start + duration]
                if on_host:
                    span_ns[event] = span_ns.get(event, 0) + duration
                    if event.startswith(label_prefix):
                        labelling.setdefault(event, []).append((start, start + duration))
    labelling = {span: union(spans) for span, spans in labelling.items()}
    busy_ns = 0
    programs: dict = {}
    idle_ns: dict = {}  # the device's idle time, split by what the host was doing
    longest: dict = {}  # the longest idle gap that was mostly under each label
    for _name, lines in devices:
        ops = next((lines[line] for line in OP_LINES if lines.get(line)), [])
        busy = union([(start, start + duration) for _e, start, duration in ops])
        busy_ns += sum(end - start for start, end in busy)
        for event, _start, duration in lines.get(MODULE_LINE) or ops:
            programs[stable(event)] = programs.get(stable(event), 0) + duration
        marks = [min(edges)] + [t for span in busy for t in span] + [max(edges)]
        for start, end in zip(marks[0::2], marks[1::2]):
            if end > start:
                doing = {span: _overlap((start, end), spans) for span, spans in labelling.items()}
                doing[HOST_BUSY] = end - start - sum(doing.values())
                for label, covered in doing.items():
                    idle_ns[label] = idle_ns.get(label, 0) + covered
                most = max(doing, key=doing.get)
                longest[most] = max(longest.get(most, 0), end - start)
    idle = []
    for label, total in sorted(idle_ns.items(), key=lambda item: -item[1]):
        if total:
            idle.append([f"{label}: idle time", total / 1e9])
        if label in longest:
            idle.append([f"{label}: longest gap", longest[label] / 1e9])
    top = sorted(programs.items(), key=lambda item: -item[1])[:10]
    return {
        "busy_s": busy_ns / len(devices) / 1e9,
        "window_s": window_s,
        "device_ops": [[name, seconds / 1e9] for name, seconds in top],
        "idle_gaps": idle[:10],
        "program_seconds": {name: ns / 1e9 for name, ns in programs.items()},
        "span_seconds": {name: ns / 1e9 for name, ns in span_ns.items()},
    }
