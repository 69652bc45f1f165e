"""The heap steward's instrumentation (server/heap.py): `heap.pass` is a
span like every other (live under a profiler capture, in the ring under
`--trace`, nothing otherwise), and its five counters are in the registry's
exposition. The `heap_steward` fixture puts the collector back as found."""

from __future__ import annotations

import pytest

from hocuspocus_tpu.observability import Metrics, disable_tracing, enable_tracing, get_tracer, tracing
from hocuspocus_tpu.server.heap import HeapStewardExtension
from tests.observability.test_span_capture import CountingAnnotation
from tests.utils import new_hocuspocus

COUNTERS = [
    ("hocuspocus_heap_passes", "counter"),
    ("hocuspocus_heap_pass_ms_total", "counter"),
    ("hocuspocus_heap_unfreezes", "counter"),
    ("hocuspocus_heap_frozen_blocks", "gauge"),
    ("hocuspocus_gc_auto_full_passes", "counter"),
]


@pytest.mark.parametrize("looking", ["capture", "ring", "nobody"])
async def test_heap_pass_is_a_span_for_whoever_is_looking(heap_steward, monkeypatch, looking):
    tracer = get_tracer()
    assert not tracer.enabled
    tracer.clear()
    monkeypatch.setattr(tracing, "_annotation", CountingAnnotation)
    monkeypatch.setattr(CountingAnnotation, "capturing", looking == "capture")
    monkeypatch.setattr(CountingAnnotation, "entered", [])
    if looking == "ring":
        enable_tracing()
    try:
        server = await new_hocuspocus(extensions=[HeapStewardExtension()])  # the boot pass
        try:
            heap_steward.run_pass("test")
        finally:
            await server.destroy()
        ring = [span for span in tracer.export() if span["name"] == "heap.pass"]
    finally:
        disable_tracing()
        tracer.clear()
    assert CountingAnnotation.entered.count("heap.pass") == (2 if looking == "capture" else 0)
    assert [span["attributes"] for span in ring] == (
        [{"reason": "boot", "unfreeze": False}, {"reason": "test", "unfreeze": False}] if looking == "ring" else []
    )


@pytest.mark.parametrize("name,kind", COUNTERS)
async def test_the_steward_counters_are_in_the_exposition(heap_steward, name, kind):
    metrics = Metrics()
    before = metrics.registry.expose()
    assert f"# TYPE {name} {kind}" in before
    server = await new_hocuspocus(extensions=[HeapStewardExtension(), metrics])
    try:
        lines = metrics.registry.expose().splitlines()
        (sample,) = [line for line in lines if line.startswith(name + " ")]
        stat = name.removeprefix("hocuspocus_")
        assert float(sample.split()[1]) == pytest.approx(heap_steward.stats[stat])
        if name != "hocuspocus_heap_unfreezes" and name != "hocuspocus_gc_auto_full_passes":
            assert heap_steward.stats[stat] > 0  # the boot pass
    finally:
        await server.destroy()
