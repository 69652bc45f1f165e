"""The program's spans under a profiler capture, and not under one.

`Tracer.span` is live when the tracer is enabled or a `jax.profiler`
capture is running; these tests hold it to that with the tracer disabled:
a real capture on the CPU backend has to carry every span the benchmark's
readers sum (and their children), on loop and executor threads alike, and
with no capture the same traffic may enter no annotation at all. Also here,
because the same tiny server shows them: the wait counters only grow.
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import shutil
import sys
import tempfile

import pytest

from hocuspocus_tpu.observability import get_tracer, tracing
from hocuspocus_tpu.storage import Durability
from hocuspocus_tpu.tpu import TpuMergeExtension
from tests.utils import new_hocuspocus, new_provider, retryable_assertion, wait_synced

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench", "lib"))

import tracereduce  # noqa: E402

# every span a capture can carry (docs/guides/observability.md, the span
# catalogue): the ones the benchmark's readers sum, and their children
LOOP_SPANS = [
    "connection.dispatch",
    "message.update_apply",
    "wal.append",
    "plane.capture",
    "plane.lower",
    "plane.broadcast",
    "fanout.tick",
    "plane.post_flush",
    "transport.read",
    "connection.receive",
    "plane.flush_turn",
    "wal.commit_done",
]
EXECUTOR_SPANS = [
    "merge_plane.flush",
    "merge_plane.drain",
    "merge_plane.upload",
    "merge_plane.append",
    "merge_plane.integrate",
    "merge_plane.readback",
    "wal.commit",
    "wal.fsync",
]
MONOTONE = ("commit_ms_total", "durable_wait_ms_total", "broadcast_wait_ms_total", "broadcast_passes")


async def serve_an_update_and_a_conflict(looking=contextlib.nullcontext) -> "list[dict]":
    """A tiny in-process server as the benchmark wires it (the log, the
    plane serving), two providers on one document. Inside `looking()`: one
    append (the run-append path) and one pair of concurrent inserts at one
    spot (the YATA integrate). Returns the wait counters, read after each."""
    wal_dir = tempfile.mkdtemp(prefix="span-capture-wal-")
    durability = Durability(wal_dir=wal_dir)
    plane_ext = TpuMergeExtension(num_docs=8, capacity=256, flush_interval_ms=1, serve=True)
    server = await new_hocuspocus(extensions=[durability, plane_ext])
    writer = new_provider(server, name="spanned")
    other = new_provider(server, name="spanned")
    readings = []

    def counters() -> dict:
        return {**durability.wal.stats, **plane_ext.plane.counters}

    async def settle(units: int) -> None:
        def settled() -> None:
            text = server.documents["spanned"].get_text("body").to_string()
            assert len(text) == units
            assert writer.document.get_text("body").to_string() == text
            assert other.document.get_text("body").to_string() == text
            assert plane_ext.plane.pending_ops() == 0 and not plane_ext._flush_inflight
            assert plane_ext.plane.text("spanned") == text

        await retryable_assertion(settled)
        readings.append(counters())

    try:
        await wait_synced(writer, other)
        writer.document.get_text("body").insert(0, "warm")
        await settle(4)
        with looking():
            writer.document.get_text("body").insert(4, " up")
            await settle(7)
            writer.document.get_text("body").insert(0, "A")
            other.document.get_text("body").insert(0, "B")
            await settle(9)
            await asyncio.sleep(0.05)
        counted = plane_ext.plane.counters
        assert counted["flush_batches_fast"] and counted["flush_slow_ops"] and not counted["cpu_fallbacks"]
        return readings
    finally:
        writer.destroy()
        other.destroy()
        await server.destroy()
        shutil.rmtree(wal_dir, ignore_errors=True)


@pytest.fixture(scope="module")
def captured():
    """`span_seconds` of a short capture around the traffic, taken as the
    harness takes its own (host tracer at level 1, no Python tracer), with
    the tracer disabled. A CPU capture has no device plane: one made by
    hand is appended, as the harness's own test does."""
    import jax

    tracer = get_tracer()
    assert not tracer.enabled
    tracer.clear()
    trace_dir = tempfile.mkdtemp(prefix="span-capture-")

    @contextlib.contextmanager
    def capture():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    try:
        readings = asyncio.run(asyncio.wait_for(serve_an_update_and_a_conflict(capture), 120))
        planes = tracereduce.load(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    assert any(name == "/host:CPU" for name, _lines in planes)
    ms = 1_000_000
    planes.append(("/device:TPU:0", [("XLA Ops", [("fusion", ms, ms)])]))
    return {
        "span_seconds": tracereduce.reduce(planes, 1.0)["span_seconds"],
        "ring": len(tracer),
        "readings": readings,
    }


@pytest.mark.parametrize("name", LOOP_SPANS + EXECUTOR_SPANS)
def test_a_capture_carries_the_span_with_the_tracer_disabled(captured, name):
    assert captured["span_seconds"].get(name, 0) > 0, sorted(captured["span_seconds"])
    assert captured["ring"] == 0  # live under the capture alone: nothing lands in the ring


def test_spans_that_await_stay_out_of_the_capture(captured):
    assert not [name for name in captured["span_seconds"] if name.startswith(("message.apply", "hooks."))]


def test_a_parent_span_holds_its_children(captured):
    seconds = captured["span_seconds"]
    assert seconds["message.update_apply"] >= seconds["wal.append"] + seconds["plane.capture"]
    assert seconds["plane.capture"] >= seconds["plane.lower"]
    assert seconds["wal.commit"] >= seconds["wal.fsync"]
    assert seconds["merge_plane.flush"] >= sum(
        seconds["merge_plane." + stage] for stage in ("drain", "upload", "append", "integrate", "readback")
    )


def test_a_loop_stage_holds_the_spans_that_open_inside_it(captured):
    seconds = captured["span_seconds"]
    assert seconds["connection.receive"] >= seconds["connection.dispatch"] + seconds["message.update_apply"]
    assert seconds["plane.flush_turn"] >= seconds["plane.post_flush"]


@pytest.mark.parametrize("counter", MONOTONE)
def test_the_wait_counters_only_grow(captured, counter):
    values = [reading[counter] for reading in captured["readings"]]
    assert values == sorted(values) and values[-1] > values[0] >= 0, values


class CountingAnnotation:
    """Stands in for `jax.profiler.TraceAnnotation`: says whether a capture
    is running, and counts what is entered."""

    capturing = False
    entered: "list[str]" = []

    def __init__(self, name: str) -> None:
        self.name = name

    @staticmethod
    def is_enabled() -> bool:
        return CountingAnnotation.capturing

    def __enter__(self) -> "CountingAnnotation":
        CountingAnnotation.entered.append(self.name)
        return self

    def __exit__(self, *exc) -> bool:
        return False


@pytest.mark.parametrize("capturing", [False, True])
async def test_a_span_site_enters_an_annotation_only_while_a_capture_runs(capturing, monkeypatch):
    tracer = get_tracer()
    assert not tracer.enabled
    tracer.clear()
    monkeypatch.setattr(tracing, "_annotation", CountingAnnotation)
    monkeypatch.setattr(CountingAnnotation, "capturing", capturing)
    monkeypatch.setattr(CountingAnnotation, "entered", [])
    await serve_an_update_and_a_conflict()
    assert len(tracer) == 0  # the tracer is disabled: the ring stays empty either way
    if capturing:
        assert set(CountingAnnotation.entered) >= set(LOOP_SPANS + EXECUTOR_SPANS)
    else:
        assert CountingAnnotation.entered == []
        assert tracer.span("anything") is tracer.span("else")  # the one shared no-op: no allocation
