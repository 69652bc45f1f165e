"""`inline_write_share` and `loop_socket_write_share`: the readers on hand-made
runs, their entries in the manifest (looked up by name: later PRs append), and
the program's spans they read. Runs on the CPU; loads no libtpu."""

from __future__ import annotations

import asyncio
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench", "lib"))

from manifest import Manifest  # noqa: E402

INLINE, QUEUED = "transport.write_inline", "transport.write_queued"


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def traced(span_seconds, window_s=10.0):
    return {"wal_delta": {}, "plane_delta": {}, "trace": {"window_s": window_s, "span_seconds": span_seconds}}


@pytest.mark.parametrize(
    "run, inline_share, write_share",
    [
        (traced({INLINE: 0.9, QUEUED: 0.1, "fanout.tick": 1.2}), 90.0, 10.0),
        (traced({INLINE: 0.5}), 100.0, 5.0),  # no frame ever queued
        (traced({QUEUED: 0.25}), 0.0, 2.5),  # a socket held back all through the window: the writer ships every frame
        (traced({INLINE: 0.0, QUEUED: 0.0}), None, 0.0),  # the spans are there and no frame was written
        (traced({"fanout.tick": 1.2, "bench.loop_asleep": 4.0}), None, None),  # the parent commit: no such span
        ({"wal_delta": {}, "plane_delta": {}, "trace": None}, None, None),  # an untraced run
    ],
)
def test_the_readers_on_hand_made_runs(manifest, run, inline_share, write_share):
    assert manifest.reader("inline_write_share")(run) == pytest.approx(inline_share)
    assert manifest.reader("loop_socket_write_share")(run) == pytest.approx(write_share)


@pytest.mark.parametrize("name, better", [("inline_write_share", "higher"), ("loop_socket_write_share", "lower")])
def test_every_cell_reports_them_under_the_loop_layer(manifest, name, better):
    (entry,) = [m for m in manifest.data["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "%", "better": better, "source": "program_span",
        "layer": "server event loop and wire codec", "moves": "update_to_peer_p95_ms",
    }
    manifest.check_names()
    for cell in manifest.cells:
        assert_reported(manifest, cell, entry)


async def test_the_program_opens_the_spans_the_readers_read():
    """One frame by each path, under an enabled tracer: the names in the ring
    are the names the readers sum."""
    sys.path.insert(1, ROOT)
    from hocuspocus_tpu.observability.tracing import get_tracer
    from hocuspocus_tpu.server.transports import CallbackWebSocketTransport

    async def send_async(data):
        pass

    async def close_async(code, reason):
        pass

    held_back = [False]
    transport = CallbackWebSocketTransport(send_async, close_async, writable=lambda: not held_back[0])
    tracer = get_tracer()
    was, tracer.enabled = tracer.enabled, True
    try:
        await asyncio.sleep(0.01)  # the writer task parks
        tracer.clear()
        transport.send(b"written by send()")
        held_back[0] = True
        transport.send(b"shipped by the writer task")
        await asyncio.sleep(0.01)
        assert [span["name"] for span in tracer.export()] == [INLINE, QUEUED]
    finally:
        tracer.enabled = was
        tracer.clear()  # the ring is the process's: leave it as found for the next test
        transport.abort()


def assert_reported(manifest, cell, entry):
    """The cell reports the quantity: under the entry itself, or where it
    reports another end-to-end metric than the entry moves, under its twin
    that lists the cell and moves what the cell reports."""
    reported = manifest.reported_as(cell, entry["name"])
    assert reported["moves"] in {m["name"] for m in manifest.metrics_of(cell, "end_to_end")}
    same = {key: value for key, value in entry.items() if key not in ("name", "moves")}
    assert {key: value for key, value in reported.items() if key not in ("name", "moves", "workloads")} == same
    assert reported["name"] == entry["name"] or reported["workloads"] == [cell]
