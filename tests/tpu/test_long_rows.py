"""Rows far longer than the 5,632 units the block kernel was sized at (the
`text-b4-paper-105k` deployment: rows of 106,496): the integrate gives a
long row what it gives a short one for the same ops, by the scan and by the
block kernel; which program the dispatcher picks at that row length; and the
plane's account of why an op took the full-row integrate.

Interpret mode and the CPU here; the same programs are compiled for a v5e at
448 x 106,496 by hand before a chip run (PERF.md, PR 34) and run on the chip
by the cell `paper-cursor-edit`.
"""

import types

import numpy as np
import pytest

from hocuspocus_tpu.crdt import Doc, apply_update, encode_state_as_update
from hocuspocus_tpu.tpu import pallas_kernels
from hocuspocus_tpu.tpu.kernels import (
    NONE_CLIENT,
    OpBatch,
    integrate_op_slots_sparse,
    make_empty_state,
)
from hocuspocus_tpu.tpu.merge_plane import MergePlane
from hocuspocus_tpu.tpu.pallas_kernels import _pick_block, integrate_op_slots_sparse_pallas

SHORT, LONG = 256, 8192
PAPER_ROW = 106_496
_CLIENTS = (7, 0x9000_0001)  # one on each side of 2**31: the tie-break is unsigned


def _stream(rng, width: int, slots: int, next_clock) -> OpBatch:
    """A causally valid (slots, width) batch from two authors: runs of 1-8
    units leaning on known units, a third of them with a right origin, and
    deletes of known ranges. `next_clock` is (authors, width), updated."""
    shape = (slots, width)
    kind = rng.integers(1, 3, size=shape).astype(np.int32)
    client = np.full(shape, _CLIENTS[0], np.uint32)
    clock = np.zeros(shape, np.int32)
    run_len = rng.integers(1, 9, size=shape).astype(np.int32)
    lc, rc = np.full(shape, NONE_CLIENT, np.uint32), np.full(shape, NONE_CLIENT, np.uint32)
    lk, rk = np.zeros(shape, np.int32), np.zeros(shape, np.int32)
    for k in range(slots):
        for d in range(width):
            author = int(rng.integers(0, 2))
            known = [(i, c) for i, c in enumerate(next_clock[:, d]) if c > 0]
            client[k, d] = _CLIENTS[author]
            if kind[k, d] == 2 and next_clock[author, d] > 0:
                clock[k, d] = rng.integers(0, next_clock[author, d])
                run_len[k, d] = min(run_len[k, d], next_clock[author, d] - clock[k, d])
                continue
            kind[k, d] = 1
            clock[k, d] = next_clock[author, d]
            if known:
                origin, reached = known[rng.integers(0, len(known))]
                lc[k, d], lk[k, d] = _CLIENTS[origin], rng.integers(0, reached)
                if rng.random() < 0.3:
                    origin, reached = known[rng.integers(0, len(known))]
                    rc[k, d], rk[k, d] = _CLIENTS[origin], rng.integers(0, reached)
            next_clock[author, d] += run_len[k, d]
    return OpBatch(kind, client, clock, run_len, lc, lk, rc, rk)


@pytest.mark.parametrize("program", ["scan", "block kernel"])
def test_a_long_row_integrates_as_a_short_row_does(program):
    """The same ops into rows of 256 and of 8,192 units: the long row's first
    256 slots hold what the short row holds and the rest stays empty."""
    rng = np.random.default_rng(34)
    rows, width, slots = 16, 8, 6
    routing = np.asarray([3, 0, 9, 12, 5, 14, 1, 8], np.int32)
    next_clock = np.zeros((2, width), np.int64)
    short, long = make_empty_state(rows, SHORT), make_empty_state(rows, LONG)
    empty = make_empty_state(1, 1)
    for _ in range(3):
        ops = _stream(rng, width, slots, next_clock)
        short, counted = integrate_op_slots_sparse(short, ops, routing)
        if program == "scan":
            long, long_counted = integrate_op_slots_sparse(long, ops, routing)
        else:
            long, long_counted = integrate_op_slots_sparse_pallas(long, ops, routing, interpret=True)
        assert int(counted) == int(long_counted) == slots * width
    assert 0 < int(np.asarray(short.length).max()) <= SHORT and not np.asarray(short.overflow).any()
    for name, a, b, fill in zip(short._fields, short, long, empty):
        a, b = np.asarray(a), np.asarray(b)
        if a.ndim == 1:
            np.testing.assert_array_equal(a, b, err_msg=name)
            continue
        np.testing.assert_array_equal(a, b[:, :SHORT], err_msg=name)
        assert (b[:, SHORT:] == np.asarray(fill)[0, 0]).all(), name


def test_the_program_that_serves_a_row_of_106_496_units(monkeypatch):
    """One rule on the shape for every capacity: the block kernel wherever a
    block of 8 rows divides the batch and the modelled live set fits the
    budget, else the scan. At 106,496 units that is the kernel at db = 8 for
    every bucket of 8 rows and more (`integrate_sparse 16x16`, `16x64`,
    `16x256` and the dense `Kx448` compile for a v5e and ran on the chip,
    PERF.md, PR 34) and the scan for the buckets of 1 and 4."""
    from hocuspocus_tpu.tpu.pallas_kernels import _LIVE_BUFFERS, _VMEM_BUDGET, _VMEM_LIMIT

    for batch in (8, 16, 64, 256, 448):
        assert _pick_block(batch, PAPER_ROW) == 8
    assert _LIVE_BUFFERS * 8 * PAPER_ROW * 4 <= _VMEM_BUDGET <= _VMEM_LIMIT < _LIVE_BUFFERS * 16 * PAPER_ROW * 4
    assert _pick_block(1, PAPER_ROW) == _pick_block(4, PAPER_ROW) == 0
    assert _pick_block(8, 2 * PAPER_ROW) == 0  # a row the model does not fit takes the scan at every width
    # the rows the accepted cells run keep their blocks
    assert (_pick_block(16, 5632), _pick_block(64, 5632), _pick_block(8192, 5632)) == (16, 64, 64)

    taken = []
    monkeypatch.setattr(pallas_kernels, "_integrate_sparse_pallas", lambda *args: taken.append("block kernel"))
    import hocuspocus_tpu.tpu.kernels as kernels

    monkeypatch.setattr(kernels, "integrate_op_slots_sparse", lambda *args: taken.append("scan"))
    arena = types.SimpleNamespace(id_client=types.SimpleNamespace(shape=(448, PAPER_ROW)))
    for batch in (1, 4, 8, 16, 64, 256):
        integrate_op_slots_sparse_pallas(arena, None, np.zeros(batch, np.int32))
    assert taken == ["scan", "scan"] + ["block kernel"] * 4


class Planted:
    """A plane with one document that two authors have in sync."""

    def __init__(self) -> None:
        self.plane = MergePlane(num_docs=8, capacity=512)
        self.plane.register("paper")
        self.authors = []
        for cid in (1 << 30 | 5, 1 << 31 | 6):
            doc, made = Doc(), []
            doc.client_id = cid
            doc.on("update", lambda update, origin, *rest, made=made: origin is None and made.append(update))
            self.authors.append((doc, made))
        first, _made = self.authors[0]
        first.get_text("t").insert(0, "the first text of a long paper")
        self.sync()
        self.flush()

    def sync(self) -> None:
        a, b = self.authors[0][0], self.authors[1][0]
        apply_update(b, encode_state_as_update(a), "peer")
        apply_update(a, encode_state_as_update(b), "peer")

    def flush(self) -> dict:
        """Hand every update made since the last flush to the plane, run one
        cycle, and return what the plane's counters moved by."""
        before = dict(self.plane.counters)
        for _doc, made in self.authors:
            for update in made:
                self.plane.enqueue_update("paper", update)
            made.clear()
        self.plane.flush()
        return {key: value - before[key] for key, value in self.plane.counters.items()}

    def text(self, author: int = 0):
        return self.authors[author][0].get_text("t")


REASONS = ("slow_ops_mid_row", "slow_ops_delete", "slow_ops_concurrent")


def test_each_reason_for_the_full_row_integrate_is_counted_by_a_planted_stream():
    planted = Planted()
    plane = planted.plane
    assert all(plane.counters[reason] == 0 for reason in REASONS)  # the first text was an append to an empty row

    body = planted.text()
    body.insert(len(body), " and")  # at the row's tail: the run-append path, no reason to count
    moved = planted.flush()
    assert moved["flush_fast_ops"] == 1 and moved["flush_slow_ops"] == 0
    assert not any(moved[reason] for reason in REASONS) and moved["integrate_row_units"] == 0

    body.insert(9, "x")  # inside the text: it names a unit to its right
    moved = planted.flush()
    assert (moved["slow_ops_mid_row"], moved["slow_ops_delete"], moved["slow_ops_concurrent"]) == (1, 0, 0)
    assert moved["flush_slow_ops"] == 1 and moved["flush_fast_ops"] == 0
    assert moved["integrate_row_units"] == 1 * plane.capacity  # a bucket of one row, swept whole

    body.insert(0, "A")  # before the first unit: no left origin, and still not at the tail
    assert planted.flush()["slow_ops_mid_row"] == 1

    body.delete(4, 1)
    moved = planted.flush()
    assert (moved["slow_ops_mid_row"], moved["slow_ops_delete"], moved["slow_ops_concurrent"]) == (0, 1, 0)

    planted.sync()
    planted.text(0).insert(len(planted.text(0)), "!")  # two authors append to the same tail at once
    planted.text(1).insert(len(planted.text(1)), "?")
    moved = planted.flush()
    assert (moved["slow_ops_mid_row"], moved["slow_ops_delete"], moved["slow_ops_concurrent"]) == (0, 0, 2)
    assert moved["flush_slow_ops"] == 2 and moved["flush_fast_ops"] == 0

    # an append in one cycle with a delete shares its column: it is slow for the company it keeps
    planted.sync()
    body.insert(len(body), "z")
    body.delete(2, 1)
    moved = planted.flush()
    assert (moved["slow_ops_mid_row"], moved["slow_ops_delete"], moved["slow_ops_concurrent"]) == (0, 1, 1)

    assert sum(plane.counters[reason] for reason in REASONS) == plane.counters["flush_slow_ops"]
    planted.sync()
    assert plane.text("paper") == planted.text(0).to_string() == planted.text(1).to_string()


def test_the_integrate_span_carries_the_row_length():
    from hocuspocus_tpu.observability.tracing import get_tracer

    planted = Planted()
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    try:
        planted.text().insert(3, "q")
        planted.flush()
        spans = [s for s in tracer.export() if s["name"] == "merge_plane.integrate"]
    finally:
        tracer.enabled = was
    assert spans and spans[-1]["attributes"]["row_units"] == planted.plane.capacity
    assert spans[-1]["attributes"]["integrated"] == 1


async def test_the_reasons_are_on_the_metrics_endpoint():
    """A served plane's new counters reach `/metrics` like every plane counter."""
    import aiohttp

    from hocuspocus_tpu.observability import Metrics
    from hocuspocus_tpu.tpu import TpuMergeExtension
    from tests.utils import new_hocuspocus, new_provider, retryable_assertion, wait_synced

    ext = TpuMergeExtension(num_docs=8, capacity=512, flush_interval_ms=1, serve=True)
    server = await new_hocuspocus(extensions=[Metrics(), ext])
    provider = new_provider(server, name="paper")
    try:
        await wait_synced(provider)
        body = provider.document.get_text("t")
        body.insert(0, "a text to edit inside")
        body.insert(5, "x")
        body.delete(2, 1)

        def integrated():
            assert ext.plane.counters["slow_ops_mid_row"] >= 1 and ext.plane.counters["slow_ops_delete"] >= 1

        await retryable_assertion(integrated)
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{server.http_url}/metrics") as response:
                lines = (await response.text()).splitlines()
        for key in REASONS + ("integrate_row_units",):
            assert any(line.startswith(f"hocuspocus_tpu_plane_{key} ") for line in lines), key
        swept = next(line for line in lines if line.startswith("hocuspocus_tpu_plane_integrate_row_units "))
        assert float(swept.split()[1]) >= 512
    finally:
        provider.destroy()
        await server.destroy()
