"""Kernel result checks for the programs a served smoke does not reach.

`chip_smoke.py` serves the unit arena through shard planes, so its
traffic runs the sparse Pallas integrate, the run-append program, the
health probe and the unit catch-up pack. This module (the smoke's, not
the product's: it lives beside `chip_smoke.py`) compiles and runs
ONCE, on whatever device JAX selected, the rest of the kernel
inventory — the dense unit sweep at K = 16 and the RLE arena's Pallas
dense/sparse, append, compact and catch-up pack — and checks each
result against the plain path of the same semantics: the XLA scan for
the integrate and append programs, the host extraction helpers
(`expand_to_units`, `delete_ranges`) for compact and pack.

The op stream is random but causally valid (every origin names an id
issued earlier in the stream), two clients with ids on both sides of
2^31 so the YATA tiebreak runs as an unsigned compare.
"""

from __future__ import annotations

import time

import numpy as np

from hocuspocus_tpu.tpu.kernels import NONE_CLIENT, OpBatch

_CLIENTS = np.asarray([7, 0x9000_0001], np.uint32)


def random_op_stream(rng, num_slots: int, next_clock: np.ndarray) -> OpBatch:
    """(K, D) random insert/delete ops as numpy fields; advances
    next_clock (2, D) in place. Inserts pick a random known id as left
    origin (and, 30% of the time, another as right origin); deletes
    tombstone a random issued range."""
    num_docs = next_clock.shape[1]
    fields = [
        np.zeros((num_slots, num_docs), dtype)
        for dtype in (
            np.int32, np.uint32, np.int32, np.int32,
            np.uint32, np.int32, np.uint32, np.int32,
        )
    ]
    kind, client, clock, run_len, lc, lk, rc, rk = fields
    lc[...] = NONE_CLIENT
    rc[...] = NONE_CLIENT
    docs = np.arange(num_docs)

    def known_id():
        """A random already-issued (client index, clock) per doc; the
        mask says which docs have any id at all."""
        pick = rng.integers(0, 2, num_docs)
        pick = np.where(next_clock[pick, docs] > 0, pick, 1 - pick)
        issued = next_clock[pick, docs]
        at = (rng.random(num_docs) * issued).astype(np.int64)
        return pick, at, issued > 0

    for k in range(num_slots):
        want = rng.integers(0, 3, num_docs)
        author = rng.integers(0, 2, num_docs)
        run = rng.integers(1, 9, num_docs)
        authored = next_clock[author, docs]
        insert = want == 1
        delete = (want == 2) & (authored > 0)
        kind[k] = np.where(insert, 1, np.where(delete, 2, 0))
        client[k] = np.where(insert | delete, _CLIENTS[author], 0)
        run_len[k] = run
        clock[k] = np.where(
            insert, authored, (rng.random(num_docs) * authored).astype(np.int64)
        )
        pick, at, has = known_id()
        left = insert & has
        lc[k] = np.where(left, _CLIENTS[pick], NONE_CLIENT)
        lk[k] = np.where(left, at, 0)
        pick, at, has = known_id()
        right = left & has & (rng.random(num_docs) < 0.3)
        rc[k] = np.where(right, _CLIENTS[pick], NONE_CLIENT)
        rk[k] = np.where(right, at, 0)
        next_clock[author, docs] += np.where(insert, run, 0)
    return OpBatch(*fields)


def _chained_appends(num_slots: int, width: int) -> "tuple[tuple, OpBatch]":
    """K chained tail-append runs per column, clients alternating so no
    two neighbours coalesce: the (client, clock, run_len) fast-path
    fields and the same chain as full insert ops for the scan path."""
    rows = np.arange(num_slots)[:, None]
    author = np.broadcast_to(rows % 2, (num_slots, width))
    run_len = np.broadcast_to(1 + rows % 5, (num_slots, width)).astype(np.int32)
    clock = np.zeros((num_slots, width), np.int32)
    issued = np.zeros((2, width), np.int64)
    for k in range(num_slots):
        clock[k] = issued[k % 2]
        issued[k % 2] += run_len[k]
    client = _CLIENTS[author]
    lc = np.full((num_slots, width), NONE_CLIENT, np.uint32)
    lk = np.zeros((num_slots, width), np.int32)
    lc[1:] = client[:-1]
    lk[1:] = clock[:-1] + run_len[:-1] - 1
    ops = OpBatch(
        kind=np.ones((num_slots, width), np.int32),
        client=client,
        clock=clock,
        run_len=run_len,
        left_client=lc,
        left_clock=lk,
        right_client=np.full((num_slots, width), NONE_CLIENT, np.uint32),
        right_clock=np.zeros((num_slots, width), np.int32),
    )
    return (client, clock, run_len), ops


def _differing(state_a, state_b) -> "list[str]":
    """Names of the fields that differ (compared on the device)."""
    import jax.numpy as jnp

    return [
        name
        for name, a, b in zip(state_a._fields, state_a, state_b)
        if not bool(jnp.array_equal(a, b))
    ]


def _host(state):
    return type(state)(*(np.asarray(field) for field in state))


def _on_device(ops: OpBatch) -> OpBatch:
    import jax.numpy as jnp

    return OpBatch(*(jnp.asarray(field) for field in ops))


def run_kernel_checks(
    num_docs: int = 8192,
    unit_capacity: int = 5632,
    rle_entries: int = 1024,
    num_slots: int = 16,
    sparse_width: int = 64,
    seed: int = 0,
    interpret: bool = False,
) -> dict:
    """Run every check; returns {check name: {"ok", "seconds", ...}}.
    A kernel that fails to compile or launch raises."""
    import jax
    import jax.numpy as jnp

    from hocuspocus_tpu.tpu.kernels import integrate_op_slots, make_empty_state
    from hocuspocus_tpu.tpu.kernels_rle import (
        append_run_slots_rle_sparse,
        catchup_pack_rle,
        compact_doc_rows_rle,
        delete_ranges,
        expand_to_units,
        integrate_op_slots_rle,
        integrate_op_slots_rle_sparse,
        make_empty_rle_state,
    )
    from hocuspocus_tpu.tpu.pallas_kernels import _pick_block, integrate_op_slots_pallas
    from hocuspocus_tpu.tpu.pallas_kernels_rle import (
        _pick_block_rle,
        integrate_op_slots_rle_pallas,
        integrate_op_slots_rle_sparse_pallas,
    )
    from hocuspocus_tpu.tpu.serving import PlaneServing

    if not (
        _pick_block(num_docs, unit_capacity)
        and _pick_block_rle(num_docs, rle_entries)
        and _pick_block_rle(sparse_width, rle_entries)
    ):
        raise ValueError("shapes must be Pallas-eligible (see _pick_block)")
    rng = np.random.default_rng(seed)
    report: dict = {}

    def timed(name: str, started: float, **fields) -> None:
        report[name] = {"seconds": round(time.perf_counter() - started, 3), **fields}

    # -- unit arena: dense Pallas sweep vs the XLA scan ----------------------
    started = time.perf_counter()
    next_clock = np.zeros((2, num_docs), np.int64)
    scan = make_empty_state(num_docs, unit_capacity)
    pallas = make_empty_state(num_docs, unit_capacity)
    applied = 0
    counts_equal = True
    for _ in range(2):
        ops = _on_device(random_op_stream(rng, num_slots, next_clock))
        scan, count_scan = integrate_op_slots(scan, ops)
        pallas, count_pallas = integrate_op_slots_pallas(pallas, ops, interpret=interpret)
        applied += int(count_pallas)
        counts_equal &= int(count_scan) == int(count_pallas)
    differ = _differing(scan, pallas)
    timed(
        "unit_dense_pallas",
        started,
        ok=not differ and counts_equal,
        differ=differ,
        shape=[num_slots, num_docs, unit_capacity],
        ops=applied,
        units=int(jnp.sum(pallas.length)),
        tombstones=int(jnp.sum(pallas.deleted)),
    )
    del scan, pallas

    # -- RLE arena: dense Pallas vs scan ------------------------------------
    started = time.perf_counter()
    next_clock = np.zeros((2, num_docs), np.int64)
    scan = make_empty_rle_state(num_docs, rle_entries)
    pallas = make_empty_rle_state(num_docs, rle_entries)
    for _ in range(2):
        ops = _on_device(random_op_stream(rng, num_slots, next_clock))
        scan, _count = integrate_op_slots_rle(scan, ops)
        pallas, _count = integrate_op_slots_rle_pallas(pallas, ops, interpret=interpret)
    differ = _differing(scan, pallas)
    timed(
        "rle_dense_pallas",
        started,
        ok=not differ,
        differ=differ,
        shape=[num_slots, num_docs, rle_entries],
        entries=int(jnp.sum(pallas.num_runs)),
    )

    # -- RLE arena: sparse Pallas vs sparse scan, on the same rows -----------
    started = time.perf_counter()
    rows = np.sort(rng.choice(num_docs, sparse_width, replace=False)).astype(np.int32)
    sub_clock = next_clock[:, rows].copy()
    ops = _on_device(random_op_stream(rng, num_slots, sub_clock))
    slots = jnp.asarray(rows)
    scan, _count = integrate_op_slots_rle_sparse(scan, ops, slots)
    pallas, _count = integrate_op_slots_rle_sparse_pallas(
        pallas, ops, slots, interpret=interpret
    )
    differ = _differing(scan, pallas)
    timed(
        "rle_sparse_pallas",
        started,
        ok=not differ,
        differ=differ,
        shape=[num_slots, sparse_width, rle_entries],
    )
    del scan

    # -- RLE catch-up pack vs the host delete-range extraction ---------------
    started = time.perf_counter()
    before = _host(pallas)
    width = min(128, rle_entries)
    fused = np.asarray(catchup_pack_rle(pallas, slots, width))
    counts = fused[:sparse_width]
    body = fused[sparse_width:].reshape(3, sparse_width, width)
    wrong = []
    packed_rows = 0
    for i, row in enumerate(rows):
        n = int(counts[i])
        if n > width:
            continue  # the host falls back to the full-row read
        packed_rows += 1
        raw = sorted(
            zip(body[0, i, :n].tolist(), body[1, i, :n].tolist(), body[2, i, :n].tolist())
        )
        # the same sort + merge the serve path applies to a packed readback
        if PlaneServing._merge_ranges(raw) != delete_ranges(before, int(row)):
            wrong.append(int(row))
    timed(
        "rle_catchup_pack",
        started,
        ok=not wrong and packed_rows > 0,
        wrong_rows=wrong,
        packed_rows=packed_rows,
        tombstone_entries=int(counts.sum()),
    )

    # -- RLE compact: unit expansion unchanged, entries not more -------------
    started = time.perf_counter()
    pallas, packed = compact_doc_rows_rle(pallas, slots)
    packed = np.asarray(packed)
    after = _host(pallas)
    wrong = []
    for i, row in enumerate(rows):
        row = int(row)
        same = all(
            np.array_equal(a, b)
            for a, b in zip(expand_to_units(before, row), expand_to_units(after, row))
        )
        if not same or packed[i] > before.num_runs[row] or packed[i] != after.num_runs[row]:
            wrong.append(row)
    untouched = np.setdiff1d(np.arange(num_docs), rows)
    moved = [
        name
        for name, a, b in zip(before._fields, before, after)
        if not np.array_equal(a[untouched], b[untouched])
    ]
    timed(
        "rle_compact",
        started,
        ok=not wrong and not moved,
        wrong_rows=wrong,
        other_rows_changed=moved,
        entries_before=int(before.num_runs[rows].sum()),
        entries_after=int(after.num_runs[rows].sum()),
    )
    del pallas, before, after

    # -- RLE run-append vs the scan on the same chain ------------------------
    started = time.perf_counter()
    (client, clock, run_len), chain = _chained_appends(num_slots, sparse_width)
    fast = make_empty_rle_state(num_docs, rle_entries)
    scan = make_empty_rle_state(num_docs, rle_entries)
    fast, applied_runs = append_run_slots_rle_sparse(
        fast, jnp.asarray(client), jnp.asarray(clock), jnp.asarray(run_len), slots
    )
    scan, _count = integrate_op_slots_rle_sparse(
        scan, _on_device(chain), slots
    )
    fast_host, scan_host = _host(fast), _host(scan)
    wrong = [
        int(row)
        for row in rows
        if not all(
            np.array_equal(a, b)
            for a, b in zip(
                expand_to_units(fast_host, int(row)),
                expand_to_units(scan_host, int(row)),
            )
        )
    ]
    timed(
        "rle_append",
        started,
        ok=not wrong and int(applied_runs) == num_slots * sparse_width,
        wrong_rows=wrong,
        runs=int(applied_runs),
        units=int(fast_host.total_units.sum()),
    )
    report["device"] = str(jax.devices()[0])
    return report
