"""Pallas RLE integrate kernel vs the vmapped XLA reference path.

Runs in Pallas interpret mode on the virtual CPU backend (conftest);
the identical kernel code compiles via Mosaic on real TPU
(test_chip_preflight.py AOT, chip_checks.py on the chip). Exact array equality is required: both paths apply the
same op sequence with the same append discipline, so every entry lane
must match, not just the expanded unit order.
"""

import numpy as np

from hocuspocus_tpu.tpu.kernels import NONE_CLIENT, OpBatch
from hocuspocus_tpu.tpu.kernels_rle import (
    integrate_op_slots_rle,
    make_empty_rle_state,
)
from hocuspocus_tpu.tpu.pallas_kernels_rle import (
    _pick_block_rle,
    integrate_op_slots_rle_pallas,
)

from tests.tpu.test_pallas_kernels import _CLIENTS, _random_stream


def test_pallas_rle_matches_xla_scan_fuzz():
    rng = np.random.default_rng(11)
    num_docs, entries, num_slots = 16, 128, 6
    next_clock = np.zeros((len(_CLIENTS), num_docs), np.int64)
    state_a = make_empty_rle_state(num_docs, entries)
    state_b = make_empty_rle_state(num_docs, entries)
    for _ in range(3):
        ops = _random_stream(rng, num_docs, num_slots, next_clock)
        state_a, ca = integrate_op_slots_rle(state_a, ops)
        state_b, cb = integrate_op_slots_rle_pallas(state_b, ops, interpret=True)
        assert int(ca) == int(cb)
    for name, a, b in zip(state_a._fields, state_a, state_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_pallas_rle_overflow_and_deps():
    """Entry-capacity overflow and missing-origin ops behave identically."""
    import jax.numpy as jnp

    num_docs, entries = 8, 4
    state_a = make_empty_rle_state(num_docs, entries)
    state_b = make_empty_rle_state(num_docs, entries)
    mk = lambda arr, dt: jnp.asarray(np.asarray(arr, dt))
    # slots: 3 tail appends fit the 4-entry arena (num_runs+2<=4 holds
    # through num_runs=2); the 4th op then fails BOTH the capacity
    # margin (3+2>4 => sticky overflow) and its unknown left origin
    kind = mk([[1] * num_docs] * 4, np.int32)
    client = mk([[7] * num_docs] * 4, np.uint32)
    clock = mk([[0] * num_docs, [8] * num_docs, [16] * num_docs, [99] * num_docs], np.int32)
    run_len = mk([[8] * num_docs, [8] * num_docs, [8] * num_docs, [1] * num_docs], np.int32)
    lc = mk(
        [[NONE_CLIENT] * num_docs, [7] * num_docs, [7] * num_docs, [12345] * num_docs],
        np.uint32,
    )
    lk = mk([[0] * num_docs, [7] * num_docs, [15] * num_docs, [0] * num_docs], np.int32)
    rc = mk([[NONE_CLIENT] * num_docs] * 4, np.uint32)
    rk = mk([[0] * num_docs] * 4, np.int32)
    ops = OpBatch(kind, client, clock, run_len, lc, lk, rc, rk)
    state_a, _ = integrate_op_slots_rle(state_a, ops)
    state_b, _ = integrate_op_slots_rle_pallas(state_b, ops, interpret=True)
    for name, a, b in zip(state_a._fields, state_a, state_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    assert bool(np.asarray(state_b.overflow).all())  # 4th insert overflowed
    assert (np.asarray(state_b.total_units) == 24).all()  # 3 applied, 4th skipped
    assert (np.asarray(state_b.num_runs) == 3).all()


def test_pick_block_rle_respects_vmem():
    from hocuspocus_tpu.tpu.pallas_kernels_rle import _LIVE_BUFFERS, _VMEM_BUDGET

    assert _pick_block_rle(8192, 1024) == 64
    assert _pick_block_rle(7, 1024) == 0
    for docs, entries in ((8192, 1024), (100_000, 2048), (2048, 16384)):
        db = _pick_block_rle(docs, entries)
        if db:
            assert _LIVE_BUFFERS * db * entries * 4 <= _VMEM_BUDGET
