"""Pallas integrate kernel vs the XLA-scan reference path.

Runs in Pallas interpret mode on the virtual CPU backend (conftest);
the identical kernel code compiles via Mosaic on real TPU — AOT for a
v5e in test_chip_preflight.py, run and checked by chip_smoke.py.
"""

import numpy as np
import pytest

from hocuspocus_tpu.tpu.kernels import (
    NONE_CLIENT,
    OpBatch,
    integrate_op_slots,
    make_empty_state,
)
from hocuspocus_tpu.tpu.pallas_kernels import _pick_block, integrate_op_slots_pallas


# one client below 2^31 and one above: same-origin concurrent inserts
# from these two exercise the YATA client-id tiebreak as an UNSIGNED
# compare (a signed compare would order them the other way round)
_CLIENTS = (7, 0x9000_0001)


def _random_stream(rng, num_docs, num_slots, next_clock):
    """Causally-valid two-client op stream with random origins.

    next_clock has shape (num_clients, num_docs).
    """
    import jax.numpy as jnp

    kind = rng.integers(0, 3, size=(num_slots, num_docs)).astype(np.int32)
    client = np.full((num_slots, num_docs), _CLIENTS[0], np.uint32)
    clock = np.zeros((num_slots, num_docs), np.int32)
    run_len = rng.integers(1, 9, size=(num_slots, num_docs)).astype(np.int32)
    lc = np.full((num_slots, num_docs), NONE_CLIENT, np.uint32)
    lk = np.zeros((num_slots, num_docs), np.int32)
    rc = np.full((num_slots, num_docs), NONE_CLIENT, np.uint32)
    rk = np.zeros((num_slots, num_docs), np.int32)
    for k in range(num_slots):
        for d in range(num_docs):
            ci = rng.integers(0, len(_CLIENTS))
            if kind[k, d] == 1:
                client[k, d] = _CLIENTS[ci]
                clock[k, d] = next_clock[ci, d]
                known = [(i, c) for i, c in enumerate(next_clock[:, d]) if c > 0]
                if known:
                    oi, oc = known[rng.integers(0, len(known))]
                    lc[k, d] = _CLIENTS[oi]
                    lk[k, d] = rng.integers(0, oc)
                    if rng.random() < 0.3:
                        ri, rcl = known[rng.integers(0, len(known))]
                        rc[k, d] = _CLIENTS[ri]
                        rk[k, d] = rng.integers(0, rcl)
                next_clock[ci, d] += run_len[k, d]
            elif kind[k, d] == 2:
                if next_clock[ci, d] == 0:
                    kind[k, d] = 0
                else:
                    client[k, d] = _CLIENTS[ci]
                    clock[k, d] = rng.integers(0, next_clock[ci, d])
                    run_len[k, d] = min(
                        run_len[k, d], next_clock[ci, d] - clock[k, d]
                    )
    return OpBatch(*map(jnp.asarray, (kind, client, clock, run_len, lc, lk, rc, rk)))


def test_pallas_matches_xla_scan_fuzz():
    rng = np.random.default_rng(7)
    num_docs, capacity, num_slots = 16, 256, 6
    next_clock = np.zeros((len(_CLIENTS), num_docs), np.int64)
    state_a = make_empty_state(num_docs, capacity)
    state_b = make_empty_state(num_docs, capacity)
    for _ in range(3):
        ops = _random_stream(rng, num_docs, num_slots, next_clock)
        state_a, ca = integrate_op_slots(state_a, ops)
        state_b, cb = integrate_op_slots_pallas(state_b, ops, interpret=True)
        assert int(ca) == int(cb)
    for name, a, b in zip(state_a._fields, state_a, state_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_pallas_overflow_and_deps():
    """Capacity overflow and missing-origin ops behave like the XLA path."""
    import jax.numpy as jnp

    num_docs, capacity = 8, 32
    state_a = make_empty_state(num_docs, capacity)
    state_b = make_empty_state(num_docs, capacity)
    mk = lambda arr, dt: jnp.asarray(np.asarray(arr, dt))
    # slot 0: fits; slot 1: overflows; slot 2: unknown left origin
    kind = mk([[1] * num_docs, [1] * num_docs, [1] * num_docs], np.int32)
    client = mk([[7] * num_docs] * 3, np.uint32)
    clock = mk([[0] * num_docs, [30] * num_docs, [99] * num_docs], np.int32)
    run_len = mk([[30] * num_docs, [30] * num_docs, [1] * num_docs], np.int32)
    lc = mk([[NONE_CLIENT] * num_docs, [7] * num_docs, [12345] * num_docs], np.uint32)
    lk = mk([[0] * num_docs, [0] * num_docs, [0] * num_docs], np.int32)
    rc = mk([[NONE_CLIENT] * num_docs] * 3, np.uint32)
    rk = mk([[0] * num_docs] * 3, np.int32)
    ops = OpBatch(kind, client, clock, run_len, lc, lk, rc, rk)
    state_a, _ = integrate_op_slots(state_a, ops)
    state_b, _ = integrate_op_slots_pallas(state_b, ops, interpret=True)
    for name, a, b in zip(state_a._fields, state_a, state_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    assert bool(np.asarray(state_b.overflow).all())
    assert (np.asarray(state_b.length) == 30).all()  # dep-missing op skipped


def test_pick_block_respects_vmem():
    from hocuspocus_tpu.tpu.pallas_kernels import (
        _LIVE_BUFFERS,
        _VMEM_BUDGET,
        _VMEM_LIMIT,
    )

    assert _pick_block(8192, 2048) == 64
    assert _pick_block(8192, 32768) == 16  # huge arenas shrink the block
    assert _pick_block(7, 2048) == 0  # indivisible doc counts fall back
    # the chosen block's modeled footprint must fit the compiler cap we
    # actually request, or Mosaic rejects the kernel at compile time
    for docs, cap in ((8192, 5632), (8192, 2048), (100_000, 5632), (2048, 32768)):
        db = _pick_block(docs, cap)
        if db:
            assert _LIVE_BUFFERS * db * cap * 4 <= _VMEM_BUDGET <= _VMEM_LIMIT


def test_pick_block_model_covers_r02_oom_shape():
    """Regression for the round-2 Mosaic VMEM OOM at the bench shape.

    The driver bench ran docs=8192, capacity=5632, K=64; Mosaic measured
    a 19.68MB scoped allocation at db=32 — i.e. ~27.3 live (db, N) int32
    buffers — while the old model assumed 12 and the old budget was 14MB
    under a 16MB cap. Pin the model to that measurement: at the OOM
    shape the modeled footprint of db=32 must be >= the observed 19.68MB
    (so an optimistic model can't sneak back in), and the picked block's
    footprint must stay under the requested compiler cap.
    """
    from hocuspocus_tpu.tpu.pallas_kernels import _LIVE_BUFFERS, _VMEM_LIMIT

    observed_oom_bytes = 19_680_000  # "Scoped allocation with size 19.68M"
    assert _LIVE_BUFFERS * 32 * 5632 * 4 >= observed_oom_bytes
    db = _pick_block(8192, 5632)
    assert db > 0, "bench shape must stay on the Pallas path"
    assert _LIVE_BUFFERS * db * 5632 * 4 <= _VMEM_LIMIT


@pytest.mark.parametrize(
    "module, jitted, entry, sparse",
    [
        ("pallas_kernels", "_integrate_pallas", "integrate_op_slots_pallas", False),
        ("pallas_kernels", "_integrate_sparse_pallas", "integrate_op_slots_sparse_pallas", True),
        ("pallas_kernels_rle", "_integrate_pallas_rle", "integrate_op_slots_rle_pallas", False),
        (
            "pallas_kernels_rle",
            "_integrate_sparse_pallas_rle",
            "integrate_op_slots_rle_sparse_pallas",
            True,
        ),
    ],
)
def test_mosaic_failure_propagates(monkeypatch, module, jitted, entry, sparse):
    """A kernel that does not compile RAISES out of every Pallas entry
    point: no per-shape rescue onto the XLA scan hides the device. The
    flush-fault rail (cpu_fallbacks) is what keeps the server up."""
    import importlib

    from hocuspocus_tpu.tpu.kernels_rle import make_empty_rle_state

    mod = importlib.import_module(f"hocuspocus_tpu.tpu.{module}")

    def boom(*args):
        raise RuntimeError("Mosaic says no (simulated VMEM OOM)")

    monkeypatch.setattr(mod, jitted, boom)
    num_docs = 64
    state = (
        make_empty_rle_state(num_docs, 64)
        if module.endswith("rle")
        else make_empty_state(num_docs, 256)
    )
    width = 16 if sparse else num_docs
    ops = OpBatch(
        kind=np.ones((2, width), np.int32),
        client=np.full((2, width), 7, np.uint32),
        clock=np.asarray([[0] * width, [4] * width], np.int32),
        run_len=np.full((2, width), 4, np.int32),
        left_client=np.asarray([[NONE_CLIENT] * width, [7] * width], np.uint32),
        left_clock=np.asarray([[0] * width, [3] * width], np.int32),
        right_client=np.full((2, width), NONE_CLIENT, np.uint32),
        right_clock=np.zeros((2, width), np.int32),
    )
    args = (state, ops, np.arange(width, dtype=np.int32)) if sparse else (state, ops)
    for _ in range(2):  # and it keeps raising: no shape is remembered as broken
        with pytest.raises(RuntimeError, match="Mosaic says no"):
            getattr(mod, entry)(*args)


def test_sharded_pallas_step_matches_xla():
    """shard_map(pallas) over a doc-only mesh == XLA sharded step."""
    import jax
    import numpy as np

    from hocuspocus_tpu.tpu.sharding import (
        make_mesh,
        make_sharded_state,
        make_sharded_step,
        ops_sharding,
    )

    assert len(jax.devices()) == 8
    mesh = make_mesh(doc_axis=8)  # doc-only: unit axis size 1
    num_docs, capacity, num_slots = 64, 128, 4

    rng = np.random.default_rng(3)
    next_clock = np.zeros((len(_CLIENTS), num_docs), np.int64)
    ops = _random_stream(rng, num_docs, num_slots, next_clock)
    op_shards = ops_sharding(mesh)
    ops = type(ops)(*(jax.device_put(f, s) for f, s in zip(ops, op_shards)))

    state_x = make_sharded_state(mesh, num_docs, capacity)
    step_x = make_sharded_step(mesh, use_pallas=False)
    state_x, count_x = step_x(state_x, ops)

    state_p = make_sharded_state(mesh, num_docs, capacity)
    step_p = make_sharded_step(mesh, use_pallas=True, interpret=True)
    state_p, count_p = step_p(state_p, ops)

    assert int(count_x) == int(count_p)
    for name, a, b in zip(state_x._fields, state_x, state_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
