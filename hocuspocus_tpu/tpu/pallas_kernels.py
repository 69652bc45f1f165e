"""Pallas TPU kernel for the batched CRDT integrate step.

The XLA-scan path (kernels.integrate_op_slots) re-reads and re-writes
every (D, N) state array from HBM once per op slot — K slots means K
full passes over ~20 bytes/unit of arena state. This kernel instead
grids over doc blocks and keeps each block's arena resident in VMEM
while a fori_loop applies all K op slots, so HBM sees exactly one read
and one write of the state per flush regardless of K. The YATA math per
op is identical to kernels._integrate_one (reference semantics:
`/root/reference/packages/server/src/MessageReceiver.ts` readUpdate →
yjs Item.integrate), restated over (DB, N) blocks.

Client ids are uint32 at the API boundary; inside the kernel they are
int32 bit patterns (equality is bit-equality; the single ordered
compare — the YATA client-id tiebreak — uses the sign-bias trick).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernels import KIND_DELETE, KIND_INSERT, DocState, OpBatch

_INF = 0x7FFFFFFF  # plain ints: jnp scalars would be captured consts
_SIGN = -0x80000000
_NONE = -1  # NONE_CLIENT (0xFFFFFFFF) as an int32 bit pattern


def _integrate_block_kernel(
    # ops (DB, K) int32 — doc-major so the K axis is the (full) lane
    # dim, satisfying Mosaic's block-shape rule for any K
    kind_ref,
    client_ref,
    clock_ref,
    run_len_ref,
    left_client_ref,
    left_clock_ref,
    right_client_ref,
    right_clock_ref,
    # state (DB, N) int32 / (DB, 1) int32 — aliased in/out
    idc_ref,
    idk_ref,
    rank_ref,
    orank_ref,
    del_ref,
    len_ref,
    ovf_ref,
    # outputs (aliases of the state refs)
    idc_out,
    idk_out,
    rank_out,
    orank_out,
    del_out,
    len_out,
    ovf_out,
    *,
    num_slots: int,
):
    db, n = idc_ref.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (db, n), 1)

    # load the op columns once; extract column k inside the loop with a
    # broadcast-compare + row-sum (dynamic lane slices don't tile on
    # TPU, and a static unroll would blow the VMEM stack with per-
    # iteration temporaries)
    lane = jax.lax.broadcasted_iota(jnp.int32, (db, num_slots), 1)
    all_kind = kind_ref[:]
    all_client = client_ref[:]
    all_clock = clock_ref[:]
    all_run = run_len_ref[:]
    all_lc = left_client_ref[:]
    all_lk = left_clock_ref[:]
    all_rc = right_client_ref[:]
    all_rk = right_clock_ref[:]

    def apply_op(k, _):
        sel = lane == k

        def col(vals, none=0):
            return jnp.sum(jnp.where(sel, vals, none), axis=1, keepdims=True)

        op_kind = col(all_kind)
        op_client = col(all_client)
        op_clock = col(all_clock)
        run = col(all_run)
        lc = col(all_lc)
        lk = col(all_lk)
        rc = col(all_rc)
        rk = col(all_rk)

        idc = idc_out[:]
        idk = idk_out[:]
        rank = rank_out[:]
        orank = orank_out[:]
        dele = del_out[:]
        length = len_out[:]
        ovf = ovf_out[:]

        occupied = idx < length

        # resolve origin ids to ranks (masked row reductions); found-ness
        # falls out of the max (occupied ranks are >= 0), saving two
        # any-reductions per op
        is_left = occupied & (idc == lc) & (idk == lk)
        has_left = lc != _NONE
        left_raw = jnp.max(jnp.where(is_left, rank, -1), axis=1, keepdims=True)
        left_found = left_raw >= 0
        left_rank = jnp.where(has_left, left_raw, -1)
        is_right = occupied & (idc == rc) & (idk == rk)
        has_right = rc != _NONE
        right_raw = jnp.max(jnp.where(is_right, rank, -1), axis=1, keepdims=True)
        right_found = right_raw >= 0
        right_rank = jnp.where(has_right, right_raw, length)

        # YATA conflict scan over the (left, right) rank window
        in_window = occupied & (rank > left_rank) & (rank < right_rank)
        client_lt = (idc ^ _SIGN) < (op_client ^ _SIGN)  # unsigned compare
        skip_cond = (orank > left_rank) | ((orank == left_rank) & client_lt)
        blocked = in_window & ~skip_cond
        first_block = jnp.min(
            jnp.where(blocked, rank, _INF), axis=1, keepdims=True
        )
        skipped = jnp.sum(
            (in_window & (rank < first_block)).astype(jnp.int32),
            axis=1,
            keepdims=True,
        )
        ins_rank = left_rank + 1 + skipped

        fits = length + run <= n
        deps_ok = (~has_left | left_found) & (~has_right | right_found)
        do_insert = (op_kind == KIND_INSERT) & fits & deps_ok

        # elementwise insert: bump ranks, fill the appended slots
        bump = do_insert & occupied
        rank_b = jnp.where(bump & (rank >= ins_rank), rank + run, rank)
        orank_b = jnp.where(bump & (orank >= ins_rank), orank + run, orank)
        slot_off = idx - length
        in_new = do_insert & (slot_off >= 0) & (slot_off < run)
        is_first = slot_off == 0

        idc_out[:] = jnp.where(in_new, op_client, idc)
        idk_out[:] = jnp.where(in_new, op_clock + slot_off, idk)
        rank_out[:] = jnp.where(in_new, ins_rank + slot_off, rank_b)
        orank_out[:] = jnp.where(
            in_new, jnp.where(is_first, left_rank, ins_rank + slot_off - 1), orank_b
        )

        # delete: id-range tombstones
        in_del = (
            (op_kind == KIND_DELETE)
            & occupied
            & (idc == op_client)
            & (idk >= op_clock)
            & (idk < op_clock + run)
        )
        del_out[:] = jnp.where(in_new, 0, dele) | in_del.astype(jnp.int32)

        len_out[:] = jnp.where(do_insert, length + run, length)
        ovf_out[:] = ovf | ((op_kind == KIND_INSERT) & ~fits).astype(jnp.int32)
        return 0

    # copy aliased inputs through once, then iterate in VMEM
    idc_out[:] = idc_ref[:]
    idk_out[:] = idk_ref[:]
    rank_out[:] = rank_ref[:]
    orank_out[:] = orank_ref[:]
    del_out[:] = del_ref[:]
    len_out[:] = len_ref[:]
    ovf_out[:] = ovf_ref[:]
    jax.lax.fori_loop(0, num_slots, apply_op, 0)


# Mosaic's default scoped-VMEM cap is 16MB; a v5e core has 128MB of
# physical VMEM. We raise the cap and keep our own budget under it so
# the block choice — not the compiler's default — is the binding limit.
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_BUDGET = 96 * 1024 * 1024

# Measured live set of the block kernel, in (db, N) int32 buffers: the
# 5 aliased arena outputs, their 5 re-reads inside apply_op, plus
# Mosaic's per-iteration temporaries for the masked reductions and the
# elementwise rewrite (~17 more). r02's OOM pinned this empirically:
# "scoped allocation 19.68M" at db=32, N=5632 => 19.68e6/(32*5632*4)
# ~ 27.3 buffers. 28 gives margin; tests/tpu/test_pallas_kernels.py
# asserts the model against that shape so a regression fails in CI.
# Shapes the model's choice has been checked at on a v5e since: db=16
# and 64 at N=5632 (every run of the benchmark's 10 KB cells), and
# db=8 at N=106,496, 95.4 MB of the budget and 19 times the measured
# shape: Mosaic compiles it and the warm grid runs it on the chip at
# B=16, 64, 256 and the dense 448 rows (PERF.md, PR 34; pinned in
# tests/tpu/test_long_rows.py). Past ~112k units a row no block fits
# and every width takes the scan.
_LIVE_BUFFERS = 28


def _pick_block(num_docs: int, capacity: int = 2048) -> int:
    """Largest doc-block that divides D and fits VMEM.

    Budget model: ~_LIVE_BUFFERS live (db, N) int32 buffers (see above;
    op blocks are (db, K) with K<=64 — noise by comparison). Measured
    best on v5e at N=2048 is db=64 (HBM-pass-bound beyond).
    """
    for db in (64, 32, 16, 8):
        if num_docs % db == 0 and _LIVE_BUFFERS * db * capacity * 4 <= _VMEM_BUDGET:
            return db
    return 0


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _integrate_pallas(state: DocState, ops: OpBatch, interpret: bool):
    """Layout conversion + pallas_call as ONE jitted program.

    Doing the int32 views, the (K, D) -> doc-major transposes, and the
    bool conversions inside the jit lets XLA fuse them into the kernel's
    input pipeline instead of dispatching ~15 eager ops per flush; the
    count is also produced here so callers get a single program whose
    outputs all depend on the device step.
    """
    idc = state.id_client.view(jnp.int32)
    idk = state.id_clock
    rank = state.rank
    orank = state.origin_rank
    dele = state.deleted.astype(jnp.int32)
    length = state.length[:, None]
    ovf = state.overflow.astype(jnp.int32)[:, None]
    ops_i32 = (  # (K, D) -> doc-major (D, K) for lane-dim K blocks
        ops.kind.T,
        ops.client.view(jnp.int32).T,
        ops.clock.T,
        ops.run_len.T,
        ops.left_client.view(jnp.int32).T,
        ops.left_clock.T,
        ops.right_client.view(jnp.int32).T,
        ops.right_clock.T,
    )
    num_docs, capacity = idc.shape
    num_slots = ops_i32[0].shape[1]
    db = _pick_block(num_docs, capacity)

    grid = (num_docs // db,)
    op_spec = pl.BlockSpec((db, num_slots), lambda i: (i, 0), memory_space=pltpu.VMEM)
    arena_spec = pl.BlockSpec((db, capacity), lambda i: (i, 0), memory_space=pltpu.VMEM)
    scalar_spec = pl.BlockSpec((db, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        functools.partial(_integrate_block_kernel, num_slots=num_slots),
        grid=grid,
        in_specs=[op_spec] * 8 + [arena_spec] * 5 + [scalar_spec] * 2,
        out_specs=tuple([arena_spec] * 5 + [scalar_spec] * 2),
        out_shape=tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype)
            for a in (idc, idk, rank, orank, dele, length, ovf)
        ),
        # state tensors update in place (inputs 8..14 -> outputs 0..6)
        input_output_aliases={8 + i: i for i in range(7)},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*ops_i32, idc, idk, rank, orank, dele, length, ovf)
    idc, idk, rank, orank, dele, length, ovf = out
    from .kernels import KIND_NOOP

    new_state = DocState(
        id_client=idc.view(jnp.uint32),
        id_clock=idk,
        rank=rank,
        origin_rank=orank,
        deleted=dele.astype(bool),
        length=length[:, 0],
        overflow=ovf[:, 0].astype(bool),
    )
    count = jnp.sum(ops.kind != KIND_NOOP)
    # tie the count to a kernel output so fetching it is a completion
    # barrier for the integrate step by DATA DEPENDENCE, not by runtime
    # program-atomicity assumptions
    count, _ = jax.lax.optimization_barrier((count, new_state.length))
    return new_state, count


def integrate_op_slots_pallas(
    state: DocState, ops: OpBatch, *, interpret: bool = False
) -> tuple[DocState, jax.Array]:
    """Drop-in equivalent of kernels.integrate_op_slots via Pallas.

    Ops fields have shape (K, D). Takes the XLA scan path when the doc
    count has no valid block factor — a shape decision, made before any
    compile. A Mosaic compile or launch failure RAISES: the caller's
    flush-fault rail (TpuMergeExtension._degrade_all_served, counted in
    cpu_fallbacks) keeps the server available and the failure visible.
    """
    from .kernels import integrate_op_slots

    if _pick_block(state.id_client.shape[0], state.id_client.shape[1]) == 0:
        return integrate_op_slots(state, ops)
    return _integrate_pallas(state, ops, interpret)


def integrate_op_slots_fast(state: DocState, ops: OpBatch) -> tuple[DocState, jax.Array]:
    """Backend dispatcher: Pallas on TPU, XLA scan elsewhere."""
    from .kernels import integrate_op_slots

    if jax.default_backend() == "tpu":
        return integrate_op_slots_pallas(state, ops)
    return integrate_op_slots(state, ops)


# -- sparse (busy-doc) dispatch ----------------------------------------------


@functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
def _integrate_sparse_pallas(state: DocState, ops: OpBatch, slots, interpret: bool):
    """Gather the B busy rows, run the VMEM-resident block kernel over
    the (B, N) sub-arena, scatter back in place — one jitted program, so
    XLA fuses the gather into the kernel's input pipeline and aliases
    the (D, N) arenas through the scatter (the state is donated)."""
    from .kernels import gather_doc_rows, scatter_doc_rows

    sub = gather_doc_rows(state, slots)
    sub, count = _integrate_pallas.__wrapped__(sub, ops, interpret)
    state = scatter_doc_rows(state, sub, slots)
    count, _ = jax.lax.optimization_barrier((count, state.length))
    return state, count


def integrate_op_slots_sparse_pallas(
    state: DocState, ops: OpBatch, slots, *, interpret: bool = False
) -> tuple[DocState, jax.Array]:
    """Sparse dispatch via Pallas; ops fields are (K, B), slots (B,).

    Takes the sparse XLA scan when no doc-block both divides B and
    fits VMEM at this row length: B < 8 at any length, every B once a
    row is too long for a block of 8 (past ~112k units; a 106,496-unit
    row still runs the kernel at db=8). A Mosaic failure raises (see
    integrate_op_slots_pallas)."""
    from .kernels import integrate_op_slots_sparse

    if _pick_block(int(slots.shape[0]), state.id_client.shape[1]) == 0:
        return integrate_op_slots_sparse(state, ops, slots)
    return _integrate_sparse_pallas(state, ops, slots, interpret)


def integrate_op_slots_sparse_fast(
    state: DocState, ops: OpBatch, slots
) -> tuple[DocState, jax.Array]:
    """Backend dispatcher for the sparse step: Pallas on TPU, XLA scan
    elsewhere."""
    from .kernels import integrate_op_slots_sparse

    if jax.default_backend() == "tpu":
        return integrate_op_slots_sparse_pallas(state, ops, slots)
    return integrate_op_slots_sparse(state, ops, slots)


# -- minimal-work run merge (sequential fast path) -----------------------------


def append_run_slots_sparse_fast(
    state: DocState, client, clock, run_len, slots
) -> tuple[DocState, jax.Array]:
    """Backend dispatcher for the run-append fast path.

    The integrate scan needs Mosaic because every op slot re-reads the
    whole (B, N) sub-arena from HBM — K passes of conflict scanning.
    The append program has no conflict scan at all: one fit pass over a
    (K,) carry and one fused masked fill of each gathered row, so the
    XLA lowering is already a single read + write of the touched rows
    on every backend. This wrapper keeps the plane's call seam uniform
    with the integrate/compact dispatchers so a future VMEM-resident
    variant slots in without touching the plane."""
    from .kernels import append_run_slots_sparse

    return append_run_slots_sparse(state, client, clock, run_len, slots)


# -- on-device compaction ------------------------------------------------------


def compact_doc_rows_fast(state: DocState, slots) -> tuple[DocState, jax.Array]:
    """Backend dispatcher for the compact (tombstone-GC) step, the seam
    the plane calls through like every other kernel entry point.

    Unlike the integrate hot loop — where the XLA scan re-reads the
    whole arena from HBM once per op slot and the VMEM-resident Mosaic
    kernel is the fix — compaction is a single-pass permutation
    (scatter + cumsum + gather) with no K-pass HBM amplification to
    kill, so the XLA lowering is already one read and one write of the
    gathered rows on every backend. A handwritten Mosaic kernel would
    buy nothing here; this wrapper exists so a future VMEM-resident
    variant slots in without touching the plane."""
    from .kernels import compact_doc_rows

    return compact_doc_rows(state, slots)
