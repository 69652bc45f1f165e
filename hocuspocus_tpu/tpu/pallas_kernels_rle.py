"""Pallas TPU kernel for the run-length batched CRDT integrate step.

Same VMEM-residency strategy as `pallas_kernels.py` (grid over doc
blocks, arena resident in VMEM while a fori_loop applies all K op
slots, one HBM read + one write per flush), restated over the
run-length arena of `kernels_rle.py`: one entry per RUN of
consecutively-typed units, so a busy doc's arena cost grows with op
count + fragmentation instead of cumulative unit count.

The op semantics are identical to kernels_rle._integrate_one_rle
(yjs Item.integrate / readUpdate semantics — reference
`/root/reference/packages/server/src/MessageReceiver.ts`), expressed
as elementwise compares + masked row reductions over (DB, R) blocks.
Client ids are int32 bit patterns inside the kernel; the single
ordered compare (YATA client-id tiebreak) uses the sign-bias trick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernels import KIND_DELETE, KIND_INSERT, OpBatch
from .kernels_rle import RleState

_INF = 0x7FFFFFFF
_SIGN = -0x80000000
_NONE = -1  # NONE_CLIENT (0xFFFFFFFF) as an int32 bit pattern


def _rle_block_kernel(
    # ops (DB, K) int32, doc-major (K on the lane dim)
    kind_ref,
    client_ref,
    clock_ref,
    run_len_ref,
    left_client_ref,
    left_clock_ref,
    right_client_ref,
    right_clock_ref,
    # state (DB, R) int32 / (DB, 1) int32 — aliased in/out
    rcl_ref,
    rck_ref,
    rln_ref,
    rrk_ref,
    ror_ref,
    rdl_ref,
    nrn_ref,
    tot_ref,
    ovf_ref,
    # outputs (aliases)
    rcl_out,
    rck_out,
    rln_out,
    rrk_out,
    ror_out,
    rdl_out,
    nrn_out,
    tot_out,
    ovf_out,
    *,
    num_slots: int,
):
    db, r = rcl_ref.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (db, r), 1)
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

        rcl = rcl_out[:]
        rck = rck_out[:]
        rln = rln_out[:]
        rrk = rrk_out[:]
        ror = ror_out[:]
        rdl = rdl_out[:]
        nrn = nrn_out[:]
        tot = tot_out[:]
        ovf = ovf_out[:]

        occupied = idx < nrn

        # -- resolve origin ids to UNIT ranks (range membership) -----------
        in_left = occupied & (rcl == lc) & (lk >= rck) & (lk < rck + rln)
        has_left = lc != _NONE
        left_raw = jnp.max(
            jnp.where(in_left, rrk + (lk - rck), -1), axis=1, keepdims=True
        )
        left_found = left_raw >= 0
        left_rank = jnp.where(has_left, left_raw, -1)
        in_right = occupied & (rcl == rc) & (rk >= rck) & (rk < rck + rln)
        has_right = rc != _NONE
        right_raw = jnp.max(
            jnp.where(in_right, rrk + (rk - rck), -1), axis=1, keepdims=True
        )
        right_found = right_raw >= 0
        right_rank = jnp.where(has_right, right_raw, tot)

        # -- YATA conflict scan over run heads -----------------------------
        # (see kernels_rle docstring: only run heads and the unit at
        # left_rank+1 inside a run can block)
        client_ge = ~((rcl ^ _SIGN) < (op_client ^ _SIGN))
        head_in_window = occupied & (rrk > left_rank) & (rrk < right_rank)
        head_blocked = head_in_window & (
            (ror < left_rank) | ((ror == left_rank) & client_ge)
        )
        succ = left_rank + 1
        succ_nonhead = (
            occupied & (rrk < succ) & (succ < rrk + rln) & (succ < right_rank)
        )
        succ_blocked = succ_nonhead & client_ge
        first_block = jnp.minimum(
            jnp.min(jnp.where(head_blocked, rrk, _INF), axis=1, keepdims=True),
            jnp.min(jnp.where(succ_blocked, succ, _INF), axis=1, keepdims=True),
        )
        ins_rank = jnp.minimum(first_block, right_rank)

        fits = nrn + 2 <= r
        deps_ok = (~has_left | left_found) & (~has_right | right_found)
        do_insert = (op_kind == KIND_INSERT) & fits & deps_ok

        # -- insert: split the straddled run -------------------------------
        inside = (
            do_insert & occupied & (rrk < ins_rank) & (ins_rank < rrk + rln)
        )
        any_split = jnp.any(inside, axis=1, keepdims=True)
        t_client = jnp.sum(jnp.where(inside, rcl, 0), axis=1, keepdims=True)
        t_clock = jnp.sum(
            jnp.where(inside, rck + (ins_rank - rrk), 0), axis=1, keepdims=True
        )
        t_len = jnp.sum(
            jnp.where(inside, rln - (ins_rank - rrk), 0), axis=1, keepdims=True
        )
        t_deleted = jnp.any(inside & (rdl != 0), axis=1, keepdims=True)
        rln = jnp.where(inside, ins_rank - rrk, rln)
        at = any_split & (idx == nrn)
        rcl = jnp.where(at, t_client, rcl)
        rck = jnp.where(at, t_clock, rck)
        rln = jnp.where(at, t_len, rln)
        rrk = jnp.where(at, ins_rank, rrk)
        ror = jnp.where(at, ins_rank - 1, ror)
        rdl = jnp.where(at, t_deleted.astype(jnp.int32), rdl)
        nrn = nrn + any_split.astype(jnp.int32)

        # -- bump ranks right of the insertion, append the new entry -------
        occupied2 = idx < nrn
        bump_rank = do_insert & occupied2 & (rrk >= ins_rank)
        bump_orank = do_insert & occupied2 & (ror >= ins_rank)
        rrk = jnp.where(bump_rank, rrk + run, rrk)
        ror = jnp.where(bump_orank, ror + run, ror)
        at2 = do_insert & (idx == nrn)
        rcl = jnp.where(at2, op_client, rcl)
        rck = jnp.where(at2, op_clock, rck)
        rln = jnp.where(at2, run, rln)
        rrk = jnp.where(at2, ins_rank, rrk)
        ror = jnp.where(at2, left_rank, ror)
        rdl = jnp.where(at2, 0, rdl)
        nrn = nrn + do_insert.astype(jnp.int32)
        tot = tot + jnp.where(do_insert, run, 0)
        ovf = ovf | ((op_kind == KIND_INSERT) & ~fits).astype(jnp.int32)

        # -- delete: split at both id boundaries, tombstone covered --------
        del_fits = nrn + 2 <= r
        do_delete = (op_kind == KIND_DELETE) & del_fits
        del_end = op_clock + run
        for bound in (op_clock, del_end):
            occ = idx < nrn
            ins_d = (
                do_delete
                & occ
                & (rcl == op_client)
                & (rck < bound)
                & (bound < rck + rln)
            )
            any_d = jnp.any(ins_d, axis=1, keepdims=True)
            d_rank = jnp.sum(
                jnp.where(ins_d, rrk + (bound - rck), 0), axis=1, keepdims=True
            )
            d_len = jnp.sum(
                jnp.where(ins_d, rln - (bound - rck), 0), axis=1, keepdims=True
            )
            d_deleted = jnp.any(ins_d & (rdl != 0), axis=1, keepdims=True)
            rln = jnp.where(ins_d, bound - rck, rln)
            at_d = any_d & (idx == nrn)
            rcl = jnp.where(at_d, op_client, rcl)
            rck = jnp.where(at_d, bound, rck)
            rln = jnp.where(at_d, d_len, rln)
            rrk = jnp.where(at_d, d_rank, rrk)
            ror = jnp.where(at_d, d_rank - 1, ror)
            rdl = jnp.where(at_d, d_deleted.astype(jnp.int32), rdl)
            nrn = nrn + any_d.astype(jnp.int32)
        occupied3 = idx < nrn
        covered = (
            do_delete
            & occupied3
            & (rcl == op_client)
            & (rck >= op_clock)
            & (rck + rln <= del_end)
        )
        rdl = rdl | covered.astype(jnp.int32)
        ovf = ovf | ((op_kind == KIND_DELETE) & ~del_fits).astype(jnp.int32)

        rcl_out[:] = rcl
        rck_out[:] = rck
        rln_out[:] = rln
        rrk_out[:] = rrk
        ror_out[:] = ror
        rdl_out[:] = rdl
        nrn_out[:] = nrn
        tot_out[:] = tot
        ovf_out[:] = ovf
        return 0

    rcl_out[:] = rcl_ref[:]
    rck_out[:] = rck_ref[:]
    rln_out[:] = rln_ref[:]
    rrk_out[:] = rrk_ref[:]
    ror_out[:] = ror_ref[:]
    rdl_out[:] = rdl_ref[:]
    nrn_out[:] = nrn_ref[:]
    tot_out[:] = tot_ref[:]
    ovf_out[:] = ovf_ref[:]
    jax.lax.fori_loop(0, num_slots, apply_op, 0)


# VMEM budget model (see pallas_kernels.py): the RLE kernel holds 6
# (db, R) arena buffers live (+ their rewrites and the masked-reduction
# temporaries inside apply_op). Counted generously at 40 live (db, R)
# int32 buffers until a chip-side measurement pins it tighter.
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_BUDGET = 96 * 1024 * 1024
_LIVE_BUFFERS = 40


def _pick_block_rle(num_docs: int, entries: int) -> int:
    for db in (64, 32, 16, 8):
        if num_docs % db == 0 and _LIVE_BUFFERS * db * entries * 4 <= _VMEM_BUDGET:
            return db
    return 0


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _integrate_pallas_rle(state: RleState, ops: OpBatch, interpret: bool):
    rcl = state.run_client.view(jnp.int32)
    rck = state.run_clock
    rln = state.run_len
    rrk = state.run_rank
    ror = state.run_orank
    rdl = state.run_deleted.astype(jnp.int32)
    nrn = state.num_runs[:, None]
    tot = state.total_units[:, None]
    ovf = state.overflow.astype(jnp.int32)[:, None]
    ops_i32 = (
        ops.kind.T,
        ops.client.view(jnp.int32).T,
        ops.clock.T,
        ops.run_len.T,
        ops.left_client.view(jnp.int32).T,
        ops.left_clock.T,
        ops.right_client.view(jnp.int32).T,
        ops.right_clock.T,
    )
    num_docs, entries = rcl.shape
    num_slots = ops_i32[0].shape[1]
    db = _pick_block_rle(num_docs, entries)

    grid = (num_docs // db,)
    op_spec = pl.BlockSpec((db, num_slots), lambda i: (i, 0), memory_space=pltpu.VMEM)
    arena_spec = pl.BlockSpec((db, entries), lambda i: (i, 0), memory_space=pltpu.VMEM)
    scalar_spec = pl.BlockSpec((db, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        functools.partial(_rle_block_kernel, num_slots=num_slots),
        grid=grid,
        in_specs=[op_spec] * 8 + [arena_spec] * 6 + [scalar_spec] * 3,
        out_specs=tuple([arena_spec] * 6 + [scalar_spec] * 3),
        out_shape=tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype)
            for a in (rcl, rck, rln, rrk, ror, rdl, nrn, tot, ovf)
        ),
        input_output_aliases={8 + i: i for i in range(9)},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*ops_i32, rcl, rck, rln, rrk, ror, rdl, nrn, tot, ovf)
    rcl, rck, rln, rrk, ror, rdl, nrn, tot, ovf = out
    from .kernels import KIND_NOOP

    new_state = RleState(
        run_client=rcl.view(jnp.uint32),
        run_clock=rck,
        run_len=rln,
        run_rank=rrk,
        run_orank=ror,
        run_deleted=rdl.astype(bool),
        num_runs=nrn[:, 0],
        total_units=tot[:, 0],
        overflow=ovf[:, 0].astype(bool),
    )
    count = jnp.sum(ops.kind != KIND_NOOP)
    # completion barrier by data dependence (see pallas_kernels.py)
    count, _ = jax.lax.optimization_barrier((count, new_state.total_units))
    return new_state, count


def integrate_op_slots_rle_pallas(
    state: RleState, ops: OpBatch, *, interpret: bool = False
):
    """Drop-in equivalent of kernels_rle.integrate_op_slots_rle via
    Pallas; takes the XLA scan path when no block factor fits. A Mosaic
    failure raises (see pallas_kernels.integrate_op_slots_pallas)."""
    from .kernels_rle import integrate_op_slots_rle

    if _pick_block_rle(state.run_client.shape[0], state.run_client.shape[1]) == 0:
        return integrate_op_slots_rle(state, ops)
    return _integrate_pallas_rle(state, ops, interpret)


def integrate_op_slots_rle_fast(state: RleState, ops: OpBatch):
    """Backend dispatcher: Pallas on TPU, XLA scan elsewhere."""
    from .kernels_rle import integrate_op_slots_rle

    if jax.default_backend() == "tpu":
        return integrate_op_slots_rle_pallas(state, ops)
    return integrate_op_slots_rle(state, ops)


# -- sparse (busy-doc) dispatch ----------------------------------------------


@functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
def _integrate_sparse_pallas_rle(state: RleState, ops: OpBatch, slots, interpret: bool):
    """RLE twin of pallas_kernels._integrate_sparse_pallas: gather the
    B busy rows, run the block kernel over the (B, R) sub-arena,
    scatter back into the donated full state."""
    from .kernels import gather_doc_rows, scatter_doc_rows

    sub = gather_doc_rows(state, slots)
    sub, count = _integrate_pallas_rle.__wrapped__(sub, ops, interpret)
    state = scatter_doc_rows(state, sub, slots)
    count, _ = jax.lax.optimization_barrier((count, state.total_units))
    return state, count


def integrate_op_slots_rle_sparse_pallas(
    state: RleState, ops: OpBatch, slots, *, interpret: bool = False
):
    """Sparse RLE dispatch via Pallas; takes the sparse XLA scan when B
    has no valid block factor. A Mosaic failure raises."""
    from .kernels_rle import integrate_op_slots_rle_sparse

    if _pick_block_rle(int(slots.shape[0]), state.run_client.shape[1]) == 0:
        return integrate_op_slots_rle_sparse(state, ops, slots)
    return _integrate_sparse_pallas_rle(state, ops, slots, interpret)


def integrate_op_slots_rle_sparse_fast(state: RleState, ops: OpBatch, slots):
    """Backend dispatcher for the sparse RLE step."""
    from .kernels_rle import integrate_op_slots_rle_sparse

    if jax.default_backend() == "tpu":
        return integrate_op_slots_rle_sparse_pallas(state, ops, slots)
    return integrate_op_slots_rle_sparse(state, ops, slots)


# -- minimal-work run merge (sequential fast path) -----------------------------


def append_run_slots_rle_sparse_fast(state: RleState, client, clock, run_len, slots):
    """Backend dispatcher for the RLE run-append fast path — like the
    compact step, the program is one fit scan over a (K,) carry plus a
    fused masked entry write per gathered row, with no K-pass HBM
    amplification for a Mosaic kernel to kill (see
    pallas_kernels.append_run_slots_sparse_fast)."""
    from .kernels_rle import append_run_slots_rle_sparse

    return append_run_slots_rle_sparse(state, client, clock, run_len, slots)


# -- on-device compaction ------------------------------------------------------


def compact_doc_rows_rle_fast(state: RleState, slots):
    """Backend dispatcher for the RLE compact (defragment) step — the
    single-pass sort+segment-merge permutation has no K-pass HBM
    amplification for a Mosaic kernel to kill (see
    pallas_kernels.compact_doc_rows_fast); the XLA lowering runs
    everywhere."""
    from .kernels_rle import compact_doc_rows_rle

    return compact_doc_rows_rle(state, slots)
