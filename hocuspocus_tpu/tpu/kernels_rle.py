"""Run-length batched text-CRDT integration (prototype, JAX).

The unit-granular arena (`kernels.py`) spends one slot per UTF-16 unit
forever — tombstoned text keeps its slots, so a long-lived busy doc
exhausts cumulative capacity no matter its live size (the documented
limit in docs/tpu/merge-plane.md). This module is the run-length
answer: one arena entry per RUN of consecutively-typed units. Typing
bursts cost one entry; deletes tombstone whole entries; entry growth is
O(ops + splits), not O(units), so tombstone cost is O(fragmentation).

Same architecture as the unit kernel — APPEND-ONLY entries + dense
UNIT-rank ordering, elementwise compares/selects + masked reductions,
no gathers — with two structural insights:

- Within a run, unit i's left origin is unit i-1 (that is what makes
  it a run), so only run HEADS can block a YATA conflict scan; the one
  exception is the unit at rank left_rank+1 inside a run, which ties
  on client id. The scan stays a couple of masked reductions.
- Unit ranks are DENSE (0..total_units), so "how many window units are
  skipped" needs no counting reduction: the insertion rank is simply
  `min(first_block_rank, right_rank)`.

Inserting or deleting into the middle of a run SPLITS it; both cases
reduce to two primitives (`_split_at_rank`, `_split_at_clock`) that
append the run's tail as a fresh entry (≤2 appends per op, bounded).

Status: production. Wired into the plane via `MergePlane(arena="rle")`
(capacity = ENTRIES; serving resolves payloads through the host
serve-log index), with the Pallas/VMEM-resident variant in
`pallas_kernels_rle.py` and mesh sharding in `sharding.py`.
Equivalence suites: tests/tpu/test_kernels_rle.py (vs the unit
kernel), test_pallas_kernels_rle.py (Pallas vs scan),
test_plane_fuzz.py + test_rle_plane.py (vs the CPU engine through the
live serve path; churn survival).

Reference semantics mirrored: yjs Item.integrate via
`/root/reference/packages/server/src/MessageReceiver.ts` readUpdate.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .kernels import KIND_DELETE, KIND_INSERT, KIND_NOOP, NONE_CLIENT, OpBatch

_INF = 0x7FFFFFFF


class RleState(NamedTuple):
    """Run-length arena for a batch of documents. Leading axis = doc."""

    run_client: jax.Array  # (D, R) uint32 — author of the run
    run_clock: jax.Array  # (D, R) int32 — clock of the first unit
    run_len: jax.Array  # (D, R) int32 — units in this entry
    run_rank: jax.Array  # (D, R) int32 — UNIT rank of the first unit
    run_orank: jax.Array  # (D, R) int32 — origin UNIT rank of the first unit
    run_deleted: jax.Array  # (D, R) bool
    num_runs: jax.Array  # (D,) int32 — occupied entries
    total_units: jax.Array  # (D,) int32 — rank-space size (live + tombstones)
    overflow: jax.Array  # (D,) bool

    @property
    def length(self) -> jax.Array:
        """Alias: cumulative INSERTED units — the same accounting the
        unit arena's `length` reports, so the plane's health readback
        (_sync_health: validated dispatch tallies vs device length) is
        arena-agnostic. Not a pytree field (properties are not)."""
        return self.total_units


def make_empty_rle_state(num_docs: int, entries: int) -> RleState:
    shape = (num_docs, entries)
    return RleState(
        run_client=jnp.full(shape, NONE_CLIENT, jnp.uint32),
        run_clock=jnp.zeros(shape, jnp.int32),
        run_len=jnp.zeros(shape, jnp.int32),
        run_rank=jnp.full(shape, _INF, jnp.int32),
        run_orank=jnp.full(shape, -1, jnp.int32),
        run_deleted=jnp.zeros(shape, bool),
        num_runs=jnp.zeros((num_docs,), jnp.int32),
        total_units=jnp.zeros((num_docs,), jnp.int32),
        overflow=jnp.zeros((num_docs,), bool),
    )


def _append_entry(state: RleState, lane, do, client, clock, length, rank, orank, deleted):
    """Write one entry at `lane` when `do` (single doc, elementwise)."""
    r = state.run_client.shape[0]
    idx = jnp.arange(r, dtype=jnp.int32)
    at = do & (idx == lane)
    return state._replace(
        run_client=jnp.where(at, client, state.run_client),
        run_clock=jnp.where(at, clock, state.run_clock),
        run_len=jnp.where(at, length, state.run_len),
        run_rank=jnp.where(at, rank, state.run_rank),
        run_orank=jnp.where(at, orank, state.run_orank),
        run_deleted=jnp.where(at, deleted, state.run_deleted),
        num_runs=state.num_runs + do.astype(jnp.int32),
    )


def _split_at_rank(state: RleState, rank, do):
    """Split the entry strictly containing unit-rank `rank` (if any).

    The head keeps its lane (len shortened); the tail appends at
    num_runs with orank = rank-1 (within-run chaining). No entry
    contains `rank` strictly when it is a run boundary — no-op then.
    """
    idx = jnp.arange(state.run_client.shape[0], dtype=jnp.int32)
    occupied = idx < state.num_runs
    inside = (
        do
        & occupied
        & (state.run_rank < rank)
        & (rank < state.run_rank + state.run_len)
    )
    any_split = jnp.any(inside)
    # at most ONE entry strictly contains a given rank, so masked SUMS
    # extract its fields exactly (masked max would misread uint32
    # client ids with the high bit set through an int32 view)
    off = jnp.sum(jnp.where(inside, rank - state.run_rank, 0))
    t_client = jnp.sum(
        jnp.where(inside, state.run_client, jnp.uint32(0)), dtype=jnp.uint32
    )
    t_clock = jnp.sum(jnp.where(inside, state.run_clock + off, 0))
    t_len = jnp.sum(jnp.where(inside, state.run_len - off, 0))
    t_deleted = jnp.any(inside & state.run_deleted)
    shortened = jnp.where(inside, off, state.run_len)
    state = state._replace(run_len=shortened)
    return _append_entry(
        state, state.num_runs, any_split, t_client, t_clock, t_len, rank, rank - 1,
        t_deleted,
    )


def _split_at_clock(state: RleState, client, clock, do):
    """Split the entry of `client` strictly containing `clock` (if any)."""
    idx = jnp.arange(state.run_client.shape[0], dtype=jnp.int32)
    occupied = idx < state.num_runs
    inside = (
        do
        & occupied
        & (state.run_client == client)
        & (state.run_clock < clock)
        & (clock < state.run_clock + state.run_len)
    )
    any_split = jnp.any(inside)
    off = jnp.sum(jnp.where(inside, clock - state.run_clock, 0))
    t_rank = jnp.sum(jnp.where(inside, state.run_rank + off, 0))
    t_len = jnp.sum(jnp.where(inside, state.run_len - off, 0))
    t_deleted = jnp.any(inside & state.run_deleted)
    shortened = jnp.where(inside, off, state.run_len)
    state = state._replace(run_len=shortened)
    return _append_entry(
        state, state.num_runs, any_split, client, clock, t_len, t_rank, t_rank - 1,
        t_deleted,
    )


def _integrate_one_rle(state: RleState, op: OpBatch) -> RleState:
    """Integrate a single op into a single document (unbatched)."""
    r = state.run_client.shape[0]
    idx = jnp.arange(r, dtype=jnp.int32)
    occupied = idx < state.num_runs

    # -- resolve origin ids to UNIT ranks (range membership) ---------------
    in_left = (
        occupied
        & (state.run_client == op.left_client)
        & (op.left_clock >= state.run_clock)
        & (op.left_clock < state.run_clock + state.run_len)
    )
    has_left = op.left_client != jnp.uint32(NONE_CLIENT)
    left_found = jnp.any(in_left)
    left_rank = jnp.where(
        has_left,
        jnp.max(jnp.where(in_left, state.run_rank + (op.left_clock - state.run_clock), -1)),
        -1,
    )
    in_right = (
        occupied
        & (state.run_client == op.right_client)
        & (op.right_clock >= state.run_clock)
        & (op.right_clock < state.run_clock + state.run_len)
    )
    has_right = op.right_client != jnp.uint32(NONE_CLIENT)
    right_found = jnp.any(in_right)
    right_rank = jnp.where(
        has_right,
        jnp.max(
            jnp.where(in_right, state.run_rank + (op.right_clock - state.run_clock), -1)
        ),
        state.total_units,
    )

    # -- YATA conflict scan over run heads ---------------------------------
    # Only two unit shapes can BLOCK (see module docstring): an
    # in-window run head whose origin precedes the window, and the
    # non-head unit at rank left_rank+1 (its origin IS left), both
    # losing the client-id tie against op.client.
    client_ge = ~(state.run_client < op.client)
    head_in_window = occupied & (state.run_rank > left_rank) & (state.run_rank < right_rank)
    head_blocked = head_in_window & (
        (state.run_orank < left_rank)
        | ((state.run_orank == left_rank) & client_ge)
    )
    succ = left_rank + 1  # the unit right after left, when inside a run
    succ_nonhead = (
        occupied
        & (state.run_rank < succ)
        & (succ < state.run_rank + state.run_len)
        & (succ < right_rank)
    )
    succ_blocked = succ_nonhead & client_ge
    first_block = jnp.minimum(
        jnp.min(jnp.where(head_blocked, state.run_rank, _INF)),
        jnp.min(jnp.where(succ_blocked, succ, _INF)),
    )
    # dense rank space: skipped window units need no counting reduction
    ins_rank = jnp.minimum(first_block, right_rank)

    run = op.run_len
    fits = state.num_runs + 2 <= r
    deps_ok = (~has_left | left_found) & (~has_right | right_found)
    do_insert = (op.kind == KIND_INSERT) & fits & deps_ok

    # -- insert: split the straddled run, bump ranks, append ---------------
    state = _split_at_rank(state, ins_rank, do_insert)
    occupied2 = jnp.arange(r, dtype=jnp.int32) < state.num_runs
    bump_rank = do_insert & occupied2 & (state.run_rank >= ins_rank)
    bump_orank = do_insert & occupied2 & (state.run_orank >= ins_rank)
    state = state._replace(
        run_rank=jnp.where(bump_rank, state.run_rank + run, state.run_rank),
        run_orank=jnp.where(bump_orank, state.run_orank + run, state.run_orank),
    )
    state = _append_entry(
        state,
        state.num_runs,
        do_insert,
        op.client,
        op.clock,
        run,
        ins_rank,
        left_rank,
        False,
    )
    state = state._replace(
        total_units=state.total_units + jnp.where(do_insert, run, 0),
        overflow=state.overflow | ((op.kind == KIND_INSERT) & ~fits),
    )

    # -- delete: split at both boundaries, tombstone covered entries -------
    # capture the capacity verdict BEFORE the splits mutate num_runs
    # (like the insert path's `fits`): a delete that fit must not flag
    # sticky overflow just because its own splits consumed the margin
    del_fits = state.num_runs + 2 <= r
    do_delete = (op.kind == KIND_DELETE) & del_fits
    del_end = op.clock + op.run_len
    state = _split_at_clock(state, op.client, op.clock, do_delete)
    state = _split_at_clock(state, op.client, del_end, do_delete)
    occupied3 = jnp.arange(r, dtype=jnp.int32) < state.num_runs
    covered = (
        do_delete
        & occupied3
        & (state.run_client == op.client)
        & (state.run_clock >= op.clock)
        & (state.run_clock + state.run_len <= del_end)
    )
    state = state._replace(
        run_deleted=state.run_deleted | covered,
        overflow=state.overflow | ((op.kind == KIND_DELETE) & ~del_fits),
    )
    return state


_integrate_batch_rle = jax.vmap(_integrate_one_rle)


@partial(jax.jit, donate_argnums=(0,))
def integrate_ops_rle(state: RleState, ops: OpBatch) -> RleState:
    """Integrate one op per document (noop slots pass through)."""
    return _integrate_batch_rle(state, ops)


@partial(jax.jit, donate_argnums=(0,))
def integrate_op_slots_rle(state: RleState, ops: OpBatch):
    """Integrate (K, D)-shaped op slots via lax.scan, like the unit
    kernel's integrate_op_slots."""

    def step(carry, slot_ops):
        return _integrate_batch_rle(carry, slot_ops), None

    state, _ = jax.lax.scan(step, state, ops)
    count = jnp.sum(ops.kind != KIND_NOOP)
    count, _ = jax.lax.optimization_barrier((count, state.total_units))
    return state, count


@partial(jax.jit, donate_argnums=(0,))
def integrate_op_slots_rle_sparse(state: RleState, ops: OpBatch, slots):
    """Sparse busy-doc dispatch over the RLE arena: (K, B) op slots plus
    an int32 (B,) slot-routing vector (see kernels.integrate_op_slots_
    sparse — same gather/integrate/scatter contract, padding columns
    carry noops and the out-of-range sentinel)."""
    from .kernels import gather_doc_rows, scatter_doc_rows

    sub = gather_doc_rows(state, slots)
    sub, count = integrate_op_slots_rle.__wrapped__(sub, ops)
    state = scatter_doc_rows(state, sub, slots)
    count, _ = jax.lax.optimization_barrier((count, state.total_units))
    return state, count


# -- on-device compaction (defragmentation GC) --------------------------------
#
# RLE entry cost grows with fragmentation: every mid-run insert or
# delete splits a run into head+tail (and zero-length heads linger as
# dead lanes), so a churny doc's entry count creeps toward capacity even
# when its logical state is a handful of runs. The compact kernel is the
# id-PRESERVING defragmenter: drop zero-length lanes and merge entries
# that are rank-adjacent, id-consecutive, same-client and same-deleted —
# the exact fragments splitting created. No unit rank changes and no id
# range disappears, so origins keep resolving (range membership) and the
# host needs no serve-log or payload rewrite at all — unlike the unit
# arena's tombstone GC (kernels.compact_doc_rows), this one is pure
# housekeeping.


def _compact_one_rle(state: RleState) -> RleState:
    r = state.run_client.shape[0]
    idx = jnp.arange(r, dtype=jnp.int32)
    occupied = idx < state.num_runs
    keep = occupied & (state.run_len > 0)
    # rank-order the kept entries (dropped lanes sort to the back)
    order = jnp.argsort(jnp.where(keep, state.run_rank, _INF))
    cl = state.run_client[order]
    ck = state.run_clock[order]
    ln = state.run_len[order]
    rk = state.run_rank[order]
    ok = state.run_orank[order]
    dl = state.run_deleted[order]
    kept = keep[order]  # a prefix of size sum(keep)
    # an entry continues the previous one when splitting could have
    # produced the pair: same author, consecutive clocks AND ranks,
    # same tombstone verdict
    prev = lambda a: jnp.concatenate([a[:1], a[:-1]])
    merge = (
        kept
        & jnp.concatenate([jnp.zeros((1,), bool), kept[:-1]])
        & (cl == prev(cl))
        & (ck == prev(ck) + prev(ln))
        & (rk == prev(rk) + prev(ln))
        & (dl == prev(dl))
    )
    head = kept & ~merge
    seg = jnp.cumsum(head.astype(jnp.int32)) - 1  # segment index per entry
    num_segs = jnp.sum(head.astype(jnp.int32))
    seg_dst = jnp.where(kept, seg, r)  # r = drop
    seg_len = jnp.zeros((r,), jnp.int32).at[seg_dst].add(ln, mode="drop")
    head_dst = jnp.where(head, seg, r)  # unique: one head per segment

    def pack(vals, fill, dtype):
        return jnp.full((r,), fill, dtype).at[head_dst].set(vals, mode="drop")

    return RleState(
        run_client=pack(cl, NONE_CLIENT, jnp.uint32),
        run_clock=pack(ck, 0, jnp.int32),
        run_len=seg_len,
        run_rank=pack(rk, _INF, jnp.int32),
        run_orank=pack(ok, -1, jnp.int32),
        run_deleted=jnp.zeros((r,), bool).at[head_dst].set(dl, mode="drop"),
        num_runs=num_segs,
        total_units=state.total_units,  # rank space untouched
        overflow=jnp.zeros((), bool),
    )


_compact_batch_rle = jax.vmap(_compact_one_rle)


@partial(jax.jit, donate_argnums=(0,))
def compact_doc_rows_rle(state: RleState, slots) -> tuple[RleState, jax.Array]:
    """Defragment the B doc rows `slots` routes to (int32 (B,);
    num_docs = padding sentinel). Returns (state, packed entry counts
    (B,)) — data-dependent on the scattered state, the caller's
    completion barrier."""
    from .kernels import gather_doc_rows, scatter_doc_rows

    sub = gather_doc_rows(state, slots)
    sub = _compact_batch_rle(sub)
    state = scatter_doc_rows(state, sub, slots)
    counts, _ = jax.lax.optimization_barrier((sub.num_runs, state.total_units))
    return state, counts


# -- minimal-work run merge (the sequential fast path) ------------------------
#
# RLE twin of kernels.append_run_slots_sparse: the host classifier
# (merge_plane._classify_fast) routes a batch column here only when
# every drained op is a chained tail append (left origin = tracked
# rank-tail, right origin = NONE), for which the YATA window is empty
# and integration needs no conflict scan, no splits and no rank bumps.
# Two shapes of device work per coalesced run:
#
# - EXTEND: run 0 continues the arena's rank-tail entry (same client,
#   consecutive clock, entry not tombstoned) — run_len += len, zero new
#   entries. The scan path would append a fresh entry instead; the
#   fast path's layout is exactly the merge the RLE compactor
#   (_compact_one_rle) performs later, so unit expansion — and every
#   serve derived from it — is identical while entry pressure drops.
# - APPEND: one new entry at the next free lane with rank = old total
#   + chain offset and orank = rank - 1, the same fields the scan
#   path's _append_entry writes for an end-of-doc insert.
#
# Overflow semantics: a run that needs a lane when none is free flags
# overflow and kills the chain (later runs' origins would be missing).
# This admits strictly MORE work near capacity than the scan path's
# conservative `num_runs + 2 <= R` split margin (extensions need no
# lane at all) — a doc the fast path still fits would have overflowed
# under the slow path, never the reverse, so the retire/degrade story
# is unchanged and the equivalence fuzz compares unit expansions away
# from the capacity edge.


def _append_entries_one_rle(state: RleState, client, clock, run_len) -> tuple:
    """Apply up to K chained tail-append runs to one document row."""
    r = state.run_client.shape[0]
    idx = jnp.arange(r, dtype=jnp.int32)
    total = state.total_units
    entries = state.num_runs
    is_run = run_len > 0

    # the rank-tail entry: occupied entry spans are disjoint and cover
    # [0, total), so exactly one nonempty entry ends at `total` (none
    # when the doc is empty) — masked sums extract its fields
    occupied = (idx < entries) & (state.run_len > 0)
    tail = occupied & (state.run_rank + state.run_len == total) & (total > 0)
    tail_client = jnp.sum(jnp.where(tail, state.run_client, jnp.uint32(0)), dtype=jnp.uint32)
    tail_end_clock = jnp.sum(jnp.where(tail, state.run_clock + state.run_len, 0))
    tail_deleted = jnp.any(tail & state.run_deleted)
    ext0 = (
        is_run[0]
        & (total > 0)
        & jnp.any(tail)
        & (tail_client == client[0])
        & (clock[0] == tail_end_clock)
        & ~tail_deleted
    )

    def fit_step(carry, m):
        applied_units, new_entries, alive, over = carry
        extend = (m == 0) & ext0
        fits = extend | (entries + new_entries + 1 <= r)
        live = alive & fits & is_run[m]
        start = applied_units
        lane = entries + new_entries
        applied_units = applied_units + jnp.where(live, run_len[m], 0)
        new_entries = new_entries + jnp.where(live & ~extend, 1, 0)
        over = over | (is_run[m] & ~fits)
        alive = alive & (fits | ~is_run[m])
        return (applied_units, new_entries, alive, over), (
            start,
            lane,
            live & ~extend,
        )

    (applied_units, _new_entries, _alive, overflow), (starts, lanes, appends) = (
        jax.lax.scan(
            fit_step,
            (jnp.int32(0), jnp.int32(0), jnp.bool_(True), state.overflow),
            jnp.arange(client.shape[0]),
        )
    )

    # extension first (its own lane, disjoint from every appended lane)
    extend_applied = ext0  # an extension always fits
    run_len_out = jnp.where(
        tail & extend_applied, state.run_len + run_len[0], state.run_len
    )

    def write_step(carry, m):
        e_client, e_clock, e_len, e_rank, e_orank, e_deleted = carry
        at = appends[m] & (idx == lanes[m])
        e_client = jnp.where(at, client[m], e_client)
        e_clock = jnp.where(at, clock[m], e_clock)
        e_len = jnp.where(at, run_len[m], e_len)
        e_rank = jnp.where(at, total + starts[m], e_rank)
        e_orank = jnp.where(at, total + starts[m] - 1, e_orank)
        e_deleted = jnp.where(at, False, e_deleted)
        return (e_client, e_clock, e_len, e_rank, e_orank, e_deleted), None

    (e_client, e_clock, e_len, e_rank, e_orank, e_deleted), _ = jax.lax.scan(
        write_step,
        (
            state.run_client,
            state.run_clock,
            run_len_out,
            state.run_rank,
            state.run_orank,
            state.run_deleted,
        ),
        jnp.arange(client.shape[0]),
    )
    new_state = RleState(
        run_client=e_client,
        run_clock=e_clock,
        run_len=e_len,
        run_rank=e_rank,
        run_orank=e_orank,
        run_deleted=e_deleted,
        num_runs=entries + jnp.sum(appends.astype(jnp.int32)),
        total_units=total + applied_units,
        overflow=overflow,
    )
    applied_runs = jnp.sum(appends.astype(jnp.int32)) + extend_applied.astype(jnp.int32)
    return new_state, applied_runs


_append_entries_batch_rle = jax.vmap(_append_entries_one_rle, in_axes=(0, 1, 1, 1))


@partial(jax.jit, donate_argnums=(0,))
def append_run_slots_rle_sparse(
    state: RleState, client, clock, run_len, slots
) -> tuple[RleState, jax.Array]:
    """Fast-path integrate for B all-sequential busy docs (RLE arena).

    Same batch layout and padding contract as the unit arena's
    kernels.append_run_slots_sparse: (K, B) coalesced runs + int32
    (B,) slot routing (sentinel = num_docs)."""
    from .kernels import gather_doc_rows, scatter_doc_rows

    sub = gather_doc_rows(state, slots)
    sub, counts = _append_entries_batch_rle(sub, client, clock, run_len)
    state = scatter_doc_rows(state, sub, slots)
    count, _ = jax.lax.optimization_barrier((jnp.sum(counts), state.total_units))
    return state, count


# -- on-device catch-up support (SyncStep2 serving) ---------------------------


def _tail_probe_one_rle(state: RleState) -> tuple:
    """(client, clock) id of the rank-tail UNIT of one document row —
    the RLE twin of kernels._tail_probe_one (same host contract: an
    empty doc reads as (0, 0), keyed on total_units == 0)."""
    r = state.run_client.shape[0]
    idx = jnp.arange(r, dtype=jnp.int32)
    occupied = (idx < state.num_runs) & (state.run_len > 0)
    tail = occupied & (state.run_rank + state.run_len == state.total_units) & (
        state.total_units > 0
    )
    client = jnp.sum(jnp.where(tail, state.run_client, jnp.uint32(0)), dtype=jnp.uint32)
    clock = jnp.sum(jnp.where(tail, state.run_clock + state.run_len - 1, 0))
    return client, clock.astype(jnp.uint32)


@jax.jit
def health_probe_rle(state: RleState, slots) -> jax.Array:
    """(3D + 2B,) uint32 [lengths..., overflows..., tail clients...,
    tail clocks..., occupied entries...]: the flush cycle's health
    readback for the RLE arena (same contract as kernels.health_probe,
    and last every row's num_runs: what the plane differences row by
    row, from cycle to cycle, into `rle_entries_appended`)."""
    from .kernels import gather_doc_rows

    sub = gather_doc_rows(state, slots)
    clients, clocks = jax.vmap(_tail_probe_one_rle)(sub)
    return jnp.concatenate(
        [
            state.length.astype(jnp.uint32),
            state.overflow.astype(jnp.uint32),
            clients,
            clocks,
            state.num_runs.astype(jnp.uint32),
        ]
    )


@partial(jax.jit, static_argnames=("width",))
def catchup_pack_rle(state: RleState, slots, width: int) -> jax.Array:
    """Device-side delete-set pack for B requested rows (RLE arena):
    ONE (B + 3*B*width,) uint32 readback laid out [counts (B,),
    clients flat, clocks flat, lens flat] of the tombstoned entries in
    lane order — the host sorts/merges exactly as the full-row path
    did, so emitted DeleteSet bytes are identical. Rows with more than
    `width` tombstoned entries report the true count and fall back."""
    from .kernels import gather_doc_rows

    def one(row: RleState):
        r = row.run_client.shape[0]
        idx = jnp.arange(r, dtype=jnp.int32)
        dead = (idx < row.num_runs) & row.run_deleted & (row.run_len > 0)
        pos = jnp.cumsum(dead.astype(jnp.int32)) - 1
        dst = jnp.where(dead, pos, width)  # width = drop sentinel
        clients = (
            jnp.zeros((width,), jnp.uint32).at[dst].set(row.run_client, mode="drop")
        )
        clocks = jnp.zeros((width,), jnp.int32).at[dst].set(row.run_clock, mode="drop")
        lens = jnp.zeros((width,), jnp.int32).at[dst].set(row.run_len, mode="drop")
        return (
            jnp.sum(dead.astype(jnp.int32)),
            clients,
            clocks.astype(jnp.uint32),
            lens.astype(jnp.uint32),
        )

    sub = gather_doc_rows(state, slots)
    counts, clients, clocks, lens = jax.vmap(one)(sub)
    return jnp.concatenate(
        [
            counts.astype(jnp.uint32),
            clients.reshape(-1),
            clocks.reshape(-1),
            lens.reshape(-1),
        ]
    )


# -- host-side extraction ----------------------------------------------------


def expand_to_units(state: RleState, doc: int):
    """Document order as parallel unit arrays (client, clock, deleted),
    sorted by rank — the comparison form used by the equivalence tests
    and any host consumer."""
    import numpy as np

    n = int(np.asarray(state.num_runs)[doc])
    client = np.asarray(state.run_client)[doc][:n]
    clock = np.asarray(state.run_clock)[doc][:n]
    length = np.asarray(state.run_len)[doc][:n]
    rank = np.asarray(state.run_rank)[doc][:n]
    deleted = np.asarray(state.run_deleted)[doc][:n]
    keep = length > 0  # split heads shortened to zero never re-emit
    client, clock, length, rank, deleted = (
        client[keep], clock[keep], length[keep], rank[keep], deleted[keep],
    )
    order = np.argsort(rank)
    out_client = np.concatenate(
        [np.full(length[i], client[i], np.uint32) for i in order]
    ) if len(order) else np.zeros(0, np.uint32)
    out_clock = np.concatenate(
        [clock[i] + np.arange(length[i], dtype=np.int32) for i in order]
    ) if len(order) else np.zeros(0, np.int32)
    out_deleted = np.concatenate(
        [np.full(length[i], deleted[i], bool) for i in order]
    ) if len(order) else np.zeros(0, bool)
    return out_client, out_clock, out_deleted


def delete_ranges(state: RleState, doc: int):
    """Tombstones as sorted (client, clock, length) ranges — direct from
    deleted entries (the unit arena needs a per-unit pair scan here)."""
    import numpy as np

    n = int(np.asarray(state.num_runs)[doc])
    client = np.asarray(state.run_client)[doc][:n]
    clock = np.asarray(state.run_clock)[doc][:n]
    length = np.asarray(state.run_len)[doc][:n]
    deleted = np.asarray(state.run_deleted)[doc][:n]
    sel = deleted & (length > 0)
    ranges = sorted(zip(client[sel].tolist(), clock[sel].tolist(), length[sel].tolist()))
    merged: list[tuple] = []
    for c, k, l in ranges:
        if merged and merged[-1][0] == c and merged[-1][1] + merged[-1][2] == k:
            merged[-1] = (c, merged[-1][1], merged[-1][2] + l)
        else:
            merged.append((c, k, l))
    return merged
