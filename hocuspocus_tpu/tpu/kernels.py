"""Batched text-CRDT integration kernels (JAX, TPU-first).

This is the compute core of the TPU merge plane (BASELINE.md north star):
the per-connection integrate loop of the reference server
(`packages/server/src/MessageReceiver.ts` readUpdate → yjs integrate)
reformulated as a dense, data-parallel kernel over thousands of
documents.

Representation (per document, fixed capacity N — "arena"):
  APPEND-ONLY storage + RANK ordering. Units are stored in arrival
  order (slot = arrival index) and never move; the document order is a
  dense `rank` array. Inserting at logical rank r is then a pure
  elementwise bump (`rank += run where rank >= r`) instead of a
  physical shift — no gathers or scatters anywhere in the hot path,
  which is what lets XLA lower each op to vectorized compares,
  selects and reductions on the VPU. (A physically-ordered variant
  needs a batched dynamic gather per op, which serializes on TPU.)

  id_client/id_clock     — the unit's Yjs id (client ids are uint32)
  rank                   — current logical position (0..length-1)
  origin_rank            — current RANK of the left origin, maintained
                           incrementally so conflict resolution never
                           searches (origin *ids* are not kept on
                           device — they are write-only for the kernel
                           and live host-side in the lowerer)
  deleted                — tombstone flag
  length                 — number of occupied arena slots
  overflow               — capacity exceeded; host falls back to CPU

CHARACTER PAYLOADS LIVE ON THE HOST, not in device state: conflict
resolution never reads them, and append-only slot assignment is
deterministic (slot = arrival index), so the host lowerer keeps a
per-document char log indexed by arena slot (merge_plane.MergePlane).
Keeping payloads off-device removes ~40% of the per-op HBM traffic and
unbounds run length: one Yjs string struct of any length is ONE op
(rank bump by run_len + elementwise slot fill), where a device-side
chars buffer would force splitting runs into fixed-width pieces.

The YATA conflict rule (Yjs Item.integrate: same-origin siblings ordered
by ascending client id, nested subtrees skipped transitively) becomes a
masked reduction over the (leftOrigin, rightOrigin) rank window:
  skip c while origin_rank[c] > L or (origin_rank[c] == L and client[c] < op.client)

Ops are (kind, client, clock, run_len, left id, right id):
  kind 0 = noop, 1 = insert run, 2 = delete id-range.
Deletes are pure id-range compares — no position work at all.

Everything is static-shape, vmap-batched over the doc axis and
lax.scan-ed over op slots; the doc axis shards over a device mesh
(see sharding.py).
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


def place_compile_cache() -> Optional[str]:
    """Give JAX's persistent compilation cache a stable home.

    A plane shape warms ~25 programs; without a persistent cache every
    process start recompiles all of them. The directory is part of
    what makes an entry findable again, so it must not move between
    runs: JAX_COMPILATION_CACHE_DIR wins when set (JAX reads it itself
    — nothing is touched here), otherwise the cache lives in
    `<checkout>/.jax_cache`, derived from this package's location.
    The compile-time floor is lowered so the warm grid's sub-second
    programs are cached too, unless the environment sets its own.
    Returns the directory this call configured, else None."""
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# every device module imports this one first, so the cache is placed
# before the first program compiles
COMPILE_CACHE_DIR = place_compile_cache()

NONE_CLIENT = 0xFFFFFFFF  # "no origin" sentinel (client ids are uint32)
# plain int, NOT jnp.int32: a module-level jnp scalar would initialize
# the JAX backend at import time
_INF = 0x7FFFFFFF

KIND_NOOP = 0
KIND_INSERT = 1
KIND_DELETE = 2


class DocState(NamedTuple):
    """Dense arena for a batch of documents. Leading axis = doc."""

    id_client: jax.Array  # (D, N) uint32
    id_clock: jax.Array  # (D, N) int32
    rank: jax.Array  # (D, N) int32 — logical position
    origin_rank: jax.Array  # (D, N) int32 — rank of left origin (-1 = start)
    deleted: jax.Array  # (D, N) bool
    length: jax.Array  # (D,) int32 — occupied slots
    overflow: jax.Array  # (D,) bool


class OpBatch(NamedTuple):
    """One op slot per document. Leading axis = doc (or (K, D) under scan)."""

    kind: jax.Array  # int32
    client: jax.Array  # uint32
    clock: jax.Array  # int32
    run_len: jax.Array  # int32
    left_client: jax.Array  # uint32 (NONE_CLIENT = doc start)
    left_clock: jax.Array  # int32
    right_client: jax.Array  # uint32 (NONE_CLIENT = doc end)
    right_clock: jax.Array  # int32


def make_empty_state(num_docs: int, capacity: int) -> DocState:
    shape = (num_docs, capacity)
    # distinct buffers per field: integrate steps donate their input
    # state and XLA rejects donating one buffer twice
    return DocState(
        id_client=jnp.full(shape, NONE_CLIENT, jnp.uint32),
        id_clock=jnp.zeros(shape, jnp.int32),
        rank=jnp.full(shape, _INF, jnp.int32),
        origin_rank=jnp.full(shape, -1, jnp.int32),
        deleted=jnp.zeros(shape, bool),
        length=jnp.zeros((num_docs,), jnp.int32),
        overflow=jnp.zeros((num_docs,), bool),
    )


def make_noop_batch(num_docs: int) -> OpBatch:
    zeros = jnp.zeros((num_docs,), jnp.int32)
    return OpBatch(
        kind=zeros,
        client=jnp.zeros((num_docs,), jnp.uint32),
        clock=zeros,
        run_len=zeros,
        left_client=jnp.full((num_docs,), NONE_CLIENT, jnp.uint32),
        left_clock=zeros,
        right_client=jnp.full((num_docs,), NONE_CLIENT, jnp.uint32),
        right_clock=zeros,
    )


def _integrate_one(state: DocState, op: OpBatch) -> DocState:
    """Integrate a single op into a single document (unbatched).

    Elementwise compares/selects + reductions only — no gathers.
    """
    n = state.id_client.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    occupied = idx < state.length

    # -- resolve origin ids to ranks (masked reductions) -------------------
    is_left = occupied & (state.id_client == op.left_client) & (state.id_clock == op.left_clock)
    has_left = op.left_client != jnp.uint32(NONE_CLIENT)
    left_found = jnp.any(is_left)
    left_rank = jnp.where(has_left, jnp.max(jnp.where(is_left, state.rank, -1)), -1)

    is_right = occupied & (state.id_client == op.right_client) & (state.id_clock == op.right_clock)
    has_right = op.right_client != jnp.uint32(NONE_CLIENT)
    right_found = jnp.any(is_right)
    right_rank = jnp.where(has_right, jnp.max(jnp.where(is_right, state.rank, -1)), state.length)

    # -- YATA conflict scan over the (left, right) rank window -------------
    in_window = occupied & (state.rank > left_rank) & (state.rank < right_rank)
    skip_cond = (state.origin_rank > left_rank) | (
        (state.origin_rank == left_rank) & (state.id_client < op.client)
    )
    blocked = in_window & ~skip_cond
    first_block_rank = jnp.min(jnp.where(blocked, state.rank, _INF))
    skipped = jnp.sum((in_window & (state.rank < first_block_rank)).astype(jnp.int32))
    ins_rank = left_rank + 1 + skipped

    run = op.run_len
    fits = state.length + run <= n
    deps_ok = (~has_left | left_found) & (~has_right | right_found)
    do_insert = (op.kind == KIND_INSERT) & fits & deps_ok

    # -- elementwise insert ------------------------------------------------
    # bump ranks at/after the insertion rank; append units to free slots
    bump = do_insert & occupied
    rank_bumped = jnp.where(bump & (state.rank >= ins_rank), state.rank + run, state.rank)
    origin_rank_bumped = jnp.where(
        bump & (state.origin_rank >= ins_rank), state.origin_rank + run, state.origin_rank
    )
    slot_off = idx - state.length  # 0..run-1 for the new slots
    in_new = do_insert & (slot_off >= 0) & (slot_off < run)
    is_first = slot_off == 0

    id_client = jnp.where(in_new, op.client, state.id_client)
    id_clock = jnp.where(in_new, op.clock + slot_off, state.id_clock)
    rank = jnp.where(in_new, ins_rank + slot_off, rank_bumped)
    origin_rank = jnp.where(
        in_new, jnp.where(is_first, left_rank, ins_rank + slot_off - 1), origin_rank_bumped
    )
    deleted_after_insert = jnp.where(in_new, False, state.deleted)

    # -- delete: id-range tombstones ---------------------------------------
    do_delete = op.kind == KIND_DELETE
    in_del_range = (
        do_delete
        & occupied
        & (state.id_client == op.client)
        & (state.id_clock >= op.clock)
        & (state.id_clock < op.clock + op.run_len)
    )

    return DocState(
        id_client=id_client,
        id_clock=id_clock,
        rank=rank,
        origin_rank=origin_rank,
        deleted=deleted_after_insert | in_del_range,
        length=jnp.where(do_insert, state.length + run, state.length),
        overflow=state.overflow | ((op.kind == KIND_INSERT) & ~fits),
    )


# Batched over documents.
_integrate_batch = jax.vmap(_integrate_one)


@partial(jax.jit, donate_argnums=(0,))
def integrate_ops(state: DocState, ops: OpBatch) -> DocState:
    """Integrate one op per document (noop slots pass through)."""
    return _integrate_batch(state, ops)


@partial(jax.jit, donate_argnums=(0,))
def integrate_op_slots(state: DocState, ops: OpBatch) -> tuple[DocState, jax.Array]:
    """Integrate K op slots per document: ops fields have shape (K, D, ...).

    Returns the new state and the number of integrated (non-noop) ops.
    """

    def step(carry: DocState, op_slice: OpBatch):
        return _integrate_batch(carry, op_slice), jnp.sum(op_slice.kind != KIND_NOOP)

    state, counts = jax.lax.scan(step, state, ops)
    # data-depend the count on the final state so fetching it is a
    # completion barrier for the whole integrate step (callers use
    # int(count) as their sync point)
    count, _ = jax.lax.optimization_barrier((jnp.sum(counts), state.length))
    return state, count


# -- sparse (busy-doc) dispatch ----------------------------------------------
#
# At scale almost every flush touches a small fraction of the resident
# documents: the dense (K, D) batch pays O(K*D) host build + upload +
# device sweep regardless. The sparse step instead takes (K, B) ops over
# only the B busy doc slots plus an int32 (B,) slot-routing vector:
# gather those B arena rows, integrate, scatter back in place (the full
# state is donated, so the (D, N) arenas never copy). Padding columns
# carry KIND_NOOP ops and the out-of-range sentinel slot `num_docs`:
# the gather clips (reads a real row, mutates nothing — noops), and the
# scatter drops the write, so padding can never alias a busy row.


def gather_doc_rows(state, slots: jax.Array):
    """Gather the doc rows `slots` from every field of a doc-major
    state pytree (DocState or RleState). Out-of-range indices clip."""
    return type(state)(
        *(jnp.take(field, slots, axis=0, mode="clip") for field in state)
    )


def scatter_doc_rows(state, sub, slots: jax.Array):
    """Scatter the gathered rows back; out-of-range indices drop."""
    return type(state)(
        *(
            field.at[slots].set(sub_field, mode="drop")
            for field, sub_field in zip(state, sub)
        )
    )


@partial(jax.jit, donate_argnums=(0,))
def integrate_op_slots_sparse(
    state: DocState, ops: OpBatch, slots: jax.Array
) -> tuple[DocState, jax.Array]:
    """Integrate K op slots over the B busy docs `slots` routes to.

    ops fields have shape (K, B); slots is int32 (B,) mapping batch
    column -> doc row (num_docs = padding sentinel). Work scales with
    B, not the resident population D.
    """
    sub = gather_doc_rows(state, slots)
    sub, count = integrate_op_slots.__wrapped__(sub, ops)
    state = scatter_doc_rows(state, sub, slots)
    # re-tie the count to the SCATTERED state so fetching it is a
    # completion barrier for the full write-back, not just the sub-batch
    count, _ = jax.lax.optimization_barrier((count, state.length))
    return state, count


# -- on-device compaction (tombstone GC) --------------------------------------
#
# The arena is append-only: tombstoned units keep their slots forever, so
# a long-lived churny doc exhausts cumulative capacity no matter its live
# size — the row then overflows and the doc falls off the plane. The
# compact kernel is the device-side GC: rewrite a row so its LIVE units
# occupy slots 0..L-1 in document (rank) order, with dense ranks and
# predecessor-chained origin ranks — exactly the layout integrating a
# freshly-lowered snapshot of the live text would produce. Tombstone ids
# are dropped from the device; the host (tpu/residency.py) keeps a
# remap so future ops whose origins reference removed ids re-anchor to
# the nearest live neighbor (the same information loss yjs accepts once
# tombstones are garbage-collected).


def _compact_one(state: DocState) -> DocState:
    """Compact a single document row (unbatched): pack live units into
    slots 0..L-1 in rank order, clear tombstones and the overflow flag.

    Ranks are dense over occupied units (0..length-1, each exactly
    once), so the new rank of a live unit is a cumulative count of live
    units at lower ranks — one scatter, one cumsum, one gather, one
    scatter; no sort."""
    n = state.id_client.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    occupied = idx < state.length
    live = occupied & ~state.deleted
    new_len = jnp.sum(live.astype(jnp.int32))
    # rank-indexed live mask (ranks of unoccupied slots are _INF: the
    # out-of-range scatter drops them), then inclusive cumsum gives
    # each live rank its packed position
    live_by_rank = jnp.zeros((n,), jnp.int32).at[state.rank].add(
        live.astype(jnp.int32), mode="drop"
    )
    packed_of_rank = jnp.cumsum(live_by_rank) - 1
    dst = jnp.where(
        live, packed_of_rank[jnp.clip(state.rank, 0, n - 1)], n  # n = drop
    )
    in_new = idx < new_len
    return DocState(
        id_client=jnp.full((n,), NONE_CLIENT, jnp.uint32)
        .at[dst]
        .set(state.id_client, mode="drop"),
        id_clock=jnp.zeros((n,), jnp.int32).at[dst].set(state.id_clock, mode="drop"),
        rank=jnp.where(in_new, idx, _INF),
        origin_rank=jnp.where(in_new, idx - 1, -1),
        deleted=jnp.zeros((n,), bool),
        length=new_len,
        overflow=jnp.zeros((), bool),
    )


_compact_batch = jax.vmap(_compact_one)


@partial(jax.jit, donate_argnums=(0,))
def compact_doc_rows(state: DocState, slots: jax.Array) -> tuple[DocState, jax.Array]:
    """Compact the B doc rows `slots` routes to (int32 (B,); num_docs =
    padding sentinel, same gather/scatter contract as the sparse
    integrate step). Returns (state, packed live lengths (B,)) — the
    lengths are data-dependent on the scattered state, so fetching them
    is the caller's completion barrier."""
    sub = gather_doc_rows(state, slots)
    sub = _compact_batch(sub)
    state = scatter_doc_rows(state, sub, slots)
    lengths, _ = jax.lax.optimization_barrier((sub.length, state.length))
    return state, lengths


@jax.jit
def read_doc_row(state, slot):
    """Row `slot` of every arena field (DocState or RleState), sliced
    on the device by ONE program: reading whole (D, N) fields to pick
    one row moves gigabytes per call at deployment size."""
    return jax.tree.map(lambda field: field[slot], state)


@jax.jit
def state_vector_diff(
    doc_clocks: jax.Array, client_clocks: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Batched catch-up computation (BASELINE config 5: catch-up storm).

    doc_clocks:    (D, C) server-side clock per (doc, client-id slot)
    client_clocks: (D, C) requesting client's known clock per slot
    Returns (missing_from, missing_len): per (doc, client) the clock
    range the client is missing — the device-side equivalent of
    state-vector diff in encode_state_as_update(doc, sv).
    """
    missing_from = jnp.minimum(client_clocks, doc_clocks)
    missing_len = jnp.maximum(doc_clocks - client_clocks, 0)
    return missing_from, missing_len


# -- minimal-work run merge (the sequential fast path) ------------------------
#
# The integrate scan above pays K passes over the whole arena row no
# matter what the ops are — the eg-walker observation (arXiv:2409.14252)
# is that merge cost should track the CONCURRENT region, and the common
# op mix (one author typing, a cold snapshot hydrating) is a pure chain
# of tail appends with an EMPTY concurrent region. For those the YATA
# window between `left = rank-tail` and `right = doc end` contains
# nothing, so integration degenerates to "fill the next free slots":
# rank = slot index, origin_rank = slot index - 1, no conflict scan, no
# rank bumps, and the whole chain lands in ONE arena pass instead of one
# scan pass per op.
#
# The HOST decides eligibility (merge_plane._classify_fast): a batch
# column takes this kernel only when every drained op is an insert whose
# left origin is the tracked rank-tail of the chain and whose right
# origin is NONE — exactly the "append at document end" shape, for which
# this kernel is bit-identical to the scan path (including the
# longest-fitting-prefix overflow semantics below). Anything else —
# deletes, mid-doc inserts, unknown tails — falls back to the full-row
# integrate for that column.


def _append_runs_one(state: DocState, client, clock, run_len) -> tuple:
    """Apply up to K chained tail-append runs to one document.

    client/clock/run_len are (K,) coalesced runs (host-merged maximal
    same-client consecutive-clock chains; run_len == 0 = padding). The
    caller guarantees run m's left origin is the last unit of run m-1
    (run 0's left is the current rank-tail / doc start), so the only
    per-run work is the capacity ladder: a run integrates while the
    chain is alive and it fits, a run that does not fit marks overflow
    and kills the chain (later runs' origins are then missing — the
    exact deps_ok cascade the scan path produces, including its quirk
    that a dead-chain run only flags overflow when it ALSO fails its
    own fits check against the unchanged length)."""
    n = state.id_client.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    base = state.length
    is_run = run_len > 0

    def fit_step(carry, m):
        applied, alive, over = carry
        fits = base + applied + run_len[m] <= n
        live = alive & fits & is_run[m]
        start = applied
        applied = applied + jnp.where(live, run_len[m], 0)
        over = over | (is_run[m] & ~fits)
        alive = alive & (fits | ~is_run[m])
        return (applied, alive, over), (start, live)

    (applied_total, _alive, overflow), (starts, lives) = jax.lax.scan(
        fit_step,
        (jnp.int32(0), jnp.bool_(True), state.overflow),
        jnp.arange(client.shape[0]),
    )

    # one elementwise fill pass: new units occupy slots [base, base +
    # applied_total) in chain order, so slot i carries rank i and
    # origin rank i - 1 (run 0's first unit origins the old rank-tail
    # at rank base - 1 = i - 1; the doc-start case is -1 = i - 1 too)
    off = idx - base

    def fill_step(carry, m):
        sel_client, sel_clock, in_new = carry
        in_run = lives[m] & (off >= starts[m]) & (off < starts[m] + run_len[m])
        sel_client = jnp.where(in_run, client[m], sel_client)
        sel_clock = jnp.where(in_run, clock[m] + (off - starts[m]), sel_clock)
        return (sel_client, sel_clock, in_new | in_run), None

    (sel_client, sel_clock, in_new), _ = jax.lax.scan(
        fill_step,
        (state.id_client, state.id_clock, jnp.zeros((n,), bool)),
        jnp.arange(client.shape[0]),
    )
    new_state = DocState(
        id_client=sel_client,
        id_clock=sel_clock,
        rank=jnp.where(in_new, idx, state.rank),
        origin_rank=jnp.where(in_new, idx - 1, state.origin_rank),
        deleted=jnp.where(in_new, False, state.deleted),
        length=base + applied_total,
        overflow=overflow,
    )
    return new_state, jnp.sum(lives.astype(jnp.int32))


_append_runs_batch = jax.vmap(_append_runs_one, in_axes=(0, 1, 1, 1))


@partial(jax.jit, donate_argnums=(0,))
def append_run_slots_sparse(
    state: DocState, client, clock, run_len, slots: jax.Array
) -> tuple[DocState, jax.Array]:
    """Fast-path integrate for B all-sequential busy docs.

    client (K, B) uint32 / clock (K, B) int32 / run_len (K, B) int32
    are coalesced tail-append runs per column; slots is the int32 (B,)
    routing vector with the same gather-clip/scatter-drop padding
    contract as integrate_op_slots_sparse (sentinel = num_docs,
    padding columns all run_len == 0). Near-O(new ops) device work per
    column instead of K full-row scan passes."""
    sub = gather_doc_rows(state, slots)
    sub, counts = _append_runs_batch(sub, client, clock, run_len)
    state = scatter_doc_rows(state, sub, slots)
    count, _ = jax.lax.optimization_barrier((jnp.sum(counts), state.length))
    return state, count


# -- on-device catch-up support (SyncStep2 serving) ---------------------------


def _tail_probe_one(state: DocState) -> tuple:
    """(client, clock) id of the rank-tail unit of one document.

    The rank-tail (rank == length - 1) is the only unit a pure tail
    append may name as its left origin with a NONE right origin, so
    this pair is everything the host classifier needs to re-arm a
    slot's chain tracking. Masked SUMS, not maxes: exactly one unit
    matches (dense ranks), and a masked max through an int32 view
    would misread uint32 client ids with the high bit set. An empty
    doc matches nothing and reads as (0, 0) — the host keys on
    length == 0 before trusting the pair."""
    tail = state.rank == state.length - 1
    client = jnp.sum(jnp.where(tail, state.id_client, jnp.uint32(0)), dtype=jnp.uint32)
    clock = jnp.sum(jnp.where(tail, state.id_clock, 0))
    return client, clock.astype(jnp.uint32)


@jax.jit
def health_probe(state: DocState, slots: jax.Array) -> jax.Array:
    """A flush cycle's whole health readback as ONE (2D + 2B,) uint32
    vector — one program, one transfer: every row's length, every
    row's overflow flag, then the rank-tail ids of the B requested doc
    rows, [clients..., clocks...]. B may be 0 (no tail to re-arm).
    Padding slots re-read row 0 and return ids the host ignores."""
    sub = gather_doc_rows(state, slots)
    clients, clocks = jax.vmap(_tail_probe_one)(sub)
    return jnp.concatenate(
        [
            state.length.astype(jnp.uint32),
            state.overflow.astype(jnp.uint32),
            clients,
            clocks,
        ]
    )


@partial(jax.jit, static_argnames=("width",))
def catchup_pack(state: DocState, slots: jax.Array, width: int) -> jax.Array:
    """Device-side SyncStep2 delete-set pack for B requested doc rows.

    The host serve path used to read each row's full (3, B, N)
    [deleted, id_client, id_clock] planes and filter tombstones on the
    CPU; this kernel does the gather + prefix-sum compaction on device
    and ships only the packed tombstones: ONE (B + 2*B*width,) uint32
    readback laid out [counts (B,), clients (B, width) flat, clocks
    (B, width) flat], in arena order (the host sorts/merges exactly as
    before, so the emitted DeleteSet bytes are identical). A row with
    more than `width` tombstones reports the true count and the host
    falls back to the full-row read for that row."""

    def one(row: DocState):
        n = row.id_client.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        dead = (idx < row.length) & row.deleted
        pos = jnp.cumsum(dead.astype(jnp.int32)) - 1
        dst = jnp.where(dead, pos, width)  # width = drop sentinel
        clients = (
            jnp.zeros((width,), jnp.uint32).at[dst].set(row.id_client, mode="drop")
        )
        clocks = (
            jnp.zeros((width,), jnp.int32).at[dst].set(row.id_clock, mode="drop")
        )
        return jnp.sum(dead.astype(jnp.int32)), clients, clocks.astype(jnp.uint32)

    sub = gather_doc_rows(state, slots)
    counts, clients, clocks = jax.vmap(one)(sub)
    return jnp.concatenate(
        [counts.astype(jnp.uint32), clients.reshape(-1), clocks.reshape(-1)]
    )
