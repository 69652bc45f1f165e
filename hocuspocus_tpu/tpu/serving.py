"""Serve sync replies and broadcasts from TPU merge-plane state.

This is the piece that promotes the merge plane from a shadow mirror to
the serving path: for supported documents, SyncStep2 payloads and
steady-state update broadcasts are PRODUCED from device state — arena
ids / rank / tombstones read back from the TPU, combined with the
host-side serve/unit logs — instead of from the CPU document
(reference hot path: `packages/server/src/MessageReceiver.ts:137-213`
building SyncStep2 via `Y.encodeStateAsUpdate`, and
`packages/server/src/Document.ts:228-240` re-broadcasting every
incoming update per-connection).

Safety model:
- The CPU document stays the fallback: every serve checks the plane is
  healthy (supported, no overflow, host/device logs in sync) AND covers
  the CPU document's state vector; otherwise the caller falls back.
- SYNC serves read delete sets for *sequence* content from the DEVICE
  tombstone mask — a cold joiner can never receive a deletion the
  kernel did not apply. Map-item deletions (host-only content that
  never rides the device) are merged in from the host tombstone log.
- BROADCASTS ship the window's own delete ranges from the serve log
  (O(window), not O(doc-lifetime tombstones)): the kernel applies
  id-range tombstones unconditionally over ids the lowerer proved
  integrated, and any host/device divergence retires the doc via the
  health check (full-state CPU fallback) before the next broadcast —
  see build_broadcast.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Optional

import numpy as np

from ..crdt.content import ContentDeleted, ContentString
from ..crdt.delete_set import DeleteSet
from ..crdt.encoding import Encoder
from ..crdt.ids import ID
from ..crdt.structs import GC, Item
from ..crdt.update import _write_structs, decode_state_vector
from ..observability.tracing import get_tracer
from ..observability.wire import get_wire_telemetry
from .kernels import KIND_DELETE, KIND_INSERT, NONE_CLIENT
from .lowering import DenseOp, units_to_text
from .merge_plane import LogRec, MergePlane, PlaneDoc


class SyncFrameCache:
    """Join-storm sync cache: (doc, state-vector) -> encoded SyncStep2
    payload, scoped to the serve-log/flush epoch.

    A join storm is N clients asking for the same diff between two
    flushes — cold joiners (empty state vector) after a deploy, or a
    partitioned building's worth of tabs reconnecting with the same
    stale SV. Entries key on the doc name + the CUTOFF MAP actually
    encoded (canonical: sorted (client, clock) pairs — two wire SVs
    that trim to the same cutoffs share one entry) and validate against
    (PlaneDoc identity, serve-log key, plane flush epoch): any
    integrated op (log grows), device flush (epoch bump), compaction
    (epoch bump + `forget`), or re-registration (fresh PlaneDoc) misses
    naturally. `forget(name)` — unload/evict/degrade — drops a doc's
    entries outright. Bounded per doc (LRU): distinct stale SVs are
    unbounded in principle, and one hot doc must not evict another
    doc's storm entry.
    """

    PER_DOC_CAP = 32

    def __init__(self) -> None:
        # name -> OrderedDict[sv_key -> (PlaneDoc, epoch_key, payload)]
        self._by_name: "dict[str, OrderedDict]" = {}
        self.evictions = 0

    def get(self, name: str, doc, epoch_key, sv_key) -> Optional[bytes]:
        entries = self._by_name.get(name)
        if entries is None:
            return None
        entry = entries.get(sv_key)
        if entry is None:
            return None
        if entry[0] is not doc or entry[1] != epoch_key:
            del entries[sv_key]  # stale epoch: drop eagerly
            return None
        entries.move_to_end(sv_key)
        return entry[2]

    def put(self, name: str, doc, epoch_key, sv_key, payload: bytes) -> None:
        entries = self._by_name.setdefault(name, OrderedDict())
        entries[sv_key] = (doc, epoch_key, payload)
        entries.move_to_end(sv_key)
        while len(entries) > self.PER_DOC_CAP:
            entries.popitem(last=False)
            self.evictions += 1

    def forget(self, name: str) -> None:
        entries = self._by_name.pop(name, None)
        if entries:
            self.evictions += len(entries)

    # dict-like surface for tests / debugging
    def __contains__(self, name: str) -> bool:
        return name in self._by_name

    def __bool__(self) -> bool:
        return bool(self._by_name)

    def __len__(self) -> int:
        return sum(len(entries) for entries in self._by_name.values())


def _wire_parent(parent: Optional[tuple]):
    """DenseOp parent tuple -> the Item.write representation."""
    if parent is None:
        return None
    if parent[0] == "root":
        return parent[1]
    return ID(parent[1], parent[2])


def _make_item(rec: LogRec, unit_logs: dict):
    op = rec.op
    if op.gc:
        # collected subtree: re-encode the clock range verbatim
        return GC(ID(op.client, op.clock), op.run_len)
    origin = ID(op.left_client, op.left_clock) if op.left_client != NONE_CLIENT else None
    right_origin = (
        ID(op.right_client, op.right_clock) if op.right_client != NONE_CLIENT else None
    )
    if op.content is not None:
        content = op.content
    elif op.deleted_content:
        content = ContentDeleted(op.run_len)
    else:
        log = unit_logs[rec.slot]
        content = ContentString(
            units_to_text(log[rec.unit_off : rec.unit_off + op.run_len])
        )
    return Item(
        ID(op.client, op.clock),
        None,
        origin,
        None,
        right_origin,
        _wire_parent(op.parent),  # consulted by Item.write only when origin-less
        op.parent_sub,
        content,
    )


class PlaneServing:
    """Builds yjs update bytes from plane state for sync + broadcast."""

    def __init__(self, plane: MergePlane) -> None:
        self.plane = plane
        # doc name -> serve_log index whose records receivers already have
        self.broadcast_cursor: dict[str, int] = {}
        self._length_cache: Optional[np.ndarray] = None
        self._overflow_cache: Optional[np.ndarray] = None
        self._validated_cache: Optional[np.ndarray] = None
        self._gen_cache: Optional[np.ndarray] = None
        # slot -> ((slot_gen, flush_epoch), sorted merged deleted
        # (client, clock, length) ranges): see _slot_deleted_ranges
        self._tombstone_cache: dict[int, tuple] = {}
        # join-storm sync cache: every joiner asking for the same diff
        # of the same epoch receives the SAME SyncStep2 bytes (sync
        # serves drain the queues first, so the payload is a pure
        # function of the serve log + cutoff map) — a reconnect storm
        # re-encodes once per (doc state, SV), not once per joiner.
        # Generalizes the old cold-only cache to arbitrary stale SVs.
        self._sync_cache = SyncFrameCache()
        # catch-up batching: SyncStep1s that arrive in the same storm
        # window are triaged by ONE state_vector_diff kernel call
        self._catchup_queue: list[tuple] = []  # (name, document, sv_bytes, future)
        self._catchup_scheduled = False
        self._drain_tasks: set = set()
        # set by TpuMergeExtension: invoked when a device flush dies so
        # served docs degrade to the CPU path (captured ops were already
        # popped from the queues — they only survive via the full-state
        # fallback broadcast)
        self.flush_failure_handler = None
        # supervisor drain seam (tpu/supervisor.py): while paused, every
        # sync serve resolves to None (CPU fallback) WITHOUT touching
        # the device — a wedged runtime must never stall a document
        self.paused = False
        # on-device catch-up encode: tombstone reads ship as packed
        # (counts + tombstones) readbacks instead of full arena rows;
        # rows whose tombstone count overflows the pack width fall back
        # to the full-row gather per chunk (see _fetch_slot_rows)
        self.device_pack_enabled = True
        # unresolved batched-sync futures, so abort_pending can resolve
        # waiters stranded behind a wedged flush
        self._inflight: set = set()

    # -- device readback cache ---------------------------------------------

    def refresh(self) -> None:
        """Adopt the plane's last combined health readback; per-slot
        checks then stay host-side.

        The three caches — lengths, overflows, validated dispatch
        tallies — are snapshotted together under the step lock so they
        describe ONE device state: serve logs run optimistically ahead
        of the device, and comparing rows from flush N against tallies
        from flush N+1 would misread healthy docs as desynced. When the
        plane has already fetched the rows this cycle (_sync_health),
        this costs no device I/O at all."""
        plane = self.plane
        with plane._step_lock:
            if plane.last_lengths is not None:
                self._length_cache = plane.last_lengths
                self._overflow_cache = plane.last_overflows
            else:
                t0 = time.perf_counter()
                self._length_cache = np.asarray(plane.state.length)
                self._overflow_cache = np.asarray(plane.state.overflow)
                # cache-miss path only: a real device→host transfer,
                # charged to the same stall meter as the flush barrier
                plane.device_stats["readback_stall_ms_total"] += (
                    time.perf_counter() - t0
                ) * 1000.0
                plane.device_stats["readback_stalls"] += 1
            self._validated_cache = plane.validated_units.copy()
            self._gen_cache = None if plane.last_gen is None else plane.last_gen.copy()

    def _lengths(self) -> np.ndarray:
        if self._length_cache is None:
            self.refresh()
        return self._length_cache

    def _overflows(self) -> np.ndarray:
        if self._overflow_cache is None:
            self.refresh()
        return self._overflow_cache

    def forget(self, name: str, doc: Optional[PlaneDoc]) -> None:
        """Drop every per-doc serving cache at unload/degrade time.

        The sync cache holds a strong ref to the PlaneDoc (and its
        whole serve log); without eviction a server that churns through
        transient doc names leaks each one forever.
        """
        self.broadcast_cursor.pop(name, None)
        self._sync_cache.forget(name)
        if doc is not None:
            for slot in doc.seqs.values():
                self._tombstone_cache.pop(slot, None)
            if doc.lane_slot is not None:
                # lane slots may predate root discovery (not yet in
                # seqs): a stale entry left here would survive into the
                # slot's next tenant's cache lookups
                self._tombstone_cache.pop(doc.lane_slot, None)

    # -- health -------------------------------------------------------------

    def doc_healthy(self, name: str) -> Optional[PlaneDoc]:
        plane = self.plane
        doc = plane.docs.get(name)
        if doc is None:
            return None
        if doc.lowerer.unsupported:
            return None
        if self._length_cache is None:
            # no completed flush has been adopted yet — there is nothing
            # to validate against, and the broadcast path must NEVER
            # block on the step lock / pull device state on the event
            # loop (a first flush may be mid-executor right now). The
            # post-flush sweep covers these docs the moment a snapshot
            # exists.
            return doc
        if not plane.check_doc_health(
            name,
            doc,
            self._length_cache,
            self._overflow_cache,
            self._validated_cache,
            self._gen_cache,
        ):
            return None
        return doc

    def filter_healthy(self, names: "list[str]") -> "tuple[list[str], list[str]]":
        """(fast_ok, needs_check): one vectorized compare replaces the
        per-doc health loop for the common case (registered, supported,
        single-row doc whose cached device row matches its validated
        tally). A STALE-generation row fast-OKs — check_doc_health
        skips such slots too (the snapshot predates the binding; the
        next consistent snapshot covers it). needs_check gets the
        genuinely suspicious cases — unregistered, unsupported,
        mismatching current-generation row, multi-row trees, no
        snapshot yet — for the full doc_healthy treatment (which also
        performs the retire-on-failure side effects)."""
        plane = self.plane
        if self._length_cache is None or self._gen_cache is None:
            return [], list(names)
        candidates: list[str] = []
        slots: list[int] = []
        needs_check: list[str] = []
        for name in names:
            doc = plane.docs.get(name)
            if doc is None or doc.lowerer.unsupported:
                needs_check.append(name)
                continue
            doc_slots = list(doc.seqs.values())
            if len(doc_slots) == 0:
                candidates.append(name)
                slots.append(-1)
            elif len(doc_slots) == 1:
                candidates.append(name)
                slots.append(doc_slots[0])
            else:
                needs_check.append(name)  # multi-row trees: full check
        if not candidates:
            return [], needs_check
        arr = np.asarray(slots, np.int64)
        rowless = arr < 0
        safe = np.where(rowless, 0, arr)
        gen_current = self._gen_cache[safe] == plane.slot_gen[safe]
        mismatch = (
            (self._validated_cache[safe] != self._length_cache[safe])
            | self._overflow_cache[safe]
        )
        ok = rowless | ~gen_current | ~mismatch
        fast_ok = [name for name, good in zip(candidates, ok) if good]
        needs_check.extend(
            name for name, good in zip(candidates, ok) if not good
        )
        return fast_ok, needs_check

    def _local_sv(self, doc: PlaneDoc) -> dict:
        """The plane's integrated clocks for this doc (lane docs keep
        them natively; others in the Python lowerer)."""
        plane = self.plane
        if doc.lane_slot is not None and plane._lane is not None:
            return plane._lane_codec.lane_known(plane._lane, doc.lane_slot)
        return dict(doc.lowerer.known)

    def covers(self, name: str, document) -> bool:
        """Plane has integrated everything the CPU document has seen."""
        plane = self.plane
        doc = plane.docs.get(name)
        if doc is None:
            return False
        sv = document.store.get_state_vector()
        if doc.lane_slot is not None and plane._lane is not None:
            return bool(
                plane._lane_codec.lane_covers(
                    plane._lane, doc.lane_slot, list(sv.items())
                )
            )
        known = doc.lowerer.known
        for client, clock in sv.items():
            if clock > known.get(client, 0):
                return False
        return True

    # -- encoding -----------------------------------------------------------

    def _group_items(
        self,
        doc: PlaneDoc,
        records: list[LogRec],
        min_clock: Optional[dict[int, int]] = None,
    ) -> dict[int, list[Item]]:
        """Group serve-log records into per-client clock-sorted Items.

        min_clock trims fully-known items per client: an op is included
        when any part of it is at/above the client's cutoff (the first
        included item may overlap the cutoff — _write_structs emits it
        with an offset), and clients absent from min_clock are skipped.
        """
        by: dict[int, list[Item]] = {}
        unit_logs = self.plane.unit_logs
        for rec in records:
            op = rec.op
            if op.kind != KIND_INSERT:
                continue
            if min_clock is not None:
                cutoff = min_clock.get(op.client)
                if cutoff is None or op.clock + op.run_len <= cutoff:
                    continue
            by.setdefault(op.client, []).append(_make_item(rec, unit_logs))
        for items in by.values():
            items.sort(key=lambda item: item.id.clock)
        return by

    def _slot_deleted_ranges(self, slot: int) -> "list[tuple[int, int, int]]":
        """Sorted, merged (client, clock, length) ranges of the slot's
        device tombstones.

        Cached per (slot binding generation, flush epoch): tombstone
        rows only change when a flush integrates ops or the slot is
        cleared, so a catch-up storm hitting the same doc repeatedly —
        or many docs across waves — pays the device fetch once per
        epoch, not once per serve (~a full RTT per transfer on a
        remote-attached chip). The miss path fuses the row reads
        (deleted mask, ids — and lengths on the RLE arena) into ONE
        transfer.
        """
        plane = self.plane
        key = (int(plane.slot_gen[slot]), plane.flush_epoch)
        cached = self._tombstone_cache.get(slot)
        if cached is not None and cached[0] == key:
            return cached[1]
        self._fetch_slot_rows([slot], plane.flush_epoch)
        return self._tombstone_cache[slot][1]

    def prefetch_tombstones(self, docs: "list[PlaneDoc]") -> None:
        """Fill the tombstone cache for every slot of `docs` in ONE
        fused device transfer.

        A reconnect storm serves tens of docs in one drain; fetching
        each slot's rows individually costs ~a full RTT per slot on a
        remote-attached chip. One gathered (3, B, N) read costs one.
        """
        plane = self.plane
        epoch = plane.flush_epoch
        slots = sorted(
            {
                slot
                for doc in docs
                for slot in doc.seqs.values()
                if (
                    (cached := self._tombstone_cache.get(slot)) is None
                    or cached[0] != (int(plane.slot_gen[slot]), epoch)
                )
            }
        )
        if not slots:
            return
        # fixed gather widths: exactly two compiled programs (small
        # drains don't transfer a big batch; big storms chunk), instead
        # of one XLA compile (seconds, remote) per distinct slot count
        for pos_chunk in self._gather_chunks(slots):
            self._fetch_slot_rows(pos_chunk, epoch)

    def _gather_widths(self) -> "list[int]":
        """Fixed width ladder, capped at the plane size (pow2): a small
        drain transfers a small batch, a storm fuses into few big ones,
        and the compile count stays at len(ladder)."""
        cap = 1
        while cap < min(self.plane.num_docs, 256):
            cap *= 2
        widths = [w for w in (16, 64) if w < cap]
        widths.append(cap)
        return widths

    def _gather_chunks(self, slots: "list[int]") -> "list[list[int]]":
        biggest = self._gather_widths()[-1]
        chunks = []
        pos = 0
        while pos < len(slots):
            chunks.append(slots[pos : pos + biggest])
            pos += biggest
        return chunks

    def _gather_rows(self, slot_indices: "list[int]") -> np.ndarray:
        """One fused device read of the tombstone-relevant rows for the
        given slots. Caller holds the step lock. Unit arena: (3, B, N)
        [deleted, id_client, id_clock]. RLE arena: (4, B, R) [deleted,
        run_client, run_clock, run_len] — ranges come straight from
        deleted entries, no per-unit pair scan."""
        import jax.numpy as jnp

        state = self.plane.state
        # uploaded with the plane's placement rules: on a pinned cell an
        # uncommitted index lands on the default device (chip 0) first
        idx = self.plane._upload_slots(np.asarray(slot_indices, np.int32))
        if self.plane.arena == "rle":
            return np.asarray(
                jnp.stack(
                    [
                        state.run_deleted[idx].astype(jnp.int32),
                        state.run_client[idx].view(jnp.int32),
                        state.run_clock[idx],
                        state.run_len[idx],
                    ]
                )
            )
        return np.asarray(
            jnp.stack(
                [
                    state.deleted[idx].astype(jnp.int32),
                    state.id_client[idx].view(jnp.int32),
                    state.id_clock[idx],
                ]
            )
        )

    @staticmethod
    def _merge_ranges(
        raw: "list[tuple[int, int, int]]",
    ) -> "list[tuple[int, int, int]]":
        """Merge sorted id-adjacent (client, clock, length) ranges once
        at fetch time so every serve consumes ready ranges."""
        ranges: list[tuple[int, int, int]] = []
        for c, k, l in raw:
            if ranges and ranges[-1][0] == c and ranges[-1][1] + ranges[-1][2] == k:
                ranges[-1] = (c, ranges[-1][1], ranges[-1][2] + l)
            else:
                ranges.append((c, k, l))
        return ranges

    def _pack_width(self) -> int:
        """Tombstone-pack lane width: narrow enough that the packed
        readback (B + 2·B·W or B + 3·B·W uint32) stays far below the
        full-row read, wide enough for the overwhelming majority of
        rows. One static value = one compiled pack program per gather
        width."""
        state = self.plane.state
        dim = (
            state.run_client.shape[1]
            if self.plane.arena == "rle"
            else state.id_client.shape[1]
        )
        return min(128, int(dim))

    def _fetch_slot_rows(self, chunk: "list[int]", epoch: int) -> None:
        """Fill the tombstone cache for a slot chunk: the on-device
        packed read first, a full-row host gather for any slot whose
        tombstone count overflowed the pack width."""
        if self.device_pack_enabled:
            overflow = self._fetch_slot_rows_device(chunk, epoch)
            if overflow:
                self._fetch_slot_rows_host(overflow, epoch)
            return
        self._fetch_slot_rows_host(chunk, epoch)
        self.plane.counters["sync_encode_host"] += len(chunk)

    def _fetch_slot_rows_device(self, chunk: "list[int]", epoch: int) -> "list[int]":
        """Packed tombstone fetch: the device gathers the chunk's rows,
        masks live tombstones and prefix-sum-compacts them into a
        (B + planes·B·W) uint32 readback — O(tombstones) on the wire
        instead of O(arena width). Returns the slots whose tombstone
        count exceeded the pack width (the host full-row path re-reads
        exactly those). Tombstones arrive in arena order; the host
        sorts and merges identically to the full-row path, so the
        DeleteSet bytes emitted downstream are byte-identical."""
        plane = self.plane
        width = next(w for w in self._gather_widths() if w >= len(chunk))
        pack_w = self._pack_width()
        padded = chunk + [chunk[0]] * (width - len(chunk))
        rle = plane.arena == "rle"
        with plane._step_lock:  # never gather donated buffers mid-flush
            t0 = time.perf_counter()
            slots_dev = plane._upload_slots(np.asarray(padded, np.int32))
            shape_key = (width, pack_w)
            with plane.compile_watch.track("catchup_pack", shape_key):
                if rle:
                    from .kernels_rle import catchup_pack_rle

                    fused = np.asarray(
                        catchup_pack_rle(plane.state, slots_dev, pack_w)
                    )
                else:
                    from .kernels import catchup_pack

                    fused = np.asarray(catchup_pack(plane.state, slots_dev, pack_w))
            plane._note_dispatch("sync")
            gens = [int(plane.slot_gen[slot]) for slot in chunk]
            plane.device_stats["readback_stall_ms_total"] += (
                time.perf_counter() - t0
            ) * 1000.0
            plane.device_stats["readback_stalls"] += 1
        planes = 3 if rle else 2
        counts = fused[:width]
        body = fused[width:].reshape(planes, width, pack_w)
        overflow: list[int] = []
        for i, slot in enumerate(chunk):
            count = int(counts[i])
            if count > pack_w:
                overflow.append(slot)
                continue
            clients = body[0, i, :count]
            clocks = body[1, i, :count].astype(np.int64)
            if rle:
                lens = body[2, i, :count].astype(np.int64)
                raw = sorted(zip(clients.tolist(), clocks.tolist(), lens.tolist()))
            else:
                raw = [
                    (c, k, 1)
                    for c, k in sorted(zip(clients.tolist(), clocks.tolist()))
                ]
            self._tombstone_cache[slot] = (
                (gens[i], epoch),
                self._merge_ranges(raw),
            )
        plane.counters["sync_encode_device"] += len(chunk) - len(overflow)
        return overflow

    def _fetch_slot_rows_host(self, chunk: "list[int]", epoch: int) -> None:
        plane = self.plane
        width = next(w for w in self._gather_widths() if w >= len(chunk))
        with plane._step_lock:  # never gather donated buffers mid-flush
            t0 = time.perf_counter()
            fused = self._gather_rows(chunk + [chunk[0]] * (width - len(chunk)))
            gens = [int(plane.slot_gen[slot]) for slot in chunk]
            # tombstone gathers are serve-path device readbacks: count
            # them into the stall meter so /metrics shows how much host
            # time sync serving spends blocked on the device
            plane.device_stats["readback_stall_ms_total"] += (
                time.perf_counter() - t0
            ) * 1000.0
            plane.device_stats["readback_stalls"] += 1
        rle = plane.arena == "rle"
        for i, slot in enumerate(chunk):
            sel = np.nonzero(fused[0, i])[0]
            clients = fused[1, i][sel].view(np.uint32)
            clocks = fused[2, i][sel]
            if rle:
                lens = fused[3, i][sel]
                raw = sorted(
                    (c, k, l)
                    for c, k, l in zip(
                        clients.tolist(), clocks.tolist(), lens.tolist()
                    )
                    if l > 0
                )
            else:
                raw = [(c, k, 1) for c, k in sorted(zip(clients.tolist(), clocks.tolist()))]
            self._tombstone_cache[slot] = (
                (gens[i], epoch),
                self._merge_ranges(raw),
            )
        plane.counters["sync_encode_host"] += len(chunk)

    def warmup_gathers(self, width: Optional[int] = None) -> None:
        """Compile the tombstone-gather AND catch-up pack programs (one
        per fixed width) so the first reconnect storm pays data
        transfer, not XLA compile time. Run from the extension's
        listen-time warm task — which passes one `width` per call so
        interactive work (sync serves, lane-demote rebuilds) interleaves
        between compiles instead of waiting out the whole ladder."""
        plane = self.plane
        pack_w = self._pack_width()
        widths = self._gather_widths() if width is None else [width]
        with plane._step_lock:
            for w in widths:
                self._gather_rows([0] * w)
                shape_key = (w, pack_w)
                with plane.compile_watch.track(
                    "catchup_pack", shape_key, warmup=True
                ):
                    slots_dev = plane._upload_slots(np.zeros(w, np.int32))
                    if plane.arena == "rle":
                        from .kernels_rle import catchup_pack_rle

                        np.asarray(catchup_pack_rle(plane.state, slots_dev, pack_w))
                    else:
                        from .kernels import catchup_pack

                        np.asarray(catchup_pack(plane.state, slots_dev, pack_w))
                plane.compile_watch.mark_covered("catchup_pack", shape_key)

    def _device_delete_set(self, doc: PlaneDoc) -> DeleteSet:
        """Tombstones as the DEVICE sees them, across every row of the
        doc, plus host-applied map-item tombstones."""
        lengths = self._lengths()
        ds = DeleteSet()
        for slot in doc.seqs.values():
            if int(lengths[slot]) == 0:
                continue
            for client, clock, length in self._slot_deleted_ranges(slot):
                ds.add(client, clock, length)
        for client, clock, length in doc.map_tombstones:
            ds.add(client, clock, length)
        ds.sort_and_merge()
        return ds

    def _encode_window_native(
        self,
        doc: PlaneDoc,
        records: list[LogRec],
        min_clock: Optional[dict[int, int]],
    ) -> Optional[bytes]:
        """Struct-section bytes via the native `encode_text_window`, or
        None = use the Python path.

        The semantic work of `_group_items` + `crdt/update._write_structs`
        — cutoff trimming (the record filter below), group ordering,
        the first-item offset with its origin rewrite and payload slice
        — happens HERE; the C++ side is pure byte emission. Only the
        shapes the plane serves hot qualify (string runs, deleted runs,
        GC ranges, root parents); any rich content (formats, embeds,
        maps, ID parents) returns None and the caller re-encodes via
        Items.
        """
        from ..native import get_codec

        codec = get_codec()
        if codec is None or not hasattr(codec, "encode_text_window"):
            return None
        unit_logs = self.plane.unit_logs
        by: dict[int, list[LogRec]] = {}
        for rec in records:
            op = rec.op
            if op.kind != KIND_INSERT:
                continue
            if min_clock is not None:
                cutoff = min_clock.get(op.client)
                if cutoff is None or op.clock + op.run_len <= cutoff:
                    continue
            if op.content is not None or op.parent_sub is not None:
                return None
            if op.parent is not None and op.parent[0] != "root":
                return None
            by.setdefault(op.client, []).append(rec)
        groups = []
        for client in sorted(by, reverse=True):
            recs = sorted(by[client], key=lambda r: r.op.clock)
            cutoff = 0 if min_clock is None else min_clock[client]
            # the filter above kept only records overlapping the cutoff,
            # so recs[0] is the group's first emitted struct
            write_clock = max(cutoff, recs[0].op.clock)
            items = []
            for j, rec in enumerate(recs):
                op = rec.op
                offset = max(write_clock - op.clock, 0) if j == 0 else 0
                if op.gc:
                    items.append((1, -1, 0, -1, 0, None, op.run_len - offset))
                    continue
                oc = -1 if op.left_client == NONE_CLIENT else op.left_client
                ok = op.left_clock
                rc = -1 if op.right_client == NONE_CLIENT else op.right_client
                rk = op.right_clock
                if offset > 0:
                    # emitting a tail of the run: its origin is the unit
                    # just before the cut (Item.write offset semantics)
                    oc, ok = client, write_clock - 1
                parent_name = None
                if oc < 0 and rc < 0:
                    if op.parent is None:
                        return None
                    parent_name = op.parent[1]
                if op.deleted_content:
                    items.append(
                        (2, oc, ok, rc, rk, parent_name, op.run_len - offset)
                    )
                    continue
                log = unit_logs[rec.slot]
                payload = units_to_text(
                    log[rec.unit_off + offset : rec.unit_off + op.run_len]
                )
                items.append((0, oc, ok, rc, rk, parent_name, payload))
            groups.append((client, write_clock, items))
        return codec.encode_text_window(groups)

    def _widen_surrogate_cutoffs(
        self, records: list[LogRec], sm: dict[int, int]
    ) -> None:
        """A stale-sync cutoff landing mid-surrogate-pair would slice a
        text run so its first transmitted unit is a lone low surrogate —
        units_to_text (errors='replace') bakes U+FFFD into the wire
        bytes while the CPU document still holds the real pair. Widen
        such cutoffs by one unit: the re-sent high surrogate is already
        known to the client and struct integration skips the known
        prefix (offset semantics), so the serve stays byte-faithful
        without leaving the device path.

        The pair's two units may live in DIFFERENT serve-log records
        (a remote update re-encoded as two structs split mid-pair), so
        the unit AT the cutoff and the unit BEFORE it are resolved
        independently across all of the client's records. A high
        surrogate can never be the second half of a pair, so one step
        suffices (no cascade)."""
        unit_logs = self.plane.unit_logs
        at_unit: dict[int, int] = {}
        prev_unit: dict[int, int] = {}
        for rec in records:
            op = rec.op
            if op.kind != KIND_INSERT or op.gc or op.deleted_content:
                continue
            if op.content is not None or op.parent_sub is not None or rec.slot is None:
                continue
            cutoff = sm.get(op.client)
            if cutoff is None or cutoff <= 0:
                continue
            log = unit_logs.get(rec.slot)
            if log is None:
                continue
            if op.clock <= cutoff < op.clock + op.run_len:
                pos = rec.unit_off + (cutoff - op.clock)
                if pos < len(log) and isinstance(log[pos], int):
                    at_unit[op.client] = log[pos]
            if op.clock <= cutoff - 1 < op.clock + op.run_len:
                pos = rec.unit_off + (cutoff - 1 - op.clock)
                if pos < len(log) and isinstance(log[pos], int):
                    prev_unit[op.client] = log[pos]
        for client, unit in at_unit.items():
            prev = prev_unit.get(client)
            if (
                0xDC00 <= unit <= 0xDFFF
                and prev is not None
                and 0xD800 <= prev <= 0xDBFF
            ):
                sm[client] = sm[client] - 1

    def _encode_path(self) -> str:
        """/metrics path label for sync-cache events: which delete-set
        read route serves on a miss."""
        return "device" if self.device_pack_enabled else "host"

    def _cache_lookup(self, doc: PlaneDoc, epoch_key, sv_key) -> Optional[bytes]:
        payload = self._sync_cache.get(doc.name, doc, epoch_key, sv_key)
        counters = self.plane.counters
        wire = get_wire_telemetry()
        if payload is not None:
            counters["sync_cache_hits"] += 1
            if wire.enabled:
                wire.record_sync_cache("hit", path=self._encode_path())
        else:
            counters["sync_cache_misses"] += 1
            if wire.enabled:
                wire.record_sync_cache("miss", path=self._encode_path())
        return payload

    def _cache_store(self, doc: PlaneDoc, epoch_key, sv_key, payload: bytes) -> None:
        before = self._sync_cache.evictions
        self._sync_cache.put(doc.name, doc, epoch_key, sv_key, payload)
        evicted = self._sync_cache.evictions - before
        if evicted:
            self.plane.counters["sync_cache_evictions"] += evicted
            wire = get_wire_telemetry()
            if wire.enabled:
                wire.record_sync_cache(
                    "eviction", evicted, path=self._encode_path()
                )

    def _encode_from_sm(self, doc: PlaneDoc, sm: dict[int, int]) -> bytes:
        """SyncStep2 bytes for a doc given the per-client cutoff map.

        Both paths consult the join-storm sync cache first: the payload
        is a pure function of (serve log, cutoff map) within one flush
        epoch, so N joiners sharing a state vector pay ONE encode."""
        plane = self.plane
        if doc.lane_slot is not None and plane._lane is not None:
            # native path: cutoff trimming, offset origin-rewrite and
            # surrogate widening all happen in C — no materialization,
            # so a reconnect storm never exports the log
            epoch_key = (
                plane._lane_codec.lane_log_len(plane._lane, doc.lane_slot),
                plane.flush_epoch,
            )
            sv_key = tuple(sorted(sm.items()))
            cached = self._cache_lookup(doc, epoch_key, sv_key)
            if cached is not None:
                plane.counters["sync_serves"] += 1
                return cached
            encoder = Encoder()
            encoder.write_bytes(
                plane._lane_codec.lane_window_sm(
                    plane._lane, doc.lane_slot, list(sm.items())
                )
            )
            self._device_delete_set(doc).write(encoder)
            plane.counters["sync_serves"] += 1
            payload = encoder.to_bytes()
            self._cache_store(doc, epoch_key, sv_key, payload)
            return payload
        self.plane.materialize_lane(doc)
        if any(clock > 0 for clock in sm.values()):
            # zero cutoffs can't slice a run, so cold serves skip the
            # widening walk entirely
            self._widen_surrogate_cutoffs(doc.serve_log, sm)
        epoch_key = (
            len(doc.serve_log),
            len(doc.map_tombstones),
            plane.flush_epoch,
        )
        sv_key = tuple(sorted(sm.items()))
        cached = self._cache_lookup(doc, epoch_key, sv_key)
        if cached is not None:
            plane.counters["sync_serves"] += 1
            return cached
        encoder = Encoder()
        body = self._encode_window_native(doc, doc.serve_log, sm)
        if body is not None:
            encoder.write_bytes(body)
        else:
            items_by_client = self._group_items(doc, doc.serve_log, sm)
            encoder.write_var_uint(len(items_by_client))
            for client in sorted(items_by_client, reverse=True):
                _write_structs(encoder, items_by_client[client], client, sm[client])
        self._device_delete_set(doc).write(encoder)
        self.plane.counters["sync_serves"] += 1
        payload = encoder.to_bytes()
        self._cache_store(doc, epoch_key, sv_key, payload)
        return payload

    def encode_state_as_update(
        self, name: str, document, sv_bytes: Optional[bytes] = None
    ) -> Optional[bytes]:
        """SyncStep2 payload from device state; None = CPU fallback.

        Synchronous path (tests, benches, the non-batched sync adapter):
        holds the plane's step lock across its own flush AND the state
        reads, so an extension-scheduled executor flush can neither
        donate the buffers mid-read nor interleave between the drain
        and the encode. The server core uses the async batched path.
        """
        if self.paused:
            return None  # supervisor drain: serve from the CPU document
        with get_tracer().span("serving.sync_serve", document=name):
            return self._encode_state_as_update_inner(name, document, sv_bytes)

    def _encode_state_as_update_inner(
        self, name: str, document, sv_bytes: Optional[bytes] = None
    ) -> Optional[bytes]:
        plane = self.plane
        with plane._step_lock:  # reentrant: flush() re-acquires
            if plane.pending_ops() > 0:
                plane.flush()
                self.refresh()
            doc = self.doc_healthy(name)
            if doc is None or not self.covers(name, document):
                return None
            # plane-integrated clocks ARE the local state vector (queue
            # was just flushed), so the diff is computed before building
            # Items — a nearly-current reconnect pays for its tail, not
            # the full doc
            local_sv = self._local_sv(doc)
            target_sv = decode_state_vector(sv_bytes) if sv_bytes else {}
            sm: dict[int, int] = {}
            for client, clock in target_sv.items():
                if local_sv.get(client, 0) > clock:
                    sm[client] = clock
            for client in local_sv:
                if client not in target_sv:
                    sm[client] = 0
            return self._encode_from_sm(doc, sm)

    # -- batched catch-up (the storm path) -----------------------------------

    async def batched_sync(self, name: str, document, sv_bytes: Optional[bytes]):
        """Enqueue a SyncStep1 for device-triaged batch serving.

        Every request that lands in the same event-loop window shares
        ONE `state_vector_diff` kernel call (tpu/kernels.py) — the
        O(docs x clients) triage of a reconnect storm runs on the
        device, and only the per-request item encode stays host-side.
        Resolves to SyncStep2 bytes, or None = CPU fallback.
        """
        import asyncio

        if self.paused:
            return None  # supervisor drain: serve from the CPU document
        future = asyncio.get_event_loop().create_future()
        self._inflight.add(future)
        future.add_done_callback(self._inflight.discard)
        self._catchup_queue.append((name, document, sv_bytes, future))
        if not self._catchup_scheduled:
            self._catchup_scheduled = True
            # strong ref: a GC'd drain task would strand every waiter
            task = asyncio.ensure_future(self._drain_catchup())
            self._drain_tasks.add(task)
            task.add_done_callback(self._drain_tasks.discard)
        return await future

    def abort_pending(self) -> None:
        """Resolve every outstanding batched-sync waiter to CPU fallback.

        The supervisor's breaker-open drain: a wedge mid-flight leaves
        drain tasks blocked on the flush lock with their waiters'
        futures unresolved — clients would stall on SyncStep2 forever.
        The drain tasks' own `future.done() or set_result(...)` guards
        make the eventual (post-unwedge) resolution a no-op.
        """
        for future in list(self._inflight):
            if not future.done():
                future.set_result(None)

    async def _drain_catchup(self) -> None:
        self._catchup_scheduled = False
        batch, self._catchup_queue = self._catchup_queue, []
        if not batch:
            return
        plane = self.plane
        # device-lane admission (tpu/scheduler.py): the drain flushes
        # and runs the triage kernel — interactive class, a joiner is
        # blocked on the reply. A parked lane (breaker open) resolves
        # the batch to CPU fallback, exactly like abort_pending.
        ticket = None
        if plane.lane is not None:
            from .scheduler import CLASS_INTERACTIVE, LaneDeferred

            try:
                ticket = await plane.lane.admit(
                    CLASS_INTERACTIVE, site="sync"
                )
            except LaneDeferred:
                for *_rest, future in batch:
                    future.done() or future.set_result(None)
                return
        try:
            # the whole drain — flush, refresh, triage, item encode —
            # holds the flush lock: every step reads device state, and a
            # concurrent executor-side flush donates the buffers it reads
            async with plane.flush_lock:
                # awaits the executor's flush: ring-only, read by no metric
                tracer = get_tracer()
                started = time.perf_counter() if tracer.enabled else None
                try:
                    await self._drain_catchup_locked(batch)
                finally:
                    if started is not None:
                        tracer.add_span(
                            "serving.catchup_drain",
                            started,
                            time.perf_counter(),
                            batch=len(batch),
                        )
        finally:
            if ticket is not None:
                ticket.release()

    async def _drain_catchup_locked(self, batch: list) -> None:
        import asyncio

        plane = self.plane
        try:
            if plane.pending_ops() > 0:
                try:
                    # device step off the loop (see _flush_now)
                    await asyncio.get_event_loop().run_in_executor(
                        None, plane.flush
                    )
                except Exception:
                    # the dead flush already consumed queued ops — the
                    # same fault TpuMergeExtension._flush handles by
                    # degrading every served doc with a full-state CPU
                    # broadcast; route through the same safety model
                    # instead of silently dropping captured updates
                    for *_rest, future in batch:
                        future.done() or future.set_result(None)
                    if self.flush_failure_handler is not None:
                        self.flush_failure_handler()
                    return
                self.refresh()
            # triage rows: healthy, covering docs only (the rest resolve
            # to None and fall back to the CPU path)
            rows: list[tuple] = []  # (doc, local_sv, target_sv, columns, future)
            for name, document, sv_bytes, future in batch:
                doc = self.doc_healthy(name)
                if doc is None or not self.covers(name, document):
                    future.done() or future.set_result(None)
                    continue
                local_sv = self._local_sv(doc)
                try:
                    target_sv = decode_state_vector(sv_bytes) if sv_bytes else {}
                except Exception:
                    future.done() or future.set_result(None)
                    continue
                columns = sorted(set(local_sv) | set(target_sv))
                rows.append((doc, local_sv, target_sv, columns, future))
            if not rows:
                return
            # one gathered device read covers every doc in the batch —
            # the storm's delete-set reads must not pay per-slot RTTs,
            # and the transfer runs OFF the loop like every device step
            batch_docs = [row[0] for row in rows]
            await asyncio.get_event_loop().run_in_executor(
                None, lambda: self.prefetch_tombstones(batch_docs)
            )
            if len(rows) == 1:
                # lone reconnect (the steady-state case): the host dict
                # diff costs microseconds — save the kernel dispatch and
                # the device round-trip for actual storms
                doc, local_sv, target_sv, _, future = rows[0]
                sm = {}
                for cid, clock in target_sv.items():
                    if local_sv.get(cid, 0) > clock:
                        sm[cid] = clock
                for cid in local_sv:
                    if cid not in target_sv:
                        sm[cid] = 0
                if not future.done():
                    try:
                        future.set_result(self._encode_from_sm(doc, sm))
                    except Exception:
                        future.set_result(None)
                return
            # every (doc, client) pair of the storm, flat: the diff is
            # elementwise, so one fixed-width program serves any mix of
            # request count and state-vector width
            pairs = sum(len(columns) for _, _, _, columns, _ in rows)
            server = np.zeros(pairs, np.int32)
            client = np.zeros(pairs, np.int32)
            at = 0
            for _, local_sv, target_sv, columns, _ in rows:
                for cid in columns:
                    server[at] = local_sv.get(cid, 0)
                    client[at] = target_sv.get(cid, 0)
                    at += 1
            missing_from, missing_len = self._triage(server, client)
            at = 0
            for doc, local_sv, target_sv, columns, future in rows:
                first, at = at, at + len(columns)
                if future.done():
                    continue
                try:
                    sm = {
                        cid: int(missing_from[first + j])
                        for j, cid in enumerate(columns)
                        if missing_len[first + j] > 0
                    }
                    future.set_result(self._encode_from_sm(doc, sm))
                except Exception:
                    future.set_result(None)  # degrade this request to CPU
        except Exception:
            for *_rest, future in batch:
                future.done() or future.set_result(None)

    # (doc, client) pairs per triage dispatch: a fixed ladder, so the
    # size of a storm never compiles a program in the serving path; a
    # storm above the widest runs in chunks of it
    _TRIAGE_WIDTHS = (256, 4096, 65536)

    def _triage(
        self, server: np.ndarray, client: np.ndarray, warmup: bool = False
    ) -> "tuple[np.ndarray, np.ndarray]":
        """state_vector_diff over flat int32 clock pairs, on the
        plane's own chip (the triage has no arena operand to follow,
        so uncommitted inputs would run every cell's diff on the
        default device). Returns (missing_from, missing_len)."""
        import jax

        from .kernels import state_vector_diff

        plane = self.plane
        widest = self._TRIAGE_WIDTHS[-1]
        missing_from = np.empty_like(server)
        missing_len = np.empty_like(server)
        for at in range(0, server.size, widest):
            n = min(widest, server.size - at)
            width = next(w for w in self._TRIAGE_WIDTHS if w >= n)
            padded = np.zeros((2, width), np.int32)
            padded[0, :n] = server[at : at + n]
            padded[1, :n] = client[at : at + n]
            with plane.compile_watch.track("sv_diff", (width,), warmup=warmup):
                diff_from, diff_len = state_vector_diff(
                    jax.device_put(padded[0], plane.device),
                    jax.device_put(padded[1], plane.device),
                )
                missing_from[at : at + n] = np.asarray(diff_from)[:n]
                missing_len[at : at + n] = np.asarray(diff_len)[:n]
            plane._note_dispatch("sync")
        return missing_from, missing_len

    def warmup_triage(self, width: int) -> None:
        """Compile the catch-up triage program at one ladder width."""
        zeros = np.zeros(width, np.int32)
        self._triage(zeros, zeros, warmup=True)

    def build_broadcast(self, name: str) -> Optional[bytes]:
        """Merged update for ops integrated since the last broadcast.

        Items come from the doc's serve log (everything consumed by the
        device or host-integrated since the cursor, minus presync
        records — receivers get pre-load state via sync). The delete
        set carries exactly the WINDOW's delete ranges: the kernel
        applies id-range tombstones unconditionally over ids the
        lowerer proved integrated, and a host/device divergence is
        caught by the health check (retire + full-state CPU fallback)
        before the next broadcast — so shipping the full device
        tombstone state every time (O(doc-lifetime deletes) per
        broadcast) is not needed for safety. Cold joiners still get the
        complete device-proved set via the sync path. The cursor only
        advances on a successfully encoded payload (or a genuinely
        empty window), so a bail-out never strands ops.
        """
        pair = self.build_broadcast_pair(name)
        return None if pair is None else pair[0]

    def _encode_window(self, doc: PlaneDoc, window: list[LogRec]) -> Optional[bytes]:
        """Update bytes for a record window, or None for an empty one."""
        window_ds = DeleteSet()
        has_inserts = False
        for rec in window:
            if rec.op.kind == KIND_DELETE:
                window_ds.add(rec.op.client, rec.op.clock, rec.op.run_len)
            elif rec.op.kind == KIND_INSERT:
                has_inserts = True
        if not has_inserts and not window_ds.clients:
            return None
        encoder = Encoder()
        body = self._encode_window_native(doc, window, None)
        if body is not None:
            encoder.write_bytes(body)
        else:
            by = self._group_items(doc, window)
            encoder.write_var_uint(len(by))
            for client in sorted(by, reverse=True):
                items = by[client]
                _write_structs(encoder, items, client, items[0].id.clock)
        window_ds.sort_and_merge()
        window_ds.write(encoder)
        return encoder.to_bytes()

    def build_broadcast_pairs(
        self, names: "list[str]"
    ) -> "tuple[list[tuple[str, Optional[tuple[bytes, Optional[bytes]]]]], list[str]]":
        """Batched window drain -> (pairs, failed_names).

        Lane docs resolve in ONE native call (the per-doc Python
        overhead dominates at 10k-doc widths; a missing slot yields a
        None entry, not an exception), Python-path docs fall back to
        build_broadcast_pair each — WITH per-doc isolation: one doc's
        encode failure lands it in failed_names instead of aborting
        the other 10k docs' windows."""
        plane = self.plane
        out: list = []
        failed: list[str] = []
        lane_names: list = []
        lane_args: list = []
        for name in names:
            doc = plane.docs.get(name)
            if doc is not None and doc.lane_slot is not None and plane._lane is not None:
                lane_names.append(name)
                lane_args.append(
                    (doc.lane_slot, self.broadcast_cursor.get(name, 0))
                )
            else:
                try:
                    out.append((name, self.build_broadcast_pair(name)))
                except Exception:
                    failed.append(name)
        if lane_args:
            results = plane._lane_codec.lane_windows_batch(plane._lane, lane_args)
            for name, (full, cross, new_idx) in zip(lane_names, results):
                self.broadcast_cursor[name] = new_idx
                if full is None:
                    out.append((name, None))
                else:
                    plane.counters["plane_broadcasts"] += 1
                    out.append((name, (full, cross)))
        return out, failed

    def build_broadcast_pair(
        self, name: str
    ) -> "Optional[tuple[bytes, Optional[bytes]]]":
        """(full_window_update, cross_instance_update or None).

        The full frame goes to local connections. The cross-instance
        frame excludes REMOTE-origin records (ops that arrived from a
        peer instance) — every peer already has them from the original
        publisher, and republishing would amplify traffic O(N^2) in
        instance count. It is None when the window holds no local ops.
        When the window is all-local the same bytes serve both.
        """
        plane = self.plane
        doc = plane.docs.get(name)
        if doc is None:
            return None
        if doc.lane_slot is not None:
            # native path: one C call builds both frames' update bytes
            full, cross, new_idx, _ = plane._lane_codec.lane_window(
                plane._lane, doc.lane_slot, self.broadcast_cursor.get(name, 0)
            )
            self.broadcast_cursor[name] = new_idx
            if full is None:
                return None
            plane.counters["plane_broadcasts"] += 1
            return full, cross
        log = doc.serve_log
        cursor = min(self.broadcast_cursor.get(name, 0), len(log))
        window = [rec for rec in log[cursor:] if not rec.op.presync]
        if not window:
            self.broadcast_cursor[name] = len(log)
            return None
        full = self._encode_window(doc, window)
        if full is None:
            self.broadcast_cursor[name] = len(log)
            return None
        local_window = [rec for rec in window if not rec.remote]
        if len(local_window) == len(window):
            local = full
        elif not local_window:
            local = None
        else:
            local = self._encode_window(doc, local_window)
        self.broadcast_cursor[name] = len(log)
        plane.counters["plane_broadcasts"] += 1
        return full, local


class TpuSyncSource:
    """`document.sync_source` adapter: SyncStep2 bytes from the plane.

    Any serving error degrades to the CPU path (return None) rather
    than failing the client's sync.
    """

    def __init__(self, serving: PlaneServing, name: str, document) -> None:
        self.serving = serving
        self.name = name
        self.document = document

    def encode_state_as_update(self, sv_bytes: Optional[bytes]) -> Optional[bytes]:
        try:
            return self.serving.encode_state_as_update(self.name, self.document, sv_bytes)
        except Exception:
            from ..server import logger as _logger_mod

            _logger_mod.log_error(
                f"plane sync serve failed for {self.name!r}; using CPU path"
            )
            return None

    async def encode_state_as_update_async(self, sv_bytes: Optional[bytes]) -> Optional[bytes]:
        """Batched (storm) variant: concurrent SyncStep1s share one
        device state-vector-diff triage — see PlaneServing.batched_sync."""
        try:
            return await self.serving.batched_sync(self.name, self.document, sv_bytes)
        except Exception:
            from ..server import logger as _logger_mod

            _logger_mod.log_error(
                f"plane batched sync failed for {self.name!r}; using CPU path"
            )
            return None
