"""The TPU merge plane: cross-document update queue + batched integrate.

Replaces the reference's per-connection apply loop (SURVEY.md §3.3 hot
loop) with a micro-batched device step: updates from ALL documents are
lowered to dense ops, padded into (K slots, S sequences) tensors, and
integrated by one jitted kernel call. Exposed as `TpuMergeExtension`
hooking the same onChange boundary the reference's extensions use, with
the CPU document remaining the authoritative fallback.

Arena rows are *sequences*, not documents: a plain text doc occupies
one row; a tree doc (ProseMirror XML) occupies one row per element
child-list, so the same YATA kernel integrates every sequence of every
document in one batch. Map items (Y.Map entries, XML attributes) are
host-side last-writer-wins records that never ride the device — they
go straight to the doc's serve log.
"""

from __future__ import annotations

import asyncio
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..aio import spawn_tracked
from ..observability.device_watch import CompileTracker, pytree_nbytes
from ..observability.flight_recorder import get_flight_recorder
from ..observability.tracing import UpdateTraceBook, get_tracer
from ..server.types import Extension, Payload
from .kernels import (
    KIND_DELETE,
    KIND_INSERT,
    NONE_CLIENT,
    OpBatch,
    make_empty_state,
)
from .lowering import DenseOp, DocLowerer, units_to_text


@dataclass
class LogRec:
    """One serve-log record: an op the plane integrated (device or host).

    slot is None for host-only map items; unit_off indexes the slot's
    unit log where the op's payload starts (sequence inserts only).
    """

    op: DenseOp
    slot: Optional[int] = None
    unit_off: int = 0
    # op arrived from a peer instance (redis origin): excluded from the
    # cross-instance window republish — every peer already received it
    # from the original publisher (echo amplification would be O(N^2))
    remote: bool = False


@dataclass
class PlaneDoc:
    """Per-document host state: sequence registry + serve log."""

    name: str
    lowerer: DocLowerer = field(default_factory=DocLowerer)
    seqs: dict[tuple, int] = field(default_factory=dict)  # seq_key -> slot
    serve_log: list[LogRec] = field(default_factory=list)
    # delete ranges that target host-side map items (client, clock, len)
    map_tombstones: list[tuple] = field(default_factory=list)
    retired: bool = False
    retire_reason: Optional[str] = None  # first reason wins (see retire_doc)
    # native text lane (see native/text_lane.cpp): when set, the whole
    # host path — lowering, serve log, unit log, dispatch queue — lives
    # in C++; serve_log/unit_logs here are lazy materializations for
    # the cold serving paths, cached under lane_cache_key
    lane_slot: Optional[int] = None
    lane_cache_key: Optional[tuple] = None
    # residency compaction (tpu/residency.py): client -> ([starts],
    # [(start, end, left_id, right_id)]) for id ranges the tombstone-GC
    # kernel removed from the device — future ops whose origins land in
    # a removed range re-anchor to the recorded live neighbor
    origin_remap: dict = field(default_factory=dict)


class _FlushStaging:
    """One reusable host-side batch staging buffer, sized at the max
    flush shape (K_max, D). Each batch takes a `(k, b)` view of it —
    zero fresh numpy allocations on the flush hot path (the old builder
    allocated 8 fresh (K, D) arrays per batch, which dominated host
    time at the 100k-doc regime). MergePlane keeps TWO of these and
    alternates per batch (double buffering): the host build of batch
    i+1 must never mutate arrays whose upload for batch i may still be
    in flight on an asynchronously-transferring runtime."""

    __slots__ = ("fields", "slots")

    # per-field reset value: left/right client columns default to the
    # NONE_CLIENT sentinel, everything else to zero (KIND_NOOP)
    _DEFAULTS = (0, 0, 0, 0, NONE_CLIENT, 0, NONE_CLIENT, 0)
    _DTYPES = (
        np.int32, np.uint32, np.int32, np.int32,
        np.uint32, np.int32, np.uint32, np.int32,
    )

    def __init__(self, k_max: int, num_docs: int) -> None:
        self.fields = tuple(
            np.full((k_max, num_docs), default, dtype)
            for default, dtype in zip(self._DEFAULTS, self._DTYPES)
        )
        self.slots = np.zeros((num_docs,), np.int32)

    def views(self, k: int, b: int) -> tuple:
        """(k, b) views of the 8 op fields, reset to noop defaults."""
        views = tuple(field[:k, :b] for field in self.fields)
        for view, default in zip(views, self._DEFAULTS):
            view[...] = default
        return views

    def slot_view(self, b: int) -> np.ndarray:
        return self.slots[:b]

    def nbytes(self, k: int, b: int, with_slots: bool) -> int:
        per_field = sum(dtype().itemsize for dtype in self._DTYPES)
        return k * b * per_field + (b * 4 if with_slots else 0)


class _AppendStaging:
    """Run-merge twin of _FlushStaging: the append fast path ships only
    three (K, B) run fields (client, clock, run_len) plus the (B,)
    routing vector — under half the dense op layout's bytes — and only
    the run_len view needs resetting per batch (run_len == 0 IS the
    noop sentinel; stale client/clock under a zero length are never
    read by the kernel)."""

    __slots__ = ("client", "clock", "run_len", "slots")

    def __init__(self, k_max: int, num_docs: int) -> None:
        self.client = np.zeros((k_max, num_docs), np.uint32)
        self.clock = np.zeros((k_max, num_docs), np.int32)
        self.run_len = np.zeros((k_max, num_docs), np.int32)
        self.slots = np.zeros((num_docs,), np.int32)

    def views(self, k: int, b: int) -> tuple:
        views = (
            self.client[:k, :b],
            self.clock[:k, :b],
            self.run_len[:k, :b],
        )
        views[2][...] = 0
        return views

    def slot_view(self, b: int) -> np.ndarray:
        return self.slots[:b]

    def nbytes(self, k: int, b: int) -> int:
        return k * b * 12 + b * 4


class MergePlane:
    """Device-resident arenas for up to `num_docs` sequences.

    (The parameter keeps its historical name; for plain text docs
    sequences == documents. Tree docs consume one row per sequence.)

    Pass a `jax.sharding.Mesh` (axes "doc" × "unit", see
    tpu/sharding.py) to back the arenas with multi-chip sharded state:
    the sequence axis is data-parallel over the mesh's doc axis (ICI
    collectives only for the global op count), the arena axis optionally
    sequence-parallel over the unit axis. Host-side logic (queues,
    serve logs, health readbacks) is identical either way.
    """

    def __init__(
        self,
        num_docs: int = 256,
        capacity: int = 4096,
        max_slots_per_flush: int = 16,
        mesh=None,
        arena: str = "unit",
        device=None,
    ) -> None:
        """arena: "unit" (one arena slot per UTF-16 unit; capacity =
        units) or "rle" (one entry per run of consecutively-typed
        units; capacity = ENTRIES). The RLE arena's cost grows with op
        count + fragmentation instead of cumulative unit count, so
        long-lived busy docs survive churn that exhausts the unit
        arena — the device-side replacement for yjs GC semantics
        (reference `packages/server/src/types.ts:152-155` yDocOptions.gc).

        device: pin the whole arena (and every upload) to ONE jax
        device — the multi-device cell plane (tpu/cells.py) builds one
        plane per chip this way. The arena state is committed to the
        device, so every jitted step runs there; uploads device_put
        straight to it (never touching the default device). Mutually
        exclusive with mesh= (a mesh IS a device layout).
        """
        if arena not in ("unit", "rle"):
            raise ValueError(f"unknown arena {arena!r}")
        if device is not None and mesh is not None:
            raise ValueError("pass device= or mesh=, not both")
        self.arena = arena
        # the integrate span's attribute for a row's length, in what
        # `capacity` counts on this arena
        self._span_row = {"row_entries": capacity} if arena == "rle" else {"row_units": capacity}
        # run-length arena: each row's occupied entries (num_runs) as
        # the last health readback saw them. A cleared row's is set to
        # 0 and a defragmented row's to what the compaction left, when
        # that happens, so what a row has grown by at the next readback
        # is what its ops took
        self._rle_row_entries = np.zeros(num_docs, np.int64) if arena == "rle" else None
        self.device = device
        self.num_docs = num_docs
        self.capacity = capacity
        self.max_slots_per_flush = max_slots_per_flush
        self.mesh = mesh
        # serializes flush + device readbacks when the extension runs
        # flushes off the event loop (direct synchronous use — tests —
        # never contends)
        self.flush_lock = asyncio.Lock()
        # thread-level companion: flush() donates the old state buffers
        # to the kernel, so a reader interleaving with an executor-side
        # flush can observe garbage (and must never RETIRE a doc based
        # on it). flush() holds this for the duration of the device
        # step; synchronous readers (text, health checks, the sync
        # serve adapter) acquire it. Reentrant so a sync serve can hold
        # it across its own flush()+reads sequence.
        self._step_lock = threading.RLock()
        self._sharded_step = None
        self._sharded_sparse_step = None
        self._sharded_compact_step = None
        self._sharded_append_step = None
        self._op_shardings = None
        self._sparse_op_shardings = None
        self._slots_sharding = None
        self._append_field_sharding = None
        if mesh is not None:
            from .sharding import (
                make_sharded_rle_sparse_step,
                make_sharded_rle_state,
                make_sharded_rle_step,
                make_sharded_sparse_step,
                make_sharded_state,
                make_sharded_step,
                ops_sharding,
                sparse_ops_sharding,
            )

            doc_axis = mesh.shape["doc"]
            unit_axis = mesh.shape["unit"]
            if num_docs % doc_axis or capacity % unit_axis:
                raise ValueError(
                    f"num_docs ({num_docs}) must be a multiple of the mesh doc "
                    f"axis ({doc_axis}) and capacity ({capacity}) a multiple of "
                    f"the unit axis ({unit_axis})"
                )
            from .sharding import (
                make_sharded_compact_step,
                make_sharded_rle_compact_step,
            )

            from .sharding import (
                make_sharded_append_step,
                make_sharded_rle_append_step,
            )

            if arena == "rle":
                self.state = make_sharded_rle_state(mesh, num_docs, capacity)
                self._sharded_step = make_sharded_rle_step(mesh)
                self._sharded_sparse_step = make_sharded_rle_sparse_step(mesh)
                self._sharded_compact_step = make_sharded_rle_compact_step(mesh)
                self._sharded_append_step = make_sharded_rle_append_step(mesh)
            else:
                self.state = make_sharded_state(mesh, num_docs, capacity)
                self._sharded_step = make_sharded_step(mesh)
                self._sharded_sparse_step = make_sharded_sparse_step(mesh)
                self._sharded_compact_step = make_sharded_compact_step(mesh)
                self._sharded_append_step = make_sharded_append_step(mesh)
            self._op_shardings = ops_sharding(mesh)
            self._sparse_op_shardings, self._slots_sharding = sparse_ops_sharding(
                mesh
            )
            from jax.sharding import NamedSharding, PartitionSpec

            self._append_field_sharding = NamedSharding(
                mesh, PartitionSpec(None, None)
            )
        elif device is not None:
            # build the arena ON its chip (never a transient copy on
            # the default device), then COMMIT it: jit follows committed
            # input placement, so every step (flush, canary, warm,
            # compact) runs on this device with no resharding
            import jax

            with jax.default_device(device):
                state = self._make_empty(num_docs, capacity)
            self.state = jax.device_put(state, device)
        else:
            self.state = self._make_empty(num_docs, capacity)
        self.docs: dict[str, PlaneDoc] = {}
        self.free: list[int] = list(range(num_docs - 1, -1, -1))
        self.slot_owner: dict[int, str] = {}  # slot -> doc name
        self.queues: dict[int, list[DenseOp]] = {}
        # slots with (possibly) queued ops: per-batch bookkeeping —
        # depth scan, drain, dispatch — walks THIS set, O(busy), never
        # the full queue registry, O(D). Maintained lock-free under the
        # GIL: enqueue_update adds AFTER every extend (unconditionally),
        # so a drain-side discard that races an enqueue is always
        # repaired by the enqueuer's own add; a stale member whose
        # queue emptied elsewhere (retire/release also discard) is
        # pruned at the next depth scan. Native-lane queues are not
        # tracked here — the lane keeps its own registry of nonempty
        # queues in C++ (lane_queue_max / lane_drain are O(lane slots)).
        self._busy_slots: set[int] = set()
        # per-slot insert units handed to the device so far / as of the
        # last completed flush. Serve logs are written at ENQUEUE time
        # (so broadcasts never wait on the device); health checks
        # therefore compare device lengths against the VALIDATED
        # snapshot — the dispatch tally at the moment the readback was
        # taken — never against the (optimistically ahead) host logs.
        # ndarrays so the post-flush sweep is one vectorized compare
        # over every slot instead of a Python loop over every doc.
        self.dispatched_units = np.zeros(num_docs, np.int64)
        self.validated_units = np.zeros(num_docs, np.int64)
        # monotonic plane-wide dispatch tally, bumped ONLY at the two
        # dispatch sites below — never by slot rebinds or residency
        # rebuilds (hydration credits per-slot counters wholesale). The
        # fleet autoscaler (fleet/controller.py) diffs this for a load
        # RATE that stays honest while docs migrate between cells.
        self.dispatched_total = 0
        # minimal-work run merge (the sequential fast path): the flush
        # classifier routes a drained column to the O(new ops) append
        # program only when every op chains off the column's RANK TAIL
        # — the id of the last unit in rank order, tracked host-side so
        # eligibility costs no device read. A tail is (client, clock)
        # with client == NONE_CLIENT meaning "empty row"; _tail_known
        # gates the whole check (False -> the column takes the full
        # integrate, and the slot joins _tail_dirty so the next flush
        # cycle's health readback (health_probe) re-arms it in the same
        # read, over the dirty slots — never an O(D) sweep). Rows start, and
        # are cleared back to, known-empty; full-integrate columns and
        # residency compaction (rank remaps) invalidate.
        self.run_merge_enabled = True
        self._tail_client = np.full(num_docs, NONE_CLIENT, np.uint32)
        self._tail_clock = np.zeros(num_docs, np.int64)
        self._tail_known = np.ones(num_docs, bool)
        self._tail_dirty: set[int] = set()
        # slots currently bound to a live (non-retired) doc: the post-
        # flush health sweep masks with this so freed/retired rows
        # compared against stale caches can't read as desyncs
        self.slot_live = np.zeros(num_docs, bool)
        # per-slot binding generation, bumped at every alloc/release/
        # retire. Health snapshots (_sync_health) record the generations
        # they were taken under; a compare is only meaningful when the
        # snapshot's generation matches the slot's current one —
        # otherwise the cached device row belongs to a previous tenant
        # of the slot and must not condemn the new one.
        self.slot_gen = np.zeros(num_docs, np.int64)
        self.last_gen: Optional[np.ndarray] = None
        # bumped whenever device state may have changed (a flush cycle
        # completed, a slot was cleared): consumers caching device
        # readbacks (serving's tombstone cache) key on (slot_gen, this)
        self.flush_epoch = 0
        # docs with new serve-log records since the last broadcast pass
        self.dirty: set[str] = set()
        # last combined health readback (see _sync_health): the remote-
        # attached runtime charges ~a full RTT per transfer, so the
        # flush cycle fetches lengths+overflow as ONE array and callers
        # adopt these instead of re-reading device state
        self.last_lengths: Optional[np.ndarray] = None
        self.last_overflows: Optional[np.ndarray] = None
        # unit payloads never touch the device: slot assignment in the
        # append-only arena is deterministic (arena slot = arrival
        # index), so shipped payloads land here, indexed by slot. An
        # entry is an int UTF-16 unit for text, or the decoded Content
        # object for rich units (formats/embeds/types/values).
        self.unit_logs: dict[int, list] = {}
        self.projected_len: dict[int, int] = {}
        self.total_integrated = 0
        # degradation accounting: at 100k docs nobody notices 3% of docs
        # silently falling off the plane unless it is counted
        self.counters: dict[str, int] = {
            "docs_retired_overflow": 0,
            "docs_retired_desync": 0,
            "docs_retired_unsupported": 0,
            "docs_retired_capacity": 0,
            "docs_retired_fallback": 0,
            "docs_retired_plane_full": 0,
            "docs_retired_lane_demote": 0,
            "docs_recycled": 0,
            # residency subsystem (tpu/residency.py): slots as a managed
            # cache — idle docs snapshot off, cold docs re-admit through
            # the hydration queue, pressured rows compact in place
            "docs_evicted": 0,
            "docs_hydrated": 0,
            "docs_compacted": 0,
            "hydrations_declined": 0,
            "compactions_declined": 0,
            "sync_serves": 0,
            # join-storm sync cache (serving.SyncFrameCache): joiners
            # sharing a (doc, state-vector) within one flush epoch pay
            # one encode, not one each
            "sync_cache_hits": 0,
            "sync_cache_misses": 0,
            "sync_cache_evictions": 0,
            # on-device catch-up encode: slots whose tombstone read
            # shipped as the packed device readback vs the full-row
            # host gather (pack-width overflow or pack disabled)
            "sync_encode_device": 0,
            "sync_encode_host": 0,
            "plane_broadcasts": 0,
            "cpu_fallbacks": 0,
            # flush-engine accounting: staging buffers are allocated
            # once and reused (the regression suite pins allocs flat
            # while reuses grow), and sparse vs dense says which
            # dispatch layout flush cycles actually take
            "flush_staging_allocs": 0,
            "flush_staging_reuses": 0,
            "flush_batches_sparse": 0,
            "flush_batches_dense": 0,
            # minimal-work run merge: ops dispatched through the
            # append fast path vs the full-row integrate, plus the
            # fast-path batch count (the sparse/dense counters above
            # keep counting only full-integrate batches)
            "flush_batches_fast": 0,
            "flush_fast_ops": 0,
            "flush_slow_ops": 0,
            # per device batch: the rows that really carry ops (the
            # fast set, the slow set, or every busy slot of a dense
            # batch) and the bucket width B they were padded to
            "flush_busy_rows": 0,
            "flush_bucket_rows": 0,
            # why the classifier sent an op to the full-row integrate
            # (they sum to flush_slow_ops while run_merge_enabled): an
            # insert that names a right origin, so not at its row's
            # tail; a delete; an append that does not chain off the
            # tracked tail (a second author got there first, the tail
            # is unknown) or that shares its column with one of those
            "slow_ops_mid_row": 0,
            "slow_ops_delete": 0,
            "slow_ops_concurrent": 0,
            # arena units the integrate batches swept: each batch's
            # bucket rows, padding included, x the row's capacity (the
            # kernels sweep whole rows, not the document's length).
            # The unit arena's; a run-length row is counted in entries
            "integrate_row_units": 0,
            "integrate_row_entries": 0,
            # run-length arena: entries the device took for the ops it
            # integrated, a hydration's snapshot ops among them (splits
            # and new runs; every row's num_runs rides the cycle's
            # health readback and is differenced row by row). Over the
            # ops flushed it is what an op costs a row of `capacity`
            # entries, where the host projects one (enqueue_update)
            "rle_entries_appended": 0,
            # broadcast passes run, and the time from when each was
            # scheduled (the first capture since the last pass) to when
            # it ran: the coalescing window, the phase alignment and
            # the loop's lag — what an update waits by design
            "broadcast_passes": 0,
            "broadcast_wait_ms_total": 0.0,
            # warm-grid programs that failed to compile or run (the
            # detail rows are in warm_failures)
            "warm_failures": 0,
        }
        # set by the multi-device router (tpu/cells.py) to "cell_ops.<i>":
        # the ops this plane flushed, under a key that a sum of the
        # cells' counters by key keeps apart
        self.cell_ops_key: Optional[str] = None
        # listen-time warm pass ledger (TpuMergeExtension.on_listen):
        # entries in the pass, programs this plane compiled / found
        # covered by the shared registry, wall seconds, and whether the
        # pass ran to its end. A failed entry is kept as {site, shape,
        # error} — /healthz (supervisor snapshot) and chip_smoke.py
        # read both.
        self.warm_stats: dict = {
            "entries": 0,
            "compiled": 0,
            "covered": 0,
            "seconds": 0.0,
            "done": False,
        }
        self.warm_failures: "list[dict]" = []
        # last completed flush cycle's stage breakdown (exported as
        # gauges by observability/extension.py): host build / upload /
        # device+readback ms,
        # the (K, B) shape dispatched, busy width and fraction, bytes
        # shipped. Overwritten per cycle, never accumulated.
        self.flush_stats: dict[str, float] = {
            "build_ms": 0.0,
            "upload_ms": 0.0,
            "dispatch_ms": 0.0,
            "device_sync_ms": 0.0,
            "busy_slots": 0,
            "busy_fraction": 0.0,
            "batch_k": 0,
            "batch_b": 0,
            "batches": 0,
            "upload_bytes": 0,
            # per-cycle fast/slow split (run-merge classifier): the
            # fraction is this cycle's, the counters above accumulate
            "fast_path_ops": 0,
            "slow_path_ops": 0,
            "fast_path_fraction": 0.0,
        }
        # residency manager seam (tpu/residency.py): set by the manager
        # at construction. retire_doc consults it to preserve host logs
        # through compactable retires; observability exports the stats.
        self.residency = None
        self.residency_stats: dict[str, float] = {
            "evicted_docs": 0,
            "evicted_bytes": 0,
            "hydration_queue_depth": 0,
            "hydration_queue_peak": 0,
            "hydrations_inflight": 0,
            "hydration_p50_ms": 0.0,
            "hydration_p99_ms": 0.0,
            "last_hydration_batch": 0,
            "last_eviction_ms": 0.0,
            "last_compaction_ms": 0.0,
        }
        # double-buffered staging (see _FlushStaging): allocated on the
        # first flush, alternated per batch so building batch i+1 never
        # mutates arrays batch i's upload may still be reading. The
        # alternation alone only guarantees ONE batch of separation, so
        # _staging_inflight remembers each buffer's last uploaded device
        # arrays and _staging_for blocks on them before handing the
        # buffer out again — on an asynchronously-transferring runtime
        # a 3+-batch cycle must not reset staging[0] while batch 0's
        # transfer is still in flight (two dispatches have passed by
        # then, so the block is ~always a no-op).
        self._staging: "Optional[list[_FlushStaging]]" = None
        self._staging_inflight: "list[Optional[tuple]]" = [None, None]
        # fast-path twin of the staging pair: 3 run fields + routing,
        # same double-buffer + inflight-retire discipline, alternated
        # on its own batch counter (fast and slow batches interleave
        # freely within a cycle)
        self._append_staging: "Optional[list[_AppendStaging]]" = None
        self._append_inflight: "list[Optional[tuple]]" = [None, None]
        self._append_batches = 0
        # native text lane (enable_lane): the C++ host path for plain-
        # text docs. _lane_banned remembers docs that demoted (rich
        # content) so re-onboarding goes straight to the Python path.
        self._lane = None
        self._lane_codec = None
        self._lane_banned: set[str] = set()
        # update-lifecycle trace pipeline (observability/tracing.py):
        # the capture seam stamps sampled updates here; the flush loop
        # below carries their trace ids through drain → build → upload
        # → device → readback, and the broadcast pass closes them. One
        # truth test per flush batch when tracing is idle.
        self.update_traces = UpdateTraceBook()
        # device runtime watch (observability/device_watch.py): every
        # jitted dispatch — warmup, canary, live flush batch — is
        # classified fresh-compile vs cache-hit per (site, shape), and
        # fresh compiles past the warm grid raise the recompile-storm
        # alarm. device_stats accumulates the HBM/stall side: readback-
        # barrier time and the biggest single-cycle upload.
        self.compile_watch = CompileTracker()
        self.device_stats: dict[str, float] = {
            "readback_stall_ms_total": 0.0,
            "readback_stalls": 0,
            "upload_bytes_peak": 0,
        }
        # short-TTL memo for memory_stats (one scrape = one pytree walk)
        self._memory_stats_cache: "tuple[float, Optional[dict]]" = (0.0, None)
        # device-lane arbiter seam (tpu/scheduler.py): set by the owning
        # extension. The plane never admits itself — its CLIENTS (flush
        # engine, hydration, compaction, canary, warmup) hold the lane;
        # the dispatch sites below only ACCOUNT each device dispatch as
        # in-lane or bypass, so the scheduler-accounting test can pin
        # "no dispatch bypasses the arbiter" on the scheduled paths.
        self.lane = None

    def _note_dispatch(self, site: str, batches: int = 1) -> None:
        if self.lane is not None:
            self.lane.note_dispatch(site, batches)

    def note_warm_failure(self, site: str, shape, error: BaseException) -> None:
        """Keep one failed warm-grid entry where health, counters and
        the chip smoke read it. A live flush at this shape will fault
        to the CPU path (cpu_fallbacks), so it is an error, not a note."""
        from ..observability.device_watch import shape_label
        from ..server import logger as _logger_mod

        label = shape_label(shape)
        detail = f"{type(error).__name__}: {error}"[:500]
        self.counters["warm_failures"] += 1
        self.warm_failures.append({"site": site, "shape": label, "error": detail})
        _logger_mod.log_error(
            f"plane warm grid: {site} {label} FAILED on a "
            f"{self.num_docs}x{self.capacity} {self.arena} arena: {detail}"
        )

    # -- arena dispatch ----------------------------------------------------

    def _make_empty(self, num_docs: int, capacity: int):
        if self.arena == "rle":
            from .kernels_rle import make_empty_rle_state

            return make_empty_rle_state(num_docs, capacity)
        return make_empty_state(num_docs, capacity)

    def _step_fn(self):
        if self._sharded_step is not None:
            return self._sharded_step
        if self.arena == "rle":
            from .pallas_kernels_rle import integrate_op_slots_rle_fast

            return integrate_op_slots_rle_fast
        from .pallas_kernels import integrate_op_slots_fast

        return integrate_op_slots_fast

    def _sparse_step_fn(self):
        """The sparse (busy-doc) twin of _step_fn: takes (state, (K, B)
        ops, (B,) slot routing)."""
        if self._sharded_sparse_step is not None:
            return self._sharded_sparse_step
        if self.arena == "rle":
            from .pallas_kernels_rle import integrate_op_slots_rle_sparse_fast

            return integrate_op_slots_rle_sparse_fast
        from .pallas_kernels import integrate_op_slots_sparse_fast

        return integrate_op_slots_sparse_fast

    def _compact_step_fn(self):
        """The compact (tombstone-GC / defragment) kernel for this
        arena: takes (state, (B,) slot routing), returns (state,
        per-slot packed sizes). Called by the residency manager
        (tpu/residency.py) under the step lock."""
        if self._sharded_compact_step is not None:
            return self._sharded_compact_step
        if self.arena == "rle":
            from .pallas_kernels_rle import compact_doc_rows_rle_fast

            return compact_doc_rows_rle_fast
        from .pallas_kernels import compact_doc_rows_fast

        return compact_doc_rows_fast

    def _append_step_fn(self):
        """The run-append fast-path kernel: takes (state, (K, B) client,
        clock, run_len, (B,) slot routing), returns (state, applied-run
        count). Dispatched only for columns the flush classifier proved
        all-sequential (see _classify_fast)."""
        if self._sharded_append_step is not None:
            return self._sharded_append_step
        if self.arena == "rle":
            from .pallas_kernels_rle import append_run_slots_rle_sparse_fast

            return append_run_slots_rle_sparse_fast
        from .pallas_kernels import append_run_slots_sparse_fast

        return append_run_slots_sparse_fast

    def _health_probe_fn(self):
        """The flush cycle's health readback kernel for this arena:
        (state, (W,) slots) -> (2D + 2W,) uint32 [lengths...,
        overflows..., tail clients..., tail clocks...], on the
        run-length arena D more, every row's occupied entries.
        _sync_health reads everything it validates through this ONE
        program; the W slots are the rows whose rank tail the
        full-integrate path or a compaction invalidated (W = 0: none)."""
        if self.arena == "rle":
            from .kernels_rle import health_probe_rle

            return health_probe_rle
        from .kernels import health_probe

        return health_probe

    # -- native text lane --------------------------------------------------

    def enable_lane(self) -> bool:
        """Switch on the C++ host path for plain-text docs (see
        native/text_lane.cpp). Safe no-op when the codec is missing."""
        if self._lane is not None:
            return True
        from ..native import get_codec

        codec = get_codec()
        # gate on the NEWEST lane symbol: a stale prebuilt .so (build()
        # failed but the old module imported) must degrade to the safe
        # no-op, not AttributeError inside the serve path
        if codec is None or not hasattr(codec, "lane_window_sm"):
            return False
        self._lane_codec = codec
        self._lane = codec.lane_new()
        return True

    def register_lane(self, name: str) -> Optional[PlaneDoc]:
        """Register `name` on the native text lane (one slot, opened
        eagerly). Returns None when the lane is off / banned for this
        doc / the plane is full — caller falls back to register()."""
        if self._lane is None or name in self._lane_banned:
            return None
        doc = self.docs.get(name)
        if doc is not None:
            return doc if doc.lane_slot is not None else None
        if not self.free:
            return None
        slot = self.free.pop()
        doc = PlaneDoc(name)
        doc.lane_slot = slot
        self.docs[name] = doc
        self.slot_owner[slot] = name
        self.queues[slot] = []  # stays empty: ops queue natively
        self.unit_logs[slot] = []  # lazy materialization target
        self.projected_len[slot] = 0
        self.dispatched_units[slot] = 0
        self.validated_units[slot] = 0
        self.slot_live[slot] = True
        self.slot_gen[slot] += 1
        self._set_tail_empty(slot)
        self._lane_codec.lane_open(self._lane, slot)
        return doc

    def _enqueue_lane(
        self, doc: PlaneDoc, update: bytes, presync: bool, remote: bool
    ) -> int:
        slot = doc.lane_slot
        res = self._lane_codec.lane_apply(self._lane, slot, update, presync, remote)
        if res is None:
            # rich/tree/map content: this doc needs the Python path.
            # The ban makes the re-onboard (load-time retry or recycle)
            # take the plain register() route.
            self._lane_banned.add(doc.name)
            self.retire_doc(doc.name, "lane_demote")
            return 0
        ops_added, queued_units, queued_ops, root = res
        if root is not None and not doc.seqs:
            doc.seqs[("root", root)] = slot
        # RLE cost counts device-bound QUEUE entries, not serve-log
        # records: host-only GC records never consume arena entries
        # (mirrors the Python path routing GC to map_out). One entry
        # an op, as the Python path projects it: enqueue_update says
        # why one is right where the device may take two
        cost = queued_ops if self.arena == "rle" else queued_units
        projected = self.projected_len[slot] + cost
        if projected > self.capacity:
            self.retire_doc(doc.name, "capacity")
            return 0
        self.projected_len[slot] = projected
        if ops_added:
            self.dirty.add(doc.name)
        return ops_added

    def materialize_lane(self, doc: PlaneDoc) -> None:
        """Fill doc.serve_log / unit_logs / lowerer.known from the
        native lane for the Python serving paths (cold/stale syncs,
        text(), the RLE payload index). Cached on the log lengths, so
        repeated serves of an unchanged doc pay one export."""
        if doc.lane_slot is None or self._lane is None:
            return
        slot = doc.lane_slot
        key = self._lane_codec.lane_log_len(self._lane, slot)
        if doc.lane_cache_key == key:
            return
        ops, units_bytes, known, root = self._lane_codec.lane_export(
            self._lane, slot
        )
        self.unit_logs[slot] = np.frombuffer(
            units_bytes, np.dtype("<u2")
        ).tolist()
        parent = ("root", root) if root is not None else None
        recs = []
        for kind, client, clock, run_len, lc, lk, rc, rk, unit_off, flags in ops:
            gc = bool(flags & 2)
            op = DenseOp(
                kind=kind,
                client=client,
                clock=clock,
                run_len=run_len,
                left_client=lc,
                left_clock=lk,
                right_client=rc,
                right_clock=rk,
                deleted_content=bool(flags & 1),
                gc=gc,
                presync=bool(flags & 4),
                # mirrors the Python lowerer: the wire parent only
                # exists on origin-less items (and never on deletes/gc)
                parent=(
                    parent
                    if (
                        kind == KIND_INSERT
                        and not gc
                        and lc == NONE_CLIENT
                        and rc == NONE_CLIENT
                    )
                    else None
                ),
            )
            recs.append(
                LogRec(
                    op=op,
                    # gc records are host-only in the Python path
                    slot=None if gc else slot,
                    unit_off=unit_off,
                    remote=bool(flags & 8),
                )
            )
        doc.serve_log = recs
        doc.lowerer.known = dict(known)
        doc.lane_cache_key = key

    # -- registry ----------------------------------------------------------

    def register(self, name: str) -> PlaneDoc:
        doc = self.docs.get(name)
        if doc is None:
            doc = PlaneDoc(name)
            self.docs[name] = doc
        return doc

    def _alloc_seq(self, doc: PlaneDoc, seq_key: tuple) -> Optional[int]:
        slot = doc.seqs.get(seq_key)
        if slot is not None:
            return slot
        if not self.free:
            return None
        slot = self.free.pop()
        doc.seqs[seq_key] = slot
        self.slot_owner[slot] = doc.name
        self.queues[slot] = []
        self.unit_logs[slot] = []
        self.projected_len[slot] = 0
        self.dispatched_units[slot] = 0
        self.validated_units[slot] = 0  # freed slots keep length 0 too
        self.slot_live[slot] = True
        self.slot_gen[slot] += 1
        self._set_tail_empty(slot)
        return slot

    def note_trace(self, name: str) -> Optional[int]:
        """Capture-seam stamp: give one just-enqueued update a lifecycle
        trace id (sampled). Called by try_capture."""
        return self.update_traces.stamp(name)

    def release(self, name: str) -> None:
        doc = self.docs.pop(name, None)
        if doc is None:
            return
        self.dirty.discard(name)
        self.update_traces.drop(name)
        # Serialization: release() only runs from unload paths that hold
        # the extension's flush_lock (see TpuMergeExtension._flush_now
        # docstring), so no executor-side flush is in flight here —
        # _clear_slot may rebuild self.state without racing a device
        # step that donated its buffers. Do NOT take _step_lock on the
        # event loop: it can be held across a device step or a warmup
        # compile (tens of seconds cold), freezing every websocket.
        slots = set(doc.seqs.values())
        if doc.lane_slot is not None:
            slots.add(doc.lane_slot)  # may predate root discovery
            self._lane_codec.lane_close(self._lane, doc.lane_slot)
        for slot in slots:
            self.slot_owner.pop(slot, None)
            self.queues.pop(slot, None)
            self._busy_slots.discard(slot)
            self.unit_logs.pop(slot, None)
            self.projected_len.pop(slot, None)
            self.dispatched_units[slot] = 0
            self.validated_units[slot] = 0
            self.slot_live[slot] = False
            self.slot_gen[slot] += 1
            self.free.append(slot)
        # ONE fused device rebuild for every released row (a tree doc
        # spans many): the old per-slot _clear_slot rebuilt the whole
        # state pytree once per sequence
        self._clear_slots(sorted(slots))

    def retire_doc(self, name: str, reason: str, count: bool = True) -> None:
        """Permanently degrade a doc to the CPU path (rows stay allocated
        until unload so the name keeps resolving to 'unsupported').

        count=False marks the doc retired without incrementing the
        degradation counter — used when a failed RECYCLE re-retires the
        fresh registration of an incident that was already counted, so
        the counters keep meaning 'degradation incidents', not retire
        calls."""
        doc = self.docs.get(name)
        if doc is None:
            return
        if not doc.retired:
            doc.retired = True
            doc.retire_reason = reason
            # strict key access: every retire reason must be pre-declared
            # in __init__ so metrics exporters that bind to the counter
            # keys at configure time (observability/extension.py) can
            # never miss a degradation class added later
            if count:
                self.counters[f"docs_retired_{reason}"] += 1
            get_flight_recorder().record(name, "retire", reason=reason)
        self.update_traces.drop(name)
        doc.lowerer.unsupported = True
        # residency seam: a row-exhaustion retire keeps its host logs so
        # the compaction path (tpu/residency.py) can rebuild the doc in
        # place — a declined attempt calls drop_doc_logs to finish this.
        # Judged on the STICKY first reason, not this call's: the CPU
        # fallback re-retires with "fallback" and must not destroy the
        # logs a capacity retire just preserved.
        preserve = self.residency is not None and self.residency.wants_logs(
            doc, doc.retire_reason
        )
        if preserve:
            # the residency sweep visits preserved docs proactively, so
            # an idle retired doc doesn't hold these logs until its
            # next edit
            self.residency.note_preserved(doc.name)
        else:
            doc.serve_log = []
            doc.map_tombstones = []
        self.dirty.discard(name)
        # LOCK-FREE by documented invariant (not oversight): retires run
        # on the event loop (enqueue degrades, broadcast-timer fallback)
        # while an executor-side _build_batch may be slicing these same
        # queues under _step_lock. Taking that lock here would block the
        # loop for a device step or warmup compile. Safe without it:
        # (a) _build_batch's take/del is linearizable against clear()
        #     (it deletes exactly len(take) front items it captured);
        # (b) ops captured into `take` before the clear still dispatch,
        #     but land in rows whose generation is bumped below —
        #     slot_gen/slot_live masking excludes them from every health
        #     compare, and the rows stay inert until release() clears
        #     them under the extension flush_lock;
        # (c) unit_logs is REBOUND (not mutated): an in-flight serve
        #     holding the old list keeps a consistent snapshot.
        for slot in doc.seqs.values():
            self._tail_known[slot] = False  # rows go inert: never fast-path
            self._tail_dirty.discard(slot)
            if not preserve:
                # preserve-mode keeps the QUEUES too: those ops are
                # already in the serve/unit logs and the lowerer's known
                # clocks, so dropping them here would leave the arena
                # permanently behind the host mirrors — the compaction
                # path drains them into the (inert, uncleared) rows
                # before rebuilding instead
                self.queues[slot].clear()
                self._busy_slots.discard(slot)
                self.unit_logs[slot] = []
            self.slot_live[slot] = False
            self.slot_gen[slot] += 1
        if doc.lane_slot is not None:
            # lane slots may predate root discovery (not yet in seqs)
            slot = doc.lane_slot
            self._lane_codec.lane_clear_queue(self._lane, slot)
            self.slot_live[slot] = False
            self.slot_gen[slot] += 1
            self._tail_known[slot] = False
            self._tail_dirty.discard(slot)

    def _clear_slot(self, slot: int) -> None:
        self._clear_slots([slot])

    def _clear_slots(self, slots: "list[int]") -> None:
        """Reset a batch of arena rows to empty in ONE state rebuild
        (and one flush-epoch bump): `.at[slots].set` over every field
        instead of a full pytree rebuild per slot."""
        if not slots:
            return
        # type(self.state): DocState or RleState, same field-wise rebuild
        if len(slots) == 1:
            # static-index fast path (dynamic_update_slice, the shape
            # every flush cycle already compiled) — the gather/scatter
            # below would pay a fresh first-call compile for a hot,
            # common case
            empty = self._make_empty(1, self.capacity)
            idx = slots[0]
            self.state = type(self.state)(
                *(
                    field.at[idx].set(empty_field[0])
                    for field, empty_field in zip(self.state, empty)
                )
            )
        else:
            import jax.numpy as jnp

            # power-of-two routing width with the num_docs drop
            # sentinel (the sparse/compact steps' contract): release()
            # runs on the event loop, where an unpadded width would
            # pay a first-call scatter compile for every distinct
            # released-slot count
            width = 1
            while width < len(slots):
                width *= 2
            empty = self._make_empty(width, self.capacity)
            idx = jnp.asarray(
                list(slots) + [self.num_docs] * (width - len(slots)),
                jnp.int32,
            )
            self.state = type(self.state)(
                *(
                    field.at[idx].set(empty_field, mode="drop")
                    for field, empty_field in zip(self.state, empty)
                )
            )
        for slot in slots:
            self._set_tail_empty(slot)
        if self._rle_row_entries is not None:
            self._rle_row_entries[list(slots)] = 0
        self.flush_epoch += 1

    def _set_tail_empty(self, slot: int) -> None:
        """Mark a slot's rank tail KNOWN-EMPTY (fresh/cleared row)."""
        self._tail_client[slot] = NONE_CLIENT
        self._tail_clock[slot] = 0
        self._tail_known[slot] = True
        self._tail_dirty.discard(slot)

    def invalidate_tails(self, slots) -> None:
        """Forget the tracked rank tails for `slots` (and queue them for
        the probe re-arm at the next flush readback). Called by the
        residency manager after a compaction — tombstone GC remaps
        ranks, so the host-tracked tail id may no longer be the rank
        tail."""
        for slot in slots:
            slot = int(slot)
            self._tail_known[slot] = False
            if self.slot_live[slot]:
                self._tail_dirty.add(slot)

    def drop_doc_logs(self, name: str) -> None:
        """Finish a log-preserving retire (see retire_doc): the
        compaction attempt declined, so release the host memory (and
        the retained queues) the ordinary retire would have freed."""
        doc = self.docs.get(name)
        if doc is None:
            return
        doc.serve_log = []
        doc.map_tombstones = []
        for slot in doc.seqs.values():
            self.unit_logs[slot] = []
            queue = self.queues.get(slot)
            if queue:
                queue.clear()
            self._busy_slots.discard(slot)

    def is_supported(self, name: str) -> bool:
        doc = self.docs.get(name)
        if doc is None:
            return False
        return not doc.lowerer.unsupported

    # -- queueing ----------------------------------------------------------

    def enqueue_update(
        self, name: str, update: bytes, presync: bool = False, remote: bool = False
    ) -> int:
        """Lower + queue one update; returns the number of ops accepted."""
        lane_doc = self.docs.get(name)
        if lane_doc is not None and lane_doc.lane_slot is not None:
            if lane_doc.lowerer.unsupported:
                return 0
            return self._enqueue_lane(lane_doc, update, presync, remote)
        doc = self.register(name)
        if doc.lowerer.unsupported:
            return 0
        seq_ops, map_ops, map_tombs = doc.lowerer.lower_update(update)
        if doc.lowerer.unsupported:
            self.retire_doc(name, "unsupported")
            return 0
        count = 0
        for seq_key, ops in seq_ops.items():
            if doc.origin_remap:
                self._remap_origins(doc, seq_key, ops)
            slot = self._alloc_seq(doc, seq_key)
            if slot is None:
                self.retire_doc(name, "plane_full")
                return 0
            # host-side mirror of the device capacity check: the lowerer
            # guarantees causal readiness, so inserts succeed until the
            # arena overflows — at which point the doc is CPU-only
            # forever; stop queueing (and logging payloads) instead of
            # leaking. Unit arena: exact (capacity = units, cost =
            # run_len per insert). RLE arena: neutral 1/op estimate —
            # run-aligned churn deletes cost 0 device entries and
            # mid-run splits cost up to 2 (measured: 0.78 an op where
            # one author types and deletes inside a long text, the
            # counter `rle_entries_appended`), so the host bound only
            # stops unbounded queueing on a doomed doc; the DEVICE
            # overflow flag is the real authority (caught one flush
            # later, and routed through the same recycle seam as
            # capacity).
            if self.arena == "rle":
                projected = self.projected_len[slot] + len(ops)
            else:
                projected = self.projected_len[slot] + sum(
                    op.run_len for op in ops if op.kind == KIND_INSERT
                )
            if projected > self.capacity:
                self.retire_doc(name, "capacity")
                return 0
            self.projected_len[slot] = projected
            if presync:
                for op in ops:
                    op.presync = True
            self.queues[slot].extend(ops)
            # AFTER the extend, unconditionally: this ordering is what
            # makes the busy set lock-free against the drain side (see
            # _busy_slots in __init__)
            self._busy_slots.add(slot)
            # log at ENQUEUE time: broadcast frames build from the host
            # log without waiting for the device flush (the device round
            # trip must never sit on the edit->broadcast critical path —
            # ~an RTT per transfer on remote-attached TPUs). Arena slot
            # assignment is deterministic (arrival order), so unit
            # offsets are final here; health checks compare device state
            # against dispatched tallies, not these logs.
            log = self.unit_logs[slot]
            for op in ops:
                doc.serve_log.append(
                    LogRec(op=op, slot=slot, unit_off=len(log), remote=remote)
                )
                if op.kind == KIND_INSERT:
                    log.extend(op.chars)
            count += len(ops)
        for op in map_ops:
            op.presync = presync
            doc.serve_log.append(LogRec(op=op, slot=None, remote=remote))
            count += 1
        for client, clock, length in map_tombs:
            doc.map_tombstones.append((client, clock, length))
            doc.serve_log.append(
                LogRec(
                    op=DenseOp(
                        kind=KIND_DELETE, client=client, clock=clock, run_len=length,
                        presync=presync,
                    ),
                    slot=None,
                    remote=remote,
                )
            )
            # a map-tombstone-only update still produces a serve-log
            # record that must broadcast: count it like every other op
            count += 1
        if count:
            self.dirty.add(name)
        return count

    def _remap_origins(self, doc: PlaneDoc, seq_key: tuple, ops: list) -> None:
        """Re-anchor op origins that reference ids the tombstone-GC
        compaction removed from the device (tpu/residency.py): the left
        origin falls back to the nearest live unit that preceded the
        removed range at compaction time, the right origin to the
        nearest that followed — the same positional approximation yjs
        accepts once tombstones are garbage-collected. An op whose both
        origins dissolve into doc boundaries gets the sequence as its
        explicit wire parent (serve-time Item.write needs one)."""
        from bisect import bisect_right

        remap = doc.origin_remap

        def removed(client: int, clock: int):
            entry = remap.get(client)
            if entry is None:
                return None
            starts, rows = entry
            i = bisect_right(starts, clock) - 1
            if i >= 0 and rows[i][0] <= clock < rows[i][1]:
                return rows[i]
            return None

        def resolve(client: int, clock: int, side: int):
            """Chase the remap transitively: a recorded neighbor may
            itself sit in a range a LATER compaction removed, so a
            single lookup could hand back a dead id. Each hop follows
            the same side (a left origin wants its replacement's own
            left neighbor) and lands in a strictly newer removed range
            — replacements were live when their row was written — so
            the walk terminates."""
            moved = False
            while client != NONE_CLIENT:
                row = removed(client, clock)
                if row is None:
                    break
                moved = True
                repl = row[side]
                if repl is None:
                    client, clock = NONE_CLIENT, 0
                    break
                client, clock = repl
            return moved, client, clock

        for op in ops:
            if op.kind != KIND_INSERT:
                continue
            if op.left_client != NONE_CLIENT:
                moved, client, clock = resolve(op.left_client, op.left_clock, 2)
                if moved:
                    op.left_client, op.left_clock = client, clock
            if op.right_client != NONE_CLIENT:
                moved, client, clock = resolve(op.right_client, op.right_clock, 3)
                if moved:
                    op.right_client, op.right_clock = client, clock
            if (
                op.left_client == NONE_CLIENT
                and op.right_client == NONE_CLIENT
                and op.parent is None
            ):
                op.parent = seq_key

    def pending_ops(self) -> int:
        # O(busy), not O(D): walk the nonempty-slot set, not the full
        # queue registry. list() snapshot: the event-loop thread can
        # add busy slots while an executor-side flush calls this.
        total = 0
        for slot in list(self._busy_slots):
            queue = self.queues.get(slot)
            if queue:
                total += len(queue)
        if self._lane is not None:
            total += self._lane_codec.lane_queue_total(self._lane)
        return total

    # -- device step -------------------------------------------------------

    def flush(self, max_batches: Optional[int] = None) -> int:
        """Integrate queued ops in (K, D) batches. Returns ops integrated.

        max_batches bounds the kernel calls in this cycle (one batch
        already covers up to max_slots_per_flush ops for EVERY queue):
        the serving flush loop uses 1 so broadcasts interleave with
        integration instead of waiting for a full drain; sync serves
        drain fully (covers() needs everything integrated)."""
        with self._step_lock, get_tracer().span("merge_plane.flush"):
            return self._flush_locked(max_batches)

    def warmup_compiles(self, shape=None, shared: bool = False) -> bool:
        """Pre-compile the integrate step over the (K, B) flush grid.

        The first flush at each batch shape otherwise pays the
        XLA/Mosaic compile (seconds on CPU, tens of seconds cold on
        TPU) in the serving path — with the flush off the event loop
        that surfaced as broadcasts delayed until the compile finished.
        A no-op batch exercises the identical jitted program without
        touching document state. Pass a (k, b) tuple from
        warmup_shapes() to compile one shape (callers can interleave
        lock acquisition per shape), a bare int k for the dense
        (k, num_docs) shape, or nothing for the whole grid.

        shared=True consults the process-wide warm registry
        (tpu/scheduler.py): the jitted steps are module-level, so a
        shape another identically-shaped plane already warmed is a
        guaranteed jit-cache hit — skip the redundant no-op dispatch
        and seed this plane's CompileTracker instead (shards 2..N of a
        sharded deployment boot without N identical warm sweeps).
        Mesh-backed planes build per-plane jitted closures and never
        share. Returns True when any program was actually dispatched.
        """
        full_grid = shape is None
        shapes = (
            [shape]
            if shape is not None
            else self.warmup_shapes() + self.warmup_aux_shapes()
        )
        shapes = [
            entry if isinstance(entry, tuple) else (entry, self.num_docs)
            for entry in shapes
        ]
        share = shared and self.mesh is None
        if share:
            from .scheduler import note_warmed, shared_warm_filter

            shapes, covered = shared_warm_filter(
                self.arena,
                self.num_docs,
                self.capacity,
                shapes,
                device=self._warm_device_key(),
            )
            for entry in covered:
                site, shape_key = self._warm_site(entry)
                self.compile_watch.mark_covered(site, shape_key)
        dispatched = False
        with self._step_lock:
            for entry in shapes:
                site, shape_key = self._warm_site(entry)
                step, args = self._warm_program(entry)
                with self.compile_watch.track(site, shape_key, warmup=True):
                    if site == "health_probe":
                        np.asarray(step(self.state, *args))
                    else:
                        self.state, count = step(self.state, *args)
                        int(count)  # completion barrier (data-dependent)
                self._note_dispatch("warmup")
                dispatched = True
                if share:
                    note_warmed(
                        self.arena,
                        self.num_docs,
                        self.capacity,
                        entry,
                        device=self._warm_device_key(),
                    )
        if full_grid:
            # the whole grid is compiled: any later fresh compile means
            # the flush shapes drifted off the warmed buckets
            self.compile_watch.mark_warmed()
        return dispatched

    def _warm_device_key(self) -> str:
        """The shared-warm-registry discriminator for a pinned plane:
        XLA caches executables per device placement, so identically-
        shaped planes on DIFFERENT chips never share a warm pass."""
        if self.device is None:
            return ""
        return str(getattr(self.device, "id", self.device))

    def canary_probe(self) -> float:
        """One tiny no-op integrate + data-dependent readback: the plane
        supervisor's liveness probe (tpu/supervisor.py). Returns the
        elapsed seconds. Deliberately takes the step lock — a wedged
        flush holding it blocks the probe, which is exactly the
        condition the watchdog's deadline detects. Uses the smallest
        sparse shape (K=1, B=1) so the probe's device work is O(1)
        rows, not a full-population sweep."""
        started = time.perf_counter()
        with self._step_lock:
            if self.num_docs > 1:
                # (K_max, 1): the first entry of the warmup grid — a
                # warmed plane's probes never pay a compile
                k_max = self._k_buckets()[-1]
                ops, slots = self._empty_sparse_batch(k_max, 1)
                with self.compile_watch.track("integrate_sparse", (k_max, 1)):
                    self.state, count = self._sparse_step_fn()(self.state, ops, slots)
                    int(count)  # completion barrier (data-dependent readback)
            else:
                ops = self._empty_batch(1)
                with self.compile_watch.track("integrate_dense", (1, self.num_docs)):
                    self.state, count = self._step_fn()(self.state, ops)
                    int(count)  # completion barrier (data-dependent readback)
            self._note_dispatch("canary")
        return time.perf_counter() - started

    def _k_buckets(self) -> list[int]:
        buckets = []
        k = 1
        while True:
            buckets.append(k)
            if k >= self.max_slots_per_flush:
                return buckets
            k *= 2

    def _b_buckets(self) -> list[int]:
        """SPARSE busy-width buckets: powers of four (a subset of the
        powers of two, so two octaves of headroom per bucket) strictly
        below the population. A busy width above the top bucket takes
        the dense (K, D) layout instead — so the full set of reachable
        batch shapes is this ladder plus the dense K ladder."""
        buckets = []
        b = 1
        while b < self.num_docs:
            buckets.append(b)
            b *= 4
        return buckets

    def warmup_shapes(self) -> "list[tuple[int, int]]":
        """Every (K, B) batch shape a flush can dispatch.

        Sparse batches PIN K to the top bucket (the op axis is cheap at
        sparse widths, and pinning turns the compile grid from
        |K| x |B| — measured ~1s of XLA compile per shape — into
        |K| + |B|): one shape per sparse B bucket, plus the dense
        (k, num_docs) ladder where the op axis does matter. The first
        entry, (K_max, 1), is also the canary probe's shape, so a
        supervisor warm pass covers the watchdog's program before the
        first probe fires."""
        k_max = self._k_buckets()[-1]
        return [(k_max, b) for b in self._b_buckets()] + [
            (k, self.num_docs) for k in self._k_buckets()
        ]

    def warmup_aux_shapes(self) -> "list[tuple]":
        """Tagged warm-grid entries beyond the integrate (k, b) pairs:
        the run-append fast path's ("append", K_max, B) ladder (same
        pinned-K discipline as the sparse integrate, plus the
        num_docs-wide routing the dense regime takes) and the
        ("health", W) readbacks _sync_health can dispatch. Kept out
        of warmup_shapes() so its (k, b)-pair contract — relied on by
        the supervisor grid checks — survives."""
        k_max = self._k_buckets()[-1]
        shapes: "list[tuple]" = [
            ("append", k_max, b) for b in self._b_buckets() + [self.num_docs]
        ]
        shapes += [("health", w) for w in self._probe_widths()]
        return shapes

    def _warm_site(self, entry: tuple) -> "tuple[str, tuple]":
        """(compile-watch site, shape key) for one warm-grid entry —
        plain (k, b) integrate pairs or tagged aux entries."""
        if entry[0] == "append":
            return "append_sparse", (entry[1], entry[2])
        if entry[0] == "health":
            return "health_probe", (entry[1],)
        k, b = entry
        if b >= self.num_docs:
            return "integrate_dense", (k, self.num_docs)
        return "integrate_sparse", (k, b)

    def _warm_program(self, entry: tuple) -> tuple:
        """(step, args) for one warm-grid entry: the callable a live
        flush dispatches at that shape — Pallas or XLA as the plane's
        own seams decide — and all-noop arguments to follow the state.
        warmup_compiles runs it; the AOT pre-flight lowers it."""
        site, _ = self._warm_site(entry)
        if site == "append_sparse":
            return self._append_step_fn(), self._empty_append_batch(*entry[1:])
        if site == "health_probe":  # re-reads row 0
            slots = self._upload_slots(np.zeros((entry[1],), np.int32))
            return self._health_probe_fn(), (slots,)
        if site == "integrate_dense":
            return self._step_fn(), (self._empty_batch(entry[0]),)
        return self._sparse_step_fn(), self._empty_sparse_batch(*entry)

    def _empty_append_batch(self, k: int, b: int) -> tuple:
        """All-noop append fast-path args (run_len == 0 everywhere,
        every routing entry the drop sentinel): applies nothing,
        compiles the exact program of a real (k, b) fast batch."""
        client = np.zeros((k, b), np.uint32)
        clock = np.zeros((k, b), np.int32)
        run_len = np.zeros((k, b), np.int32)
        slots = np.full((b,), self.num_docs, np.int32)
        return self._upload_append_batch((client, clock, run_len), slots)

    def _bucket_b(self, busy: int) -> int:
        """Round a busy width up to its sparse bucket; num_docs (the
        dense layout) when it exceeds the top sparse bucket."""
        b = 1
        while b < busy:
            b *= 4
        return b if b < self.num_docs else self.num_docs

    def _plan_batch(self, busy: int) -> "tuple[bool, int]":
        """The flush layout decision, in ONE place: (dense, b). Sparse
        — a compact (K, B) batch plus slot routing — while the busy
        width buckets below the population; the dense (K, D) sweep once
        it doesn't, where routing would only add gather/scatter
        overhead. _flush_locked derives K from `dense` (depth ladder vs
        pinned k_max) and _assemble_batch lays the batch out from the
        same verdict — never recomputed separately."""
        b = self._bucket_b(busy)
        return b >= self.num_docs, b

    def _empty_batch(self, k: int) -> OpBatch:
        d = self.num_docs
        fields = (
            np.zeros((k, d), np.int32),
            np.zeros((k, d), np.uint32),
            np.zeros((k, d), np.int32),
            np.zeros((k, d), np.int32),
            np.full((k, d), NONE_CLIENT, np.uint32),
            np.zeros((k, d), np.int32),
            np.full((k, d), NONE_CLIENT, np.uint32),
            np.zeros((k, d), np.int32),
        )
        return self._upload_batch(fields)

    def _empty_sparse_batch(self, k: int, b: int) -> tuple:
        """All-noop (K, B) batch with every routing entry the padding
        sentinel (num_docs): integrates nothing, compiles/exercises the
        exact sparse program of a real (k, b) flush batch."""
        fields = tuple(
            np.full((k, b), default, dtype)
            for default, dtype in zip(
                _FlushStaging._DEFAULTS, _FlushStaging._DTYPES
            )
        )
        slots = np.full((b,), self.num_docs, np.int32)
        return self._upload_sparse_batch(fields, slots)

    def _flush_locked(self, max_batches: Optional[int] = None) -> int:
        tracer = get_tracer()
        book = self.update_traces
        trace_batches: list = []
        k_max = self._k_buckets()[-1]
        total = 0
        batches = 0
        device_batches = 0
        fast_total = slow_total = 0
        build_ms = upload_ms = dispatch_ms = 0.0
        upload_bytes = 0
        k_last = b_last = busy_last = 0
        while max_batches is None or batches < max_batches:
            t0 = time.perf_counter()
            # minimal-work run merge: split the drained columns into
            # all-sequential (fast) and genuinely-concurrent (slow)
            # sets. A column is entirely one or the other per batch —
            # the two dispatches below touch disjoint rows, so their
            # relative order is immaterial.
            fast = None
            with tracer.span("merge_plane.drain"):
                slow = drained = self._drain_ops(k_max)
                if drained is not None and self.run_merge_enabled:
                    fast, slow = self._classify_fast(drained)
            if drained is None:
                break
            cycle_traces = None
            if book.active():
                # stamped updates whose slots drained this batch enter
                # the in-flight set; t0 closes their queue-wait stage
                cycle_traces = book.take_drained(
                    (self.slot_owner.get(int(s)) for s in drained[4]), t0
                )
            built = drained[5]
            busy_total = int(drained[4].size)
            if fast is not None:
                (
                    run_row, run_col, f_client, f_clock, f_run,
                    f_slots, f_ops, f_tail_cl, f_tail_ck,
                ) = fast
                nf = int(f_slots.size)
                bf = self._bucket_b(nf)
                staging_f = self._append_staging_for(self._append_batches, k_max)
                cl_v, ck_v, rn_v = staging_f.views(k_max, bf)
                cl_v[run_row, run_col] = f_client
                ck_v[run_row, run_col] = f_clock
                rn_v[run_row, run_col] = f_run
                slot_view_f = staging_f.slot_view(bf)
                slot_view_f[:nf] = f_slots
                slot_view_f[nf:] = self.num_docs
                t1 = time.perf_counter()
                with tracer.span("merge_plane.upload"):
                    args_f = self._upload_append_batch(
                        (cl_v, ck_v, rn_v), slot_view_f
                    )
                self._append_inflight[self._append_batches % 2] = args_f
                self._append_batches += 1
                t2 = time.perf_counter()
                step_f = self._append_step_fn()
                with tracer.span(
                    "merge_plane.append", slots=k_max, busy=bf, integrated=f_ops
                ):
                    self.state, _count = step_f(self.state, *args_f)
                t_dispatch = time.perf_counter()
                self.compile_watch.observe(
                    "append_sparse", (k_max, bf), t_dispatch - t2
                )
                # the dispatched runs land at the rank tail, so the new
                # tail is each column's last coalesced run — tracked
                # here with no device read; the slot stays fast-eligible
                self._tail_client[f_slots] = f_tail_cl
                self._tail_clock[f_slots] = f_tail_ck
                self.counters["flush_batches_fast"] += 1
                self.counters["flush_fast_ops"] += f_ops
                if self.cell_ops_key is not None:
                    self.counters[self.cell_ops_key] += f_ops
                self.counters["flush_busy_rows"] += nf
                self.counters["flush_bucket_rows"] += bf
                fast_total += f_ops
                device_batches += 1
                build_ms += (t1 - t0) * 1000.0
                upload_ms += (t2 - t1) * 1000.0
                dispatch_ms += (t_dispatch - t2) * 1000.0
                upload_bytes += staging_f.nbytes(k_max, bf)
                k_last, b_last = k_max, bf
                if cycle_traces and slow is None:
                    trace_batches.append((cycle_traces, t1, t2, t_dispatch))
                t0 = t_dispatch  # the slow build, if any, starts here
            if slow is not None:
                depth = slow[6]
                # sparse batches pin K to the top bucket (one compiled
                # program per B bucket — see warmup_shapes); dense
                # batches keep the power-of-two K ladder, where the op
                # axis multiplies a full-population sweep
                dense, b_bucket = self._plan_batch(int(slow[4].size))
                if dense:
                    k = 1
                    while k < depth:
                        k *= 2
                else:
                    k = k_max
                staging = self._staging_for(batches, k)
                fields, slot_view, b, b_actual = self._assemble_batch(
                    k, slow, staging, dense, b_bucket
                )
                t1 = time.perf_counter()
                if slot_view is None:
                    with tracer.span("merge_plane.upload"):
                        step_args = (self._upload_batch(fields),)
                    step = self._step_fn()
                    self.counters["flush_batches_dense"] += 1
                else:
                    with tracer.span("merge_plane.upload"):
                        step_args = self._upload_sparse_batch(fields, slot_view)
                    step = self._sparse_step_fn()
                    self.counters["flush_batches_sparse"] += 1
                # remember what this staging buffer fed the device:
                # _staging_for blocks on it before the buffer's next
                # reuse (two batches from now), so an async transfer can
                # never still be reading views a later batch resets
                self._staging_inflight[batches % 2] = step_args
                t2 = time.perf_counter()
                # `built` is the host-side op count — identical to the
                # device's kind!=NOOP sum by construction, so the flush
                # needs no per-batch count readback (a full RTT each on
                # remote-attached TPUs); _sync_health below is the
                # cycle's single completion barrier (content readback —
                # buffer *readiness* of aliased Pallas outputs is not
                # trustworthy). The dispatch itself
                # is ASYNC: while the device integrates batch i, the
                # next loop iteration builds and uploads batch i+1 from
                # the OTHER staging buffer — that alternation is the
                # double-buffered pipeline.
                with tracer.span(
                    "merge_plane.integrate",
                    slots=k,
                    busy=b,
                    integrated=slow[5],
                    arena=self.arena,
                    **self._span_row,
                ):
                    self.state, _count = step(self.state, *step_args)
                t_dispatch = time.perf_counter()
                # compile-event classification from the timestamps
                # already taken: a first dispatch at this (site, shape)
                # paid its XLA/Mosaic compile inline in t_dispatch - t2
                if slot_view is None:
                    self.compile_watch.observe(
                        "integrate_dense", (k, self.num_docs), t_dispatch - t2
                    )
                else:
                    self.compile_watch.observe(
                        "integrate_sparse", (k, b), t_dispatch - t2
                    )
                # full-integrate columns invalidate their tracked rank
                # tails (a concurrent insert/delete may have moved the
                # tail); _sync_health re-arms the live ones below
                slow_cols = slow[4].astype(np.intp)
                self._tail_known[slow_cols] = False
                for col in slow_cols:
                    col = int(col)
                    if self.slot_live[col]:
                        self._tail_dirty.add(col)
                self.counters["flush_slow_ops"] += slow[5]
                if self.cell_ops_key is not None:
                    self.counters[self.cell_ops_key] += slow[5]
                self.counters["flush_busy_rows"] += b_actual
                self.counters["flush_bucket_rows"] += b
                if self.arena == "rle":
                    self.counters["integrate_row_entries"] += b * self.capacity
                else:
                    self.counters["integrate_row_units"] += b * self.capacity
                slow_total += slow[5]
                device_batches += 1
                if cycle_traces:
                    trace_batches.append((cycle_traces, t1, t2, t_dispatch))
                build_ms += (t1 - t0) * 1000.0
                upload_ms += (t2 - t1) * 1000.0
                # ~0 where dispatch is truly asynchronous; on
                # synchronous backends this is the device compute the
                # cycle pays inline
                dispatch_ms += (t_dispatch - t2) * 1000.0
                upload_bytes += staging.nbytes(k, b, slot_view is not None)
                k_last, b_last = k, b
            total += built
            busy_last = busy_total
            batches += 1
        if batches:
            self._note_dispatch("flush", device_batches)
            t3 = time.perf_counter()
            with tracer.span("merge_plane.readback"):
                self._sync_health()
            t_sync = time.perf_counter()
            # readback-barrier stall: the host time this cycle spent
            # blocked on the device before results were visible
            self.device_stats["readback_stall_ms_total"] += (t_sync - t3) * 1000.0
            self.device_stats["readback_stalls"] += 1
            if upload_bytes > self.device_stats["upload_bytes_peak"]:
                self.device_stats["upload_bytes_peak"] = upload_bytes
            self._memory_stats_cache = (0.0, None)  # staging/stalls moved
            if trace_batches:
                # the cycle's single readback barrier closes every
                # in-flight trace's device/readback stages
                book.complete_cycle(trace_batches, t_sync)
            self.flush_stats.update(
                build_ms=round(build_ms, 3),
                upload_ms=round(upload_ms, 3),
                dispatch_ms=round(dispatch_ms, 3),
                device_sync_ms=round((t_sync - t3) * 1000.0, 3),
                busy_slots=busy_last,
                busy_fraction=round(busy_last / max(self.num_docs, 1), 6),
                batch_k=k_last,
                batch_b=b_last,
                batches=batches,
                upload_bytes=upload_bytes,
                fast_path_ops=fast_total,
                slow_path_ops=slow_total,
                fast_path_fraction=round(fast_total / max(total, 1), 6),
            )
        self.total_integrated += total
        return total

    def memory_stats(self) -> dict:
        """Device/host memory footprint (HBM watch): arena state bytes
        (constant after construction), allocated staging bytes, the
        biggest single-cycle upload and the cumulative readback-stall
        time. Array `.nbytes` reads only metadata — no transfer. The
        pytree walks are cached briefly: one /metrics scrape reads five
        gauges off this dict and must pay one walk, not five (x shards
        on the summed variant)."""
        now = time.monotonic()
        cached_at, cached = self._memory_stats_cache
        if cached is not None and now - cached_at < 0.5:
            return cached
        staging_bytes = 0
        for staging in self._staging or ():
            staging_bytes += pytree_nbytes(staging.fields) + staging.slots.nbytes
        for staging in self._append_staging or ():
            staging_bytes += (
                staging.client.nbytes
                + staging.clock.nbytes
                + staging.run_len.nbytes
                + staging.slots.nbytes
            )
        stats = {
            "arena_bytes": pytree_nbytes(self.state),
            "staging_bytes": staging_bytes,
            "upload_bytes_peak": self.device_stats["upload_bytes_peak"],
            "readback_stall_ms_total": round(
                self.device_stats["readback_stall_ms_total"], 3
            ),
            "readback_stalls": self.device_stats["readback_stalls"],
        }
        self._memory_stats_cache = (now, stats)
        return stats

    def _sync_health(self) -> None:
        """ONE combined device->host readback per flush cycle.

        Fetches lengths + overflow as a single array from a single
        program (health_probe) — this read is also the completion
        barrier for every batch dispatched above, by data dependence. The dispatched->validated snapshot is taken
        at the same point (under the step lock), so health checks
        compare device rows against exactly the ops the device has
        integrated, never against optimistically-ahead host logs. A
        launch failure surfaces here and propagates to the caller
        (flush -> extension degrade path).

        When full-integrate columns (or a compaction) invalidated
        tracked rank tails, the dirty LIVE slots' tail ids ride the
        same program's readback — one transfer, never a second RTT —
        and re-arm the run-merge classifier for the next cycle. At
        most _TAIL_PROBE_MAX slots re-arm per cycle (the compiled
        widths are _probe_widths(), never an unbounded shape ladder);
        the remainder stay dirty for the next readback."""
        probe_slots = np.zeros(0, np.intp)
        if self._tail_dirty and self.run_merge_enabled:
            live = sorted(
                slot for slot in self._tail_dirty if self.slot_live[slot]
            )
            self._tail_dirty.clear()
            if len(live) > self._TAIL_PROBE_MAX:
                self._tail_dirty.update(live[self._TAIL_PROBE_MAX :])
                live = live[: self._TAIL_PROBE_MAX]
            probe_slots = np.asarray(live, np.intp)
        probe_width = next(
            w for w in self._probe_widths() if w >= probe_slots.size
        )
        padded = np.zeros(probe_width, np.int32)
        padded[: probe_slots.size] = probe_slots  # pad: re-read slot 0
        with self.compile_watch.track("health_probe", (probe_width,)):
            probed = self._health_probe_fn()(self.state, self._upload_slots(padded))
            # everything above holds the interpreter lock (staging, the
            # upload, an asynchronous dispatch); this is the cycle's one
            # wait for the device, made with the lock released
            with get_tracer().span("merge_plane.device_wait"):
                combined = np.asarray(probed)
        if probe_slots.size:
            self._note_dispatch("tail_probe")
        if self.arena == "rle":
            entries = combined[-self.num_docs :].astype(np.int64)
            self.counters["rle_entries_appended"] += int((entries - self._rle_row_entries).sum())
            self._rle_row_entries = entries
        lengths = combined[: self.num_docs].astype(np.int64)
        self.last_lengths = lengths
        self.last_overflows = combined[self.num_docs : 2 * self.num_docs].astype(
            bool
        )
        if probe_slots.size:
            probe = combined[2 * self.num_docs :]
            n = probe_slots.size
            clients = probe[:n].astype(np.uint32)
            clocks = probe[probe_width : probe_width + n].astype(np.int64)
            empty = lengths[probe_slots] == 0
            self._tail_client[probe_slots] = np.where(
                empty, np.uint32(NONE_CLIENT), clients
            )
            self._tail_clock[probe_slots] = np.where(empty, 0, clocks)
            self._tail_known[probe_slots] = True
        self.validated_units = self.dispatched_units.copy()
        self.last_gen = self.slot_gen.copy()
        self.flush_epoch += 1

    # per-cycle cap on tail re-arms: bounds both the probe's device
    # work and the compiled width ladder (_probe_widths)
    _TAIL_PROBE_MAX = 256

    def _probe_widths(self) -> "list[int]":
        """Every tail-slot width _sync_health dispatches health_probe
        at: 0 (nothing to re-arm: lengths + overflow only), 16, and the
        per-cycle cap on planes that can hold more than 16 rows."""
        if self.num_docs <= 16:
            return [0, 16]
        return [0, 16, self._TAIL_PROBE_MAX]

    def _drain_ops(self, k: int):
        """Pop up to k ops from every BUSY queue (Python + native lane)
        into flat coordinate/value lists — O(busy), never a walk of the
        full queue registry. Returns None when nothing was drained,
        else (rows, slots, vals, lane, cols, built, depth): python op
        coordinates (row-in-batch, arena slot) + 8 per-field value
        columns, the lane's columnar drain tuple (or None), the sorted
        unique busy slot ids, the total op count, and the deepest
        per-queue take (the dense layout's K requirement).

        The busy snapshot is taken via sorted(set) (atomic under the
        GIL); enqueues landing after the snapshot wait for the next
        batch, exactly like the old full-registry snapshot."""
        rows: list[int] = []
        slots: list[int] = []
        vals: tuple[list[int], ...] = ([], [], [], [], [], [], [], [])
        built = 0
        depth = 0
        for slot in sorted(self._busy_slots):
            queue = self.queues.get(slot)
            if not queue:
                self._busy_slots.discard(slot)
                if queue:  # an enqueue raced the discard: repair
                    self._busy_slots.add(slot)
                continue
            take = queue[:k]
            # del by len(take), not k: the loop thread may EXTEND this
            # queue between the slice and the del (both atomic alone
            # under the GIL, not together). Appends only touch the back,
            # so the front len(take) items are exactly the taken ones —
            # `del queue[:k]` with k > len(take) would silently discard
            # ops appended in that window (logged in serve_log but never
            # dispatched: permanent host/device divergence).
            del queue[: len(take)]
            if not queue:
                self._busy_slots.discard(slot)
                if queue:  # an enqueue raced the discard: repair
                    self._busy_slots.add(slot)
            dispatched = 0
            for i, op in enumerate(take):
                rows.append(i)
                slots.append(slot)
                vals[0].append(op.kind)
                vals[1].append(op.client)
                vals[2].append(op.clock)
                vals[3].append(op.run_len)
                vals[4].append(op.left_client)
                vals[5].append(op.left_clock)
                vals[6].append(op.right_client)
                vals[7].append(op.right_clock)
                if op.kind == KIND_INSERT:
                    dispatched += op.run_len
            built += len(take)
            if len(take) > depth:
                depth = len(take)
            self.dispatched_units[slot] += dispatched
            self.dispatched_total += dispatched
        lane = None
        if self._lane is not None:
            # native lane drain: one C call pops up to k ops per lane
            # slot into columnar buffers scattered by _assemble_batch —
            # no per-op Python at all on the hot-doc flush path
            drained = self._lane_codec.lane_drain(self._lane, k)
            if drained[0]:
                lane = drained
                ds = np.frombuffer(drained[11], np.int64)
                lane_units = np.frombuffer(drained[12], np.int64)
                self.dispatched_units[ds] += lane_units
                self.dispatched_total += int(lane_units.sum())
                built += drained[0]
                lane_rows = np.frombuffer(drained[1], np.int64)
                depth = max(depth, int(lane_rows.max()) + 1)
        if not built:
            return None
        py_cols = np.unique(np.asarray(slots, np.int64))
        if lane is not None:
            lane_cols = np.unique(np.frombuffer(lane[2], np.int64))
            cols = np.union1d(py_cols, lane_cols)
        else:
            cols = py_cols
        return rows, slots, vals, lane, cols, built, depth

    def _classify_fast(self, drained):
        """The run-merge concurrency classifier: split one drained cycle
        into fast COLUMNS (every op a chained tail append — integrable
        by the near-O(new ops) append program) and slow columns (the
        full-row integrate). Returns (fast_pack | None, slow | None)
        where `slow` has the same shape as a _drain_ops result (lane
        ops already folded into the flat arrays, lane=None).

        An op is a pure tail append iff it is an INSERT with no right
        origin whose left origin is the column's current rank tail —
        the Yjs end-append shape. For such ops the YATA conflict window
        is empty, so the append program is bit-identical to the scan
        integrate (tpu/kernels.py, "minimal-work run merge"). Chains
        verify inductively: op m's left must be op m-1's last unit.
        All checks are vectorized numpy over the drained cycle — the
        classifier costs O(drained ops), no Python per-op loop, no
        device read (tails are host-tracked, see _tail_known)."""
        rows, slots, vals, lane, cols, built, depth = drained
        n_py = len(rows)
        if lane is None and n_py == 0:
            return None, drained
        parts_row: list = []
        parts_slot: list = []
        parts_f: "list[list]" = [[] for _ in range(8)]
        if n_py:
            parts_row.append(np.asarray(rows, np.int64))
            parts_slot.append(np.asarray(slots, np.int64))
            for i in range(8):
                dtype = np.uint32 if i in (1, 4, 6) else np.int64
                parts_f[i].append(np.asarray(vals[i], dtype))
        if lane is not None:
            parts_row.append(np.frombuffer(lane[1], np.int64))
            parts_slot.append(np.frombuffer(lane[2], np.int64))
            for i, buf in enumerate(lane[3:11]):
                if i in (1, 4, 6):
                    parts_f[i].append(np.frombuffer(buf, np.uint32))
                else:
                    parts_f[i].append(
                        np.frombuffer(buf, np.int32).astype(np.int64)
                    )
        if len(parts_row) == 1:
            op_row, op_slot = parts_row[0], parts_slot[0]
            fields = [p[0] for p in parts_f]
        else:
            op_row = np.concatenate(parts_row)
            op_slot = np.concatenate(parts_slot)
            fields = [np.concatenate(p) for p in parts_f]
        n = op_slot.size
        # column-major order: a slot's ops are contiguous, row-ordered
        # (a slot drains from exactly one source — Python queue or lane
        # — so concatenation never interleaves within a column)
        order = np.lexsort((op_row, op_slot))
        s = op_slot[order]
        row_s = op_row[order]
        kind_s = fields[0][order]
        cl_s = fields[1][order]
        ck_s = fields[2][order]
        rn_s = fields[3][order]
        lc_s = fields[4][order]
        lk_s = fields[5][order]
        rc_s = fields[6][order]
        rk_s = fields[7][order]
        first = np.ones(n, bool)
        first[1:] = s[1:] != s[:-1]
        sp = s.astype(np.intp)
        head_ok = np.where(
            lc_s == NONE_CLIENT,
            # an origin-less insert appends only to an EMPTY row
            self._tail_client[sp] == np.uint32(NONE_CLIENT),
            (lc_s == self._tail_client[sp])
            & (lk_s == self._tail_clock[sp]),
        )
        prev_cl = np.empty(n, np.uint32)
        prev_end = np.empty(n, np.int64)
        prev_cl[0] = 0
        prev_end[0] = 0
        prev_cl[1:] = cl_s[:-1]
        prev_end[1:] = ck_s[:-1] + rn_s[:-1] - 1
        ok = (
            (kind_s == KIND_INSERT)
            & (rc_s == NONE_CLIENT)
            & self._tail_known[sp]
            & np.where(first, head_ok, (lc_s == prev_cl) & (lk_s == prev_end))
        )
        col_starts = np.flatnonzero(first)
        col_ok = np.logical_and.reduceat(ok, col_starts)
        if not col_ok.any():
            self._count_slow_reasons(kind_s, rc_s)
            return None, drained
        counts = np.diff(np.append(col_starts, n))
        member = np.repeat(col_ok, counts)
        # coalesce the fast subset: consecutive same-client runs with
        # clock continuity merge into ONE device run (a typing burst of
        # K ops ships as a single (client, clock, len) triple)
        fs = s[member]
        fcl = cl_s[member]
        fck = ck_s[member]
        frn = rn_s[member]
        m = int(fs.size)
        newrun = np.ones(m, bool)
        newrun[1:] = (
            (fs[1:] != fs[:-1])
            | (fcl[1:] != fcl[:-1])
            | (fck[1:] != fck[:-1] + frn[:-1])
        )
        run_starts = np.flatnonzero(newrun)
        run_slot = fs[run_starts]
        run_client = fcl[run_starts]
        run_clock = fck[run_starts]
        run_len = np.add.reduceat(frn, run_starts)
        run_first = np.ones(run_slot.size, bool)
        run_first[1:] = run_slot[1:] != run_slot[:-1]
        col_of_run = np.cumsum(run_first) - 1
        first_run = np.flatnonzero(run_first)
        run_row = np.arange(run_slot.size) - first_run[col_of_run]
        last_run = np.append(first_run[1:] - 1, run_slot.size - 1)
        fast = (
            run_row.astype(np.intp),
            col_of_run.astype(np.intp),
            run_client,
            run_clock.astype(np.int64),
            run_len.astype(np.int64),
            run_slot[run_first].astype(np.int64),
            m,
            run_client[last_run],
            (run_clock[last_run] + run_len[last_run] - 1).astype(np.int64),
        )
        if member.all():
            return fast, None
        keep = ~member
        slow = (
            row_s[keep],
            s[keep],
            (
                kind_s[keep], cl_s[keep], ck_s[keep], rn_s[keep],
                lc_s[keep], lk_s[keep], rc_s[keep], rk_s[keep],
            ),
            None,
            s[col_starts][~col_ok],
            int(n - m),
            int(row_s[keep].max()) + 1,
        )
        self._count_slow_reasons(slow[2][0], slow[2][6])
        return fast, slow

    def _count_slow_reasons(self, kinds: np.ndarray, right_clients: np.ndarray) -> None:
        """Why each op of this cycle's slow columns takes the full-row
        integrate, by the op's own shape: a delete; an insert that names
        a right origin (not at its row's tail); anything else is an
        append the classifier could not chain off the tracked tail, or
        one that rides in a column with a slow op."""
        deletes = int(np.count_nonzero(kinds == KIND_DELETE))
        mid_row = int(
            np.count_nonzero((kinds == KIND_INSERT) & (right_clients != NONE_CLIENT))
        )
        self.counters["slow_ops_delete"] += deletes
        self.counters["slow_ops_mid_row"] += mid_row
        self.counters["slow_ops_concurrent"] += int(kinds.size) - deletes - mid_row

    def _append_staging_for(self, batch_index: int, k: int) -> _AppendStaging:
        """The append fast path's staging buffer for this batch — same
        double-buffer + retire-before-reuse discipline as _staging_for."""
        if (
            self._append_staging is None
            or self._append_staging[0].client.shape[0] < k
        ):
            k_max = max(self._k_buckets()[-1], k)
            self._append_staging = [
                _AppendStaging(k_max, self.num_docs) for _ in range(2)
            ]
            self._append_inflight = [None, None]
            self.counters["flush_staging_allocs"] += 2
        else:
            self.counters["flush_staging_reuses"] += 1
        index = batch_index % 2
        inflight = self._append_inflight[index]
        if inflight is not None:
            import jax

            jax.block_until_ready(inflight)
            self._append_inflight[index] = None
        return self._append_staging[index]

    def _upload_append_batch(self, fields: tuple, slots: np.ndarray) -> tuple:
        """Upload the three (K, B) run fields + (B,) routing — the
        append twin of _upload_sparse_batch (same placement rules)."""
        if self._append_field_sharding is not None:
            import jax

            return tuple(
                jax.device_put(field, self._append_field_sharding)
                for field in fields
            ) + (jax.device_put(slots, self._slots_sharding),)
        if self.device is not None:
            import jax

            return tuple(
                jax.device_put(field, self.device) for field in fields
            ) + (jax.device_put(slots, self.device),)
        import jax.numpy as jnp

        return tuple(jnp.asarray(field) for field in fields) + (
            jnp.asarray(slots),
        )

    def _upload_slots(self, slots: np.ndarray):
        """Upload a bare routing vector (tail probe) with the plane's
        placement rules."""
        import jax

        if self._slots_sharding is not None:
            return jax.device_put(slots, self._slots_sharding)
        if self.device is not None:
            return jax.device_put(slots, self.device)
        import jax.numpy as jnp

        return jnp.asarray(slots)

    def _staging_for(self, batch_index: int, k: int) -> _FlushStaging:
        """The staging buffer for this batch (alternating between the
        two preallocated sets), with its previous upload retired first:
        block_until_ready on the device arrays last fed from this
        buffer, so resetting it can never race an in-flight host->device
        transfer (device_put pins the host views until the transfer
        completes). Reallocation only happens when a caller asks for a
        K beyond the bucketed grid (equivalence tests) — counted, so
        the reuse regression suite can pin allocs flat."""
        if self._staging is None or self._staging[0].fields[0].shape[0] < k:
            k_max = max(self._k_buckets()[-1], k)
            self._staging = [
                _FlushStaging(k_max, self.num_docs) for _ in range(2)
            ]
            # fresh buffers: nothing uploaded from them yet (old
            # buffers' transfers keep their own pins alive)
            self._staging_inflight = [None, None]
            self.counters["flush_staging_allocs"] += 2
        else:
            self.counters["flush_staging_reuses"] += 1
        index = batch_index % 2
        inflight = self._staging_inflight[index]
        if inflight is not None:
            import jax

            jax.block_until_ready(inflight)
            self._staging_inflight[index] = None
        return self._staging[index]

    def _assemble_batch(
        self, k: int, drained, staging: _FlushStaging, dense: bool, b: int
    ):
        """Scatter drained ops into staging views.

        `dense`/`b` come from _plan_batch (the single source of the
        layout decision — this method never recomputes it). Returns
        (fields, slot_view, b, b_actual). Sparse layout — a compact
        (K, B) batch over the busy columns plus the int32 (B,)
        slot-routing view; dense (K, D) layout (column = arena slot,
        slot_view None) when every slot is effectively busy, where
        routing would only add gather/scatter overhead."""
        rows, slots, vals, lane, cols, _built, _depth = drained
        b_actual = int(cols.size)
        if dense:
            b = self.num_docs
            views = staging.views(k, b)
            col_idx = np.asarray(slots, np.intp)
            slot_view = None
        else:
            views = staging.views(k, b)
            col_idx = np.searchsorted(cols, np.asarray(slots, np.int64))
            slot_view = staging.slot_view(b)
            slot_view[:b_actual] = cols
            # padding columns route to the out-of-range sentinel: the
            # device gather clips (reads some real row, applies noops),
            # the scatter drops the write — padding can never alias a
            # busy row (see kernels.integrate_op_slots_sparse)
            slot_view[b_actual:] = self.num_docs
        if len(rows):  # list (live drain) or ndarray (classifier remainder)
            ri = np.asarray(rows, np.intp)
            views[0][ri, col_idx] = vals[0]
            views[1][ri, col_idx] = np.asarray(vals[1], np.uint32)
            views[2][ri, col_idx] = vals[2]
            views[3][ri, col_idx] = vals[3]
            views[4][ri, col_idx] = np.asarray(vals[4], np.uint32)
            views[5][ri, col_idx] = vals[5]
            views[6][ri, col_idx] = np.asarray(vals[6], np.uint32)
            views[7][ri, col_idx] = vals[7]
        if lane is not None:
            (
                _lane_built, l_rows, l_slots, l_kind, l_client, l_clock,
                l_run, l_lc, l_lk, l_rc, l_rk, _d_slots, _d_units,
            ) = lane
            ri = np.frombuffer(l_rows, np.int64)
            lane_slots = np.frombuffer(l_slots, np.int64)
            ci = lane_slots if dense else np.searchsorted(cols, lane_slots)
            views[0][ri, ci] = np.frombuffer(l_kind, np.int32)
            views[1][ri, ci] = np.frombuffer(l_client, np.uint32)
            views[2][ri, ci] = np.frombuffer(l_clock, np.int32)
            views[3][ri, ci] = np.frombuffer(l_run, np.int32)
            views[4][ri, ci] = np.frombuffer(l_lc, np.uint32)
            views[5][ri, ci] = np.frombuffer(l_lk, np.int32)
            views[6][ri, ci] = np.frombuffer(l_rc, np.uint32)
            views[7][ri, ci] = np.frombuffer(l_rk, np.int32)
        return views, slot_view, b, b_actual

    def _build_batch(self, k: int) -> "tuple[OpBatch, int]":
        """Drain + assemble + upload one DENSE (K, D) batch.

        Kept for callers that want the dense layout regardless of busy
        width (lane/Python equivalence tests compare batches column by
        column); the flush loop itself dispatches through the
        sparse/dense pipeline in _flush_locked."""
        drained = self._drain_ops(k)
        if drained is None:
            return self._empty_batch(k), 0
        staging = self._staging_for(0, k)
        fields, _slot_view, _b, _busy = self._assemble_batch(
            k, drained, staging, True, self.num_docs
        )
        ops = self._upload_batch(fields)
        self._staging_inflight[0] = (ops,)
        return ops, drained[5]

    def _upload_batch(self, fields: tuple) -> OpBatch:
        if self._op_shardings is not None:
            # upload straight to the mesh layout — routing through
            # jnp.asarray would commit to the default device first and
            # pay a second device-to-device reshard per field per flush
            import jax

            return OpBatch(
                *(
                    jax.device_put(field, sharding)
                    for field, sharding in zip(fields, self._op_shardings)
                )
            )
        if self.device is not None:
            # straight to the pinned chip: an uncommitted jnp.asarray
            # would land on the default device and pay a device-to-
            # device hop per field per flush
            import jax

            return OpBatch(
                *(jax.device_put(field, self.device) for field in fields)
            )
        import jax.numpy as jnp

        return OpBatch(*(jnp.asarray(field) for field in fields))

    def _upload_sparse_batch(self, fields: tuple, slots: np.ndarray) -> tuple:
        """Upload a compact (K, B) batch + its (B,) routing vector.

        On a mesh the tiny op fields replicate (sparse_ops_sharding);
        XLA routes each busy row's gather/scatter to the shard owning
        it. jnp.asarray/device_put COPY the staging views, so the
        staging buffers are free to be rebuilt two batches later."""
        if self._sparse_op_shardings is not None:
            import jax

            ops = OpBatch(
                *(
                    jax.device_put(field, sharding)
                    for field, sharding in zip(fields, self._sparse_op_shardings)
                )
            )
            return ops, jax.device_put(slots, self._slots_sharding)
        if self.device is not None:
            import jax

            return (
                OpBatch(
                    *(jax.device_put(field, self.device) for field in fields)
                ),
                jax.device_put(slots, self.device),
            )
        import jax.numpy as jnp

        return OpBatch(*(jnp.asarray(field) for field in fields)), jnp.asarray(
            slots
        )

    # -- extraction --------------------------------------------------------

    def check_doc_health(
        self,
        name: str,
        doc: PlaneDoc,
        lengths: np.ndarray,
        overflows: np.ndarray,
        validated: Optional[np.ndarray] = None,
        gens: Optional[np.ndarray] = None,
    ) -> bool:
        """Device/host invariants for every row of a doc; retires on fail.

        The single health definition shared by text() and the serving
        path (PlaneServing.doc_healthy) — callers supply the (D,)
        length/overflow rows AND the validated-unit + generation
        snapshots taken with them, so serving can reuse its refresh()
        caches. Device lengths are compared against VALIDATED dispatch
        tallies (what the device had been given as of that readback),
        never the host unit logs — those run optimistically ahead of
        the device by design. A slot whose binding generation changed
        since the snapshot (released + reallocated) is skipped: the
        cached row describes the previous tenant, and the next
        consistent snapshot will cover the new one.
        """
        if validated is None:
            validated = self.validated_units
        if gens is None:
            gens = self.last_gen
        for slot in doc.seqs.values():
            if gens is None or gens[slot] != self.slot_gen[slot]:
                continue  # snapshot predates this slot's binding
            if bool(overflows[slot]):
                self.retire_doc(name, "overflow")
                return False
            if int(validated[slot]) != int(lengths[slot]):
                # dispatched ops and arena desynced (op rejected on
                # device) — the CPU document stays authoritative; retire
                # the doc so it stops consuming queue/log/kernel
                # resources
                self.retire_doc(name, "desync")
                return False
        return True

    def _read_row(self, slot: int):
        """One arena row as host arrays (the state's namedtuple type),
        sliced on the device (kernels.read_doc_row)."""
        from .kernels import read_doc_row

        return type(self.state)(*map(np.asarray, read_doc_row(self.state, slot)))

    def text(self, name: str) -> Optional[str]:
        """Decode a plain-text document's live text from device state.

        Defined for docs whose content is a single root sequence of
        text units (formats are zero-width, as in Yjs); tree docs and
        value sequences return None — they are served byte-level, not
        materialized. Surrogate-pair handling mirrors Yjs splice
        semantics: a pair decodes as a real character only when its two
        units are id-consecutive from one client AND rank-adjacent —
        every split scenario breaks one of those, yielding the same
        U+FFFD output as the CPU path.
        """
        from ..crdt.content import ContentFormat

        doc = self.docs.get(name)
        if doc is None:
            return None
        if doc.lowerer.unsupported:
            return None  # doc fell back to the CPU path (content/overflow)
        self.materialize_lane(doc)
        roots = [key for key in doc.seqs if key[0] == "root"]
        if len(doc.seqs) != len(roots) or len(roots) > 1:
            return None  # tree-shaped: byte-served, not materialized
        if not roots:
            return ""
        with self._step_lock:  # never read state mid-flush (donation)
            if self.pending_ops() > 0:
                # broadcasts run ahead of the device on purpose; a
                # direct device read must first drain the queues so
                # "live text" means everything enqueued (reentrant lock:
                # _flush_locked re-acquires)
                self._flush_locked(None)
            if not self.check_doc_health(
                name, doc, np.asarray(self.state.length), np.asarray(self.state.overflow)
            ):
                return None
            slot = doc.seqs[roots[0]]
            log = self.unit_logs[slot]
            if self.arena == "rle":
                expanded = self._rle_live_units(doc, slot, log)
                if expanded is None:
                    return None
                clients, clocks, ranks, entries = expanded
            else:
                row = self._read_row(slot)
                live = (np.arange(self.capacity) < row.length) & ~row.deleted
                occupied = np.nonzero(live)[0]
                ranks_all = row.rank[occupied]
                order = np.argsort(ranks_all)
                sel = occupied[order]
                ranks = ranks_all[order]
                clients = row.id_client[sel]
                clocks = row.id_clock[sel]
                entries = [log[i] for i in sel]
        out: list[int] = []
        i = 0
        count = len(entries)
        while i < count:
            entry = entries[i]
            if entry is None:
                return None  # RLE: payload not locatable in the unit log
            if not isinstance(entry, int):
                if isinstance(entry, ContentFormat):
                    i += 1  # zero-width formatting boundary
                    continue
                return None  # embeds/values: not a plain text doc
            c = entry
            if 0xD800 <= c <= 0xDBFF:
                nxt = entries[i + 1] if i + 1 < count else None
                if (
                    isinstance(nxt, int)
                    and 0xDC00 <= nxt <= 0xDFFF
                    and clients[i + 1] == clients[i]
                    and clocks[i + 1] == clocks[i] + 1
                    and ranks[i + 1] == ranks[i] + 1
                ):
                    out.append(c)
                    out.append(nxt)
                    i += 2
                    continue
                out.append(0xFFFD)
            elif 0xDC00 <= c <= 0xDFFF:
                out.append(0xFFFD)
            else:
                out.append(c)
            i += 1
        return units_to_text(out)

    def unit_off_index(self, doc: PlaneDoc, slot: int) -> "dict[int, list]":
        """client -> clock-sorted [(clock, unit_off, run_len)] intervals
        for the slot's insert records: maps an arbitrary (client, clock)
        id to its payload position in the slot's unit log. The RLE
        arena stores runs, not per-unit arrival indices, so payload
        lookup goes through the host serve log (which is written at
        enqueue time in dispatch order)."""
        self.materialize_lane(doc)
        index: dict[int, list] = {}
        for rec in doc.serve_log:
            op = rec.op
            if rec.slot != slot or op.kind != KIND_INSERT:
                continue
            # every sequence insert logs exactly run_len entries (units,
            # zero markers for ContentDeleted, repeated Content objects
            # for rich units — lowering._emit_seq), so intervals tile
            # the log densely; gc records are host-only (slot None)
            index.setdefault(op.client, []).append(
                (op.clock, rec.unit_off, op.run_len)
            )
        for intervals in index.values():
            intervals.sort()
        return index

    def _rle_live_units(self, doc: PlaneDoc, slot: int, log: list):
        """Expand the slot's live RLE entries, rank-ordered, to parallel
        per-unit arrays (clients, clocks, ranks, entries) matching the
        unit-arena extraction — payloads resolved via unit_off_index.
        An entry of None means the unit's payload wasn't found (rich
        content in the log, or a divergence): text() returns None."""
        from bisect import bisect_right

        row = self._read_row(slot)
        num = int(row.num_runs)
        rcl = row.run_client[:num]
        rck = row.run_clock[:num]
        rln = row.run_len[:num]
        rrk = row.run_rank[:num]
        rdl = row.run_deleted[:num]
        keep = (rln > 0) & ~rdl
        order = np.argsort(rrk[keep])
        index = self.unit_off_index(doc, slot)
        clients: list[int] = []
        clocks: list[int] = []
        ranks: list[int] = []
        entries: list = []
        kcl, kck, kln, krk = rcl[keep], rck[keep], rln[keep], rrk[keep]
        for i in order:
            client, clock0, length, rank0 = (
                int(kcl[i]), int(kck[i]), int(kln[i]), int(krk[i]),
            )
            intervals = index.get(client)
            if not intervals:
                return None
            # a run's payload may span SEVERAL insert records: residency
            # compaction merges id-consecutive fragments whose payloads
            # were logged by different ops — walk the clock range across
            # the intervals instead of requiring a single container
            clk = clock0
            rnk = rank0
            remaining = length
            while remaining > 0:
                pos = bisect_right(intervals, (clk, 0x7FFFFFFF, 0)) - 1
                if pos < 0:
                    return None
                iv_clock, iv_off, iv_len = intervals[pos]
                if not (iv_clock <= clk < iv_clock + iv_len):
                    return None
                take = min(remaining, iv_clock + iv_len - clk)
                base = iv_off + (clk - iv_clock)
                for u in range(take):
                    clients.append(client)
                    clocks.append(clk + u)
                    ranks.append(rnk + u)
                    entries.append(log[base + u] if base + u < len(log) else None)
                clk += take
                rnk += take
                remaining -= take
        return clients, clocks, ranks, entries


class TpuMergeExtension(Extension):
    """Puts live documents on the TPU merge plane via onChange.

    Two modes:
    - shadow (serve=False): the plane mirrors every supported document;
      the CPU document serves (round-1 behavior).
    - serve (serve=True): for supported docs the plane IS the serving
      path — SyncStep2 replies come from device state
      (`Document.sync_source`), per-update CPU fan-out is suppressed
      (`Document.broadcast_source`) and replaced by one merged broadcast
      per device flush. Any degradation (unsupported content, overflow,
      desync) falls the doc back to the CPU path, shipping the full CPU
      state once so receivers that only saw plane broadcasts are whole.

    Replaces the reference's per-connection apply+broadcast loop
    (`packages/server/src/MessageReceiver.ts:195-213`,
    `packages/server/src/Document.ts:228-240`).
    """

    priority = 900

    def __init__(
        self,
        num_docs: int = 256,
        capacity: int = 4096,
        flush_interval_ms: float = 5.0,
        plane: Optional[MergePlane] = None,
        serve: bool = False,
        mesh=None,
        device=None,
        broadcast_interval_ms: float = 2.0,
        arena: str = "unit",
        native_lane: bool = True,
        evict_idle_secs: float = 0.0,
        hydrate_batch: int = 64,
        compact_threshold: float = 0.0,
        governor: bool = True,
        lane=None,
        phase_offset_ms: Optional[float] = None,
        drain_watermark: int = 256,
        flush_stretch: float = 4.0,
        lane_promote_ms: float = 250.0,
    ) -> None:
        """Scheduling knobs (docs/guides/tpu-scheduling.md):

        governor — arrival-aware batching: the flush cadence and the
        kernel calls per cycle follow the op-arrival EWMA, queue depth
        and lane congestion instead of the fixed flush_interval_ms
        (which stays the governor's BASE cadence). False restores the
        fixed timer exactly.
        lane — the device-lane arbiter this extension's device work
        admits through: a DeviceLane instance, None for the process-
        global one (all shards of one chip must share an arbiter), or
        False to disable arbitration entirely (a test seam).
        phase_offset_ms — deterministic timer phase (the sharded router
        assigns i/N spreads so N shards stop tick-aligning dispatches).
        drain_watermark — queue depth that collapses the tick to an
        immediate full drain. flush_stretch — max tick stretch under
        sparse arrivals. lane_promote_ms — lane starvation guard: a
        waiter older than this is promoted to the interactive class.
        """
        if plane is not None and (mesh is not None or device is not None):
            raise ValueError(
                "pass mesh=/device= to the MergePlane you construct, not "
                "alongside plane= (an explicit plane keeps its own device "
                "layout)"
            )
        self.plane = plane or MergePlane(
            num_docs=num_docs,
            capacity=capacity,
            mesh=mesh,
            arena=arena,
            device=device,
        )
        from .scheduler import BatchGovernor, get_device_lane

        if lane is False:
            self.lane = None
        elif lane is None:
            self.lane = get_device_lane()
        else:
            self.lane = lane
        if self.lane is not None:
            self.lane.promote_after_s = max(lane_promote_ms, 0.0) / 1000.0
        self.plane.lane = self.lane
        self.governor = (
            BatchGovernor(
                base_interval_ms=flush_interval_ms,
                max_stretch=flush_stretch,
                drain_watermark=drain_watermark,
            )
            if governor
            else None
        )
        self.phase_offset_ms = phase_offset_ms
        # governor policy inputs ride a short-TTL depth cache:
        # pending_ops() is O(busy slots) and the capture seam calls the
        # governor per update — during a 2k-doc hydration storm an
        # exact walk per capture would cost the interactive path more
        # than the scheduling saves. Policy tolerates 5ms staleness;
        # the post-flush reschedule check stays exact.
        self._depth_cache = 0
        self._depth_cache_at = 0.0
        # native text lane: the C++ host path (lower+log+queue+window)
        # for plain-text docs — the round-3 host-plane bottleneck fix.
        # Serve-mode only (its broadcast windows ride the lane) and
        # contingent on the codec building.
        self.native_lane = bool(native_lane and serve and self.plane.enable_lane())
        self.flush_interval_ms = flush_interval_ms
        # broadcasts build from the HOST serve logs and run on their own
        # (shorter) coalescing window, decoupled from the device flush:
        # edits landing within the window share one frame per doc, and
        # the device round trip (an RTT per transfer when the chip is
        # remote-attached) never sits on the edit->observe path
        self.broadcast_interval_ms = broadcast_interval_ms
        self._flush_handle: Optional[asyncio.TimerHandle] = None
        # single-flight guard for the flush task: captures keep the
        # timer armed, and without this a long background lane hold
        # (one hydration round can run hundreds of ms) would stack one
        # queued flush task per tick — hundreds of waiters the arbiter
        # then scans per grant. One cycle in flight; it reschedules.
        self._flush_inflight = False
        self._broadcast_handle: Optional[asyncio.TimerHandle] = None
        self._last_broadcast_at = 0.0
        self.serve = serve
        self.serving = None
        self._docs: dict[str, object] = {}  # name -> server Document being served
        self._instance = None  # hocuspocus instance (hook dispatch)
        # strong refs to in-flight flush tasks: the event loop only
        # weakly references tasks, and a GC'd flush task silently stops
        # the serve pipeline (or strands the flush lock mid-acquire)
        self._flush_tasks: set = set()
        self._warm_task: Optional[asyncio.Task] = None
        # docs whose recycle attempt found no headroom for their live
        # state: further attempts are suppressed until unload (each
        # attempt costs a snapshot re-lower under the flush lock, and a
        # queued attempt re-registering the doc must see this verdict —
        # extension-level, since release+register replaces PlaneDocs)
        self._recycle_declined: set[str] = set()
        if serve:
            from .serving import PlaneServing

            self.serving = PlaneServing(self.plane)
            self.serving.flush_failure_handler = self._degrade_all_served
        # arena residency manager (tpu/residency.py): idle-doc eviction,
        # admission-controlled hydration, on-device compaction. Opt-in
        # (serve mode + a nonzero policy knob) so the default extension
        # keeps its permanent-lease behavior exactly.
        self.residency = None
        self._residency_handle: Optional[asyncio.TimerHandle] = None
        if serve and (evict_idle_secs > 0 or compact_threshold > 0):
            from .residency import ResidencyManager

            self.residency = ResidencyManager(
                self,
                evict_idle_secs=evict_idle_secs,
                hydrate_batch=hydrate_batch,
                compact_threshold=compact_threshold,
            )

    def _spawn_tracked(self, coro) -> None:
        spawn_tracked(self._flush_tasks, coro)

    # -- supervisor surface (tpu/supervisor.py) ------------------------------

    def planes(self) -> "list[MergePlane]":
        return [self.plane]

    def servings(self) -> list:
        return [] if self.serving is None else [self.serving]

    def scheduler_snapshot(self) -> dict:
        """Lane + governor state for /debug/scheduler (uniform with the
        sharded router's aggregate)."""
        return {
            "lane": None if self.lane is None else self.lane.snapshot(),
            "governors": [
                None if self.governor is None else self.governor.snapshot()
            ],
            "phase_offsets_ms": [self.phase_offset_ms],
        }

    def is_served(self, document_name: str) -> bool:
        return document_name in self._docs

    def degrade_all(self) -> None:
        """Drain every served doc to the CPU path (full-state fallback
        broadcast each) — the supervisor's breaker-open action."""
        recorder = get_flight_recorder()
        for name in list(self._docs):
            recorder.record(name, "breaker_degrade")
        self._degrade_all_served()

    def cancel_timers(self) -> None:
        """Teardown without touching the device (the supervisor's
        non-READY shutdown: a wedged runtime must not hang destroy)."""
        if self._flush_handle is not None:
            self._flush_handle.cancel()
            self._flush_handle = None
        if self._broadcast_handle is not None:
            self._broadcast_handle.cancel()
            self._broadcast_handle = None
        if self._residency_handle is not None:
            self._residency_handle.cancel()
            self._residency_handle = None

    async def reonboard(self, document, instance=None) -> None:
        """Fresh plane registration for a live document (supervisor hot
        attach / breaker recovery): drop any previous registration and
        run the ordinary load-time onboarding path."""
        name = document.name
        async with self.plane.flush_lock:
            self._detach_serving(name, self._docs.pop(name, None))
            if name in self.plane.docs:
                self.plane.release(name)
            self._recycle_declined.discard(name)
            if self.residency is not None:
                self.residency.forget_doc(name)
        await self.after_load_document(
            Payload(
                instance=instance if instance is not None else self._instance,
                document_name=name,
                document=document,
            )
        )

    # -- hooks ---------------------------------------------------------------

    async def on_listen(self, data: Payload) -> None:
        self.resume_warm()
        self._schedule_residency()

    @property
    def warm_running(self) -> bool:
        return self._warm_task is not None and not self._warm_task.done()

    def resume_warm(self) -> None:
        """Kick off compile warmup so the first live flush at each batch
        shape doesn't pay XLA/Mosaic compile time in the serving path.
        A no-op while a pass runs or once one has run to its end: the
        listen hook starts the pass, and a breaker recovery starts it
        again where a parked lane ended it early.

        The warm grid rides the device lane at the LOWEST priority, one
        admission per shape (tpu/scheduler.py): early client flushes
        preempt between compiles instead of waiting out the whole grid,
        and the shared warm registry makes shard 2..N of a sharded
        deployment skip shapes shard 1 already compiled (the jitted
        steps are module-level, so the XLA cache already holds them)."""
        if self.warm_running or self.plane.warm_stats["done"]:
            return

        async def warm() -> None:
            from .scheduler import CLASS_CANARY, LaneDeferred

            loop = asyncio.get_event_loop()
            plane = self.plane
            stats = plane.warm_stats
            started = time.perf_counter()

            async def warm_one(site: str, shape_key, fn) -> bool:
                """One warm entry under its own lane admission and lock
                acquisition: early client syncs and unloads interleave
                between compiles instead of stalling for the whole
                grid. A failure is recorded and the grid goes on — the
                remaining shapes may well compile. False = lane parked
                (the recovery's resume_warm() runs the pass again)."""
                ticket = None
                if self.lane is not None:
                    try:
                        ticket = await self.lane.admit(
                            CLASS_CANARY, site="warmup", weight=1
                        )
                    except LaneDeferred:
                        return False
                try:
                    async with plane.flush_lock:
                        dispatched = await loop.run_in_executor(None, fn)
                    # only warmup_compiles can answer False: the shared
                    # registry already covered this shape
                    stats["covered" if dispatched is False else "compiled"] += 1
                except Exception as error:
                    plane.note_warm_failure(site, shape_key, error)
                finally:
                    if ticket is not None:
                        ticket.release(preempted=ticket.should_yield())
                    stats["seconds"] = round(time.perf_counter() - started, 3)
                return True

            entries = [
                (
                    *plane._warm_site(shape),
                    lambda s=shape: plane.warmup_compiles(s, shared=True),
                )
                for shape in plane.warmup_shapes() + plane.warmup_aux_shapes()
            ]
            if self.serving is not None:
                pack_w = self.serving._pack_width()
                entries += [
                    (
                        "catchup_pack",
                        (width, pack_w),
                        lambda w=width: self.serving.warmup_gathers(w),
                    )
                    for width in self.serving._gather_widths()
                ]
                entries += [
                    ("sv_diff", (width,), lambda w=width: self.serving.warmup_triage(w))
                    for width in self.serving._TRIAGE_WIDTHS
                ]
            stats.update(entries=len(entries), compiled=0, covered=0, done=False)
            for site, shape_key, fn in entries:
                if not await warm_one(site, shape_key, fn):
                    return
            # from here every shape a live dispatch can take was
            # attempted: a later fresh compile is the recompile-storm
            # signal
            plane.compile_watch.mark_warmed()
            stats["done"] = True

        self._warm_task = spawn_tracked(self._flush_tasks, warm())

    def _attach_serving(self, name: str, document) -> None:
        """Hook a document into the plane's serving seams (shared by
        load-time onboarding and capacity recycling — the mirror of
        _detach_serving)."""
        from .serving import TpuSyncSource

        document.sync_source = TpuSyncSource(self.serving, name, document)
        document.broadcast_source = self
        self._docs[name] = document

    async def after_load_document(self, data: Payload) -> None:
        from ..crdt import encode_state_as_update

        self._instance = data.instance
        name = data.document_name
        if self.residency is not None:
            self.residency.touch(name)
            if self.residency.is_evicted(name):
                # cold load of an evicted doc: re-enter through the
                # admission-controlled hydration queue (a storm of cold
                # loads must never thundering-herd the device); the doc
                # serves from the CPU path until its batch lands
                self.residency.request_hydration(name, data.document)
                return
        lane_doc = None
        if self.native_lane:
            lane_doc = self.plane.register_lane(name)
        if lane_doc is None:
            self.plane.register(name)
        snapshot = encode_state_as_update(data.document)
        # receivers get pre-load state via sync, not broadcast
        self.plane.enqueue_update(name, snapshot, presync=True)
        if lane_doc is not None and not self.plane.is_supported(name):
            # load-time lane demote (the snapshot holds rich content):
            # nothing is served yet, so retry on the Python path in
            # place instead of the full fallback+recycle dance.
            # flush_lock: release() rebuilds device state and must not
            # race an executor-side flush holding donated buffers.
            plane_doc = self.plane.docs.get(name)
            if plane_doc is not None and plane_doc.retire_reason == "lane_demote":
                async with self.plane.flush_lock:
                    self.plane.release(name)
                    self.plane.register(name)
                    self.plane.enqueue_update(name, snapshot, presync=True)
        if self.serve and self.plane.is_supported(name):
            self._attach_serving(name, data.document)
        self._schedule_flush()

    async def on_change(self, data: Payload) -> None:
        if self.serve and data.document_name in self._docs:
            return  # already captured synchronously in try_capture
        if self.residency is not None:
            self.residency.touch(data.document_name)
            if self.residency.is_evicted(data.document_name):
                # fresh traffic on an evicted doc: updates ride the CPU
                # fan-out while the doc queues for hydration (the live
                # document tail replayed at admission carries them)
                self.residency.request_hydration(
                    data.document_name, data.document
                )
                return
        if self.serve:
            # fresh traffic on a doc that degraded off the plane (e.g.
            # a device OVERFLOW retire from the health sweep — a seam
            # try_capture never sees, since capture stops at fallback):
            # busy docs are worth re-onboarding from their live snapshot
            plane_doc = self.plane.docs.get(data.document_name)
            if plane_doc is not None and plane_doc.retired:
                self._maybe_recycle(data.document, plane_doc.retire_reason)
                return
        accepted = self.plane.enqueue_update(data.document_name, data.update)
        if accepted and self.governor is not None:
            self.governor.note_arrival(accepted)
        self._schedule_flush()

    async def after_unload_document(self, data: Payload) -> None:
        name = data.document_name
        instance = data.instance
        # release mutates the queue/log registries a concurrent
        # executor-side flush iterates — serialize with it. ALL of the
        # teardown sits inside the lock and behind a liveness re-check:
        # a rejoin can re-load the document while unload hooks await,
        # and plane.register() then reuses this registration (same
        # rows, same lowerer clocks — the arena already mirrors the
        # doc), so a late release here would silently detach the NEW
        # incarnation from the plane for the rest of its life.
        while True:
            async with self.plane.flush_lock:
                loading = (
                    None if instance is None else instance.loading_documents.get(name)
                )
                if loading is None:
                    if instance is not None and name in instance.documents:
                        return  # re-loaded while we waited: registration lives on
                    self._detach_serving(name, self._docs.pop(name, None))
                    self.plane.release(name)
                    # a future incarnation starts with a fresh recycle
                    # budget (its live state may be much smaller).
                    # _lane_banned is deliberately NOT cleared: a doc
                    # that demoted carries rich content in its stored
                    # state — re-trying the lane on every reload would
                    # re-pay the demote transient (degraded cross-
                    # instance flow while the rebuild lands) each time.
                    self._recycle_declined.discard(name)
                    if self.residency is not None:
                        self.residency.forget_doc(name)
                    return
            # A re-load is in flight. Wait for it OUTSIDE the lock: on
            # success its own eventual unload fires this hook again; on
            # FAILURE no further after_unload will ever fire for this
            # name (failed loads never enter instance.documents), so we
            # must loop back and do the teardown ourselves or the plane
            # registration leaks forever.
            try:
                await asyncio.shield(loading)
                return
            except Exception:
                # an already-failed future raises without suspending;
                # yield so create_document's finally (which pops
                # loading_documents) runs before we re-check — without
                # this the loop can spin forever without ever letting
                # the event loop breathe
                await asyncio.sleep(0)

    async def on_destroy(self, data: Payload) -> None:
        if self._flush_handle is not None:
            self._flush_handle.cancel()
        if self._broadcast_handle is not None:
            self._broadcast_handle.cancel()
        if self._residency_handle is not None:
            self._residency_handle.cancel()
            self._residency_handle = None
        # flush the broadcast tail (LOCAL only: higher-priority
        # extensions like Redis destroy first, so their pub/sub is
        # already closed — peers heal via the join protocol and
        # anti-entropy), then fully drain the device queues: no timer
        # fires after teardown to pick up either. final=True: the drain
        # is pause-exempt — a parked lane must not strand teardown
        self._broadcast_served(cross_instance=False)
        await self._flush_now(max_batches=None, final=True)

    # -- serving: update capture (called by Document._handle_update) ---------

    def is_capturing(self, name: str) -> bool:
        """True when this doc's updates actually ride plane windows
        right now. False during degrade/demote windows, where updates
        take the per-update CPU fan-out — consumers that suppress
        per-op propagation in favor of window frames (the Redis
        extension's cross-instance publish) must fall back to per-op
        when this is False, or remote peers starve down to
        anti-entropy rates."""
        if name not in self._docs:
            return False
        if self.residency is not None and self.residency.is_compacting(name):
            return False  # compaction window: updates ride per-op fan-out
        doc = self.plane.docs.get(name)
        return doc is not None and not doc.retired

    def try_capture(self, document, update: bytes, origin) -> bool:
        """Claim an update for plane-batched broadcast. False = CPU fan-out."""
        with get_tracer().span("plane.capture"):
            return self._try_capture(document, update, origin)

    def _try_capture(self, document, update: bytes, origin) -> bool:
        from ..server.hocuspocus import REDIS_ORIGIN
        from ..server.types import REPLICA_ORIGIN

        name = document.name
        if not self.serve or name not in self._docs:
            return False
        if self.residency is not None:
            self.residency.touch(name)
            if self.residency.is_compacting(name):
                # an executor-side compaction is rewriting this doc's
                # rows: enqueueing would race the serve-log rebuild.
                # Ride the CPU fan-out (always correct); the manager's
                # post-compaction tail replay re-syncs the plane.
                return False
        plane = self.plane
        if not plane.is_supported(name):
            plane_doc = plane.docs.get(name)
            reason = plane_doc.retire_reason if plane_doc is not None else None
            if reason == "lane_demote":
                # keep serving attached; this update rides the CPU
                # fan-out until the Python-plane registration lands.
                # Re-spawn per update: an earlier attempt may have
                # bailed (e.g. zero connections at the time) and the
                # rebuild's own guards make redundant spawns no-ops.
                self._spawn_tracked(self._rebuild_lane_doc(document))
                return False
            # already degraded (e.g. a device OVERFLOW retire from the
            # post-flush health sweep, where no recycle seam runs) —
            # this fresh traffic is the signal the doc is still busy
            # and worth re-onboarding
            self._fallback_to_cpu(document)
            self._maybe_recycle(document, reason)
            return False
        # capture seam: stamp the (sampled) update with a trace id + its
        # enqueue timestamp BEFORE queueing — an executor-side flush can
        # drain the queue the moment the op lands, and a stamp arriving
        # after that drain would miss its own flush cycle
        book = plane.update_traces
        trace_id = plane.note_trace(name) if book.enabled else None
        # replica-stream applies count as remote ops: the merged window's
        # cross_update must carry only locally-originated ops, or the
        # plane would echo the owner's ticks back over the replica lane
        with get_tracer().span("plane.lower"):
            accepted = plane.enqueue_update(
                name, update, remote=origin in (REDIS_ORIGIN, REPLICA_ORIGIN)
            )
        if trace_id is not None and not accepted:
            # nothing queued (deduplicated, or the doc degraded during
            # the enqueue — where retire already dropped the doc's book)
            book.unstamp(name, trace_id)
        if not plane.is_supported(name):
            # this very update degraded the doc; it broadcasts via CPU
            plane_doc = plane.docs.get(name)
            reason = plane_doc.retire_reason if plane_doc is not None else None
            if reason == "lane_demote":
                # the doc outgrew the native text lane (first map/rich
                # op): rebuild it on the Python plane IN PLACE — serving
                # stays attached, this and subsequent updates ride the
                # per-update CPU fan-out until the rebuild lands
                self._spawn_tracked(self._rebuild_lane_doc(document))
                return False
            self._fallback_to_cpu(document)
            self._maybe_recycle(document, reason)
            return False
        if accepted and self.governor is not None:
            # feed the arrival-rate EWMA BEFORE scheduling: the cadence
            # decision below reads it
            self.governor.note_arrival(accepted)
        self._schedule_flush()
        self._schedule_broadcast()
        return True

    async def _rebuild_lane_doc(self, document) -> None:
        """In-place re-onboard of a lane-demoted doc onto the Python
        plane path.

        Unlike capacity recycling there is no CPU-fallback broadcast:
        receivers stay current through (1) the pending lane window,
        shipped here before the log is dropped, and (2) per-update CPU
        fan-out for every update between the demote and this rebuild
        (try_capture returns False for a retired doc). The ban set
        routes register() to the Python path."""
        from ..crdt import encode_state_as_update

        name = document.name
        plane = self.plane
        async with plane.flush_lock:
            if document.get_connections_count() <= 0:
                return  # unloading anyway
            doc = plane.docs.get(name)
            if (
                doc is None
                or not doc.retired
                or doc.retire_reason != "lane_demote"
                or name not in self._docs
            ):
                return  # state moved on; leave it be
            try:
                pair = self.serving.build_broadcast_pair(name)
            except Exception:
                pair = None
            if pair is not None:
                update, cross = pair
                document.broadcast_update_frame(update)
                if cross is not None and self._instance is not None:
                    self._spawn_tracked(
                        self._instance.hooks(
                            "on_plane_broadcast",
                            Payload(
                                instance=self._instance,
                                document_name=name,
                                document=document,
                                update=cross,
                            ),
                        )
                    )
            try:
                plane.release(name)
                plane.register(name)
                plane.enqueue_update(
                    name, encode_state_as_update(document), presync=True
                )
                new_doc = plane.docs.get(name)
                if new_doc is None or new_doc.lowerer.unsupported:
                    raise RuntimeError("live content unsupported")
                # the cursor still points into the LANE's op log; left
                # stale it would swallow (or mis-slice) every window of
                # the fresh Python-path registration
                self.serving.broadcast_cursor[name] = len(new_doc.serve_log)
            except Exception:
                # genuinely unsupported content: the doc leaves the
                # plane for the plain CPU path
                self._fallback_to_cpu(document)
                return
        self._schedule_flush()

    def _maybe_recycle(self, document, reason: "Optional[str]") -> None:
        """Schedule a recycle for row-exhaustion retires.

        Arena rows are append-only and tree docs hold one row per
        sequence (including deleted subtrees'), so a long-lived busy
        doc eventually exhausts its rows (host-projected: "capacity";
        device-detected mid-flush, e.g. RLE split costs the host bound
        can't see: "overflow") or the plane ("plane_full") — re-onboard
        with fresh rows lowered from the live CPU snapshot. Collected
        SUBTREES vanish from the snapshot, so such docs reclaim most of
        their rows; on the RLE arena a re-lowered snapshot is compact
        again (ContentDeleted runs cost one entry each). Docs whose
        live state itself has no headroom are left on the CPU path by
        the recycle guards. Content retires ("unsupported") and desyncs
        never recycle — the condition is permanent or needs a human.
        """
        if reason not in ("capacity", "plane_full", "overflow", "lane_demote"):
            return
        if document.name in self._recycle_declined:
            return
        self._spawn_tracked(self._recycle_capacity_doc(document))

    async def _recycle_capacity_doc(self, document) -> None:
        """Give a row-exhaustion-retired doc fresh arena rows.

        The triggering update already reached receivers via the CPU
        fallback broadcast; this re-onboards the doc for FUTURE traffic
        exactly like a reload does — release the exhausted rows (ALL of
        them, including deleted subtrees'), re-register, lower the live
        snapshot as presync. If the live state itself nearly fills a
        row (no headroom) or still doesn't fit the plane, the doc stays
        on the CPU path rather than thrash through recycles.
        """
        from .scheduler import CLASS_CATCHUP, LaneDeferred

        ticket = None
        if self.lane is not None:
            try:
                # catch-up class: recovery work for a live busy doc —
                # outranks compaction sweeps, yields to live flushes
                ticket = await self.lane.admit(CLASS_CATCHUP, site="recycle")
            except LaneDeferred:
                return  # parked: the next capture on this doc retries
        try:
            await self._recycle_capacity_doc_admitted(document)
        finally:
            if ticket is not None:
                ticket.release()

    async def _recycle_capacity_doc_admitted(self, document) -> None:
        from ..crdt import encode_state_as_update

        name = document.name
        plane = self.plane
        async with plane.flush_lock:
            if document.get_connections_count() <= 0:
                return  # unloading anyway
            if name in self._docs:
                return  # already re-onboarded
            if name in self._recycle_declined:
                return  # a queued attempt ran after the verdict landed
            existing = plane.docs.get(name)
            if existing is None or not existing.retired:
                return  # registration changed under us; leave it be
            if (
                self.residency is not None
                and existing.retire_reason in ("capacity", "overflow")
            ):
                # on-device compaction first: when the doc's LIVE state
                # fits its rows, the tombstone-GC kernel recycles it in
                # place — no release, no snapshot re-lower, no re-upload.
                # On failure (nothing reclaimable, or the replayed tail
                # re-exhausted the row) fall through to the snapshot
                # recycle below.
                if await self.residency.compact_and_replay_locked(
                    name, document
                ):
                    return
            try:
                plane.release(name)
                # a hot plain-text doc keeps its native lane across the
                # recycle (unless it demoted: the ban set routes it to
                # the Python path inside register_lane)
                if not (self.native_lane and plane.register_lane(name)):
                    plane.register(name)
                snapshot = encode_state_as_update(document)
                plane.enqueue_update(name, snapshot, presync=True)
                doc = plane.docs.get(name)
                if (
                    doc is not None
                    and doc.retired
                    and doc.retire_reason == "lane_demote"
                ):
                    # the doc had never attempted the lane before (not
                    # banned) and its snapshot is rich: retry in place
                    # on the Python path instead of stranding it
                    plane.release(name)
                    plane.register(name)
                    plane.enqueue_update(name, snapshot, presync=True)
                    doc = plane.docs.get(name)
                if doc is None or doc.lowerer.unsupported:
                    self._recycle_declined.add(name)
                    return  # live content unsupported/too big: stays on CPU
                # guard retires below use count=False: this incident was
                # already counted when the original registration retired
                for slot in doc.seqs.values():
                    if plane.projected_len[slot] > plane.capacity * 3 // 4:
                        plane.retire_doc(name, "capacity", count=False)
                        self._recycle_declined.add(name)
                        return  # no row headroom: recycling would thrash
                if len(plane.free) < 2:
                    # plane-level headroom: with no spare rows the next
                    # new sequence would plane_full again immediately —
                    # each thrash cycle costs a full-state broadcast
                    # plus a snapshot re-lower, strictly worse than the
                    # CPU path
                    plane.retire_doc(name, "plane_full", count=False)
                    self._recycle_declined.add(name)
                    return
                plane.counters["docs_recycled"] += 1
                get_flight_recorder().record(name, "recycle")
                self._attach_serving(name, document)
            except Exception:
                # a half-recycled registration (released + re-registered
                # but never attached) would silently swallow ops: mark
                # it retired so the doc lives plainly on the CPU path
                from ..server import logger as _logger_mod

                _logger_mod.log_error(f"recycle failed for {name!r}; staying on CPU")
                plane.retire_doc(name, "fallback", count=False)
                return
        self._schedule_flush()

    def _detach_serving(self, name: str, document) -> None:
        """Unhook a document from the plane's serving seams and drop its
        serving caches (shared by CPU fallback and unload teardown)."""
        if document is not None:
            document.sync_source = None
            document.broadcast_source = None
        if self.serving is not None:
            self.serving.forget(name, self.plane.docs.get(name))

    def _fallback_to_cpu(self, document) -> None:
        name = document.name
        if self._docs.pop(name, None) is None:
            return  # already degraded
        self._detach_serving(name, document)
        if name in self.plane.docs:
            self.plane.retire_doc(name, "fallback")
        self.plane.update_traces.drop(name)
        get_flight_recorder().record(name, "degrade")
        self.plane.counters["cpu_fallbacks"] += 1
        # receivers may hold plane broadcasts only up to the last flush;
        # ship the full CPU state once (dedup makes it a cheap no-op for
        # anyone already current)
        from ..crdt import encode_state_as_update

        document.broadcast_update_frame(encode_state_as_update(document))

    # -- flush ---------------------------------------------------------------

    def _degrade_all_served(self) -> None:
        """Device-flush fault: the dead flush already consumed queued ops,
        so every served doc degrades to the CPU path via a full-state
        broadcast rather than silently dropping captured updates."""
        from ..server import logger as _logger_mod

        _logger_mod.log_error("plane flush failed; degrading served docs to CPU")
        for _, document in list(self._docs.items()):
            try:
                self._fallback_to_cpu(document)
            except Exception:
                _logger_mod.log_error(f"CPU fallback failed for {document.name!r}")

    def _broadcast_served(self, cross_instance: bool = True) -> None:
        """One broadcast pass: every doc with new serve-log records gets
        one merged frame. Pure host work (serve logs + cached health
        rows) — never waits on the device flush; a desync the validator
        finds a cycle later degrades that doc via full-state CPU
        fallback, which supersedes any optimistic frames (receivers
        converge by CRDT idempotence either way)."""
        if not self.serve:
            return
        queued: list = []
        with get_tracer().span("plane.broadcast"):
            self._broadcast_pass(cross_instance, queued)
        # the window's ticks run now, not a turn of the loop later: each
        # still waits for its durability gate, and the `call_soon` its
        # enqueue scheduled finds nothing pending (server/fanout.py)
        for document in queued:
            document.fanout.flush()

    def _broadcast_pass(self, cross_instance: bool, queued: list) -> None:
        plane = self.plane
        dirty = list(plane.dirty)
        plane.dirty.clear()
        docs_by_name: dict = {}
        served_dirty: list = []
        for name in dirty:
            document = self._docs.get(name)
            if document is not None:
                docs_by_name[name] = document
                served_dirty.append(name)
        # one vectorized health compare covers the common case; only
        # suspects pay the per-doc check (which retires on failure)
        try:
            healthy, suspects = self.serving.filter_healthy(served_dirty)
        except Exception:
            from ..server import logger as _logger_mod

            _logger_mod.log_error(
                "vectorized health filter failed; falling back to per-doc checks"
            )
            healthy, suspects = [], served_dirty
        for name in suspects:
            document = docs_by_name[name]
            # per-doc guard: the stated safety model is "any serving
            # error degrades that doc to the CPU path" — an exception
            # here must neither strand this doc's ops nor skip the
            # remaining docs' broadcasts
            try:
                if self.serving.doc_healthy(name) is None:
                    self._fallback_to_cpu(document)
                    continue
            except Exception:
                self._degrade_one(name, document)
                continue
            healthy.append(name)
        if not healthy:
            return
        try:
            # lane docs inside resolve in ONE batched native call — the
            # per-doc Python overhead dominates at 10k-doc window widths;
            # Python-path docs are isolated per doc inside (failed list)
            pairs, failed = self.serving.build_broadcast_pairs(healthy)
        except Exception:
            # only the batch call itself can land here (per-doc failures
            # come back in `failed`): a plane-level fault, so degrading
            # the set is the honest outcome
            for name in healthy:
                self._degrade_one(name, docs_by_name[name])
            return
        for name in failed:
            self._degrade_one(name, docs_by_name[name])
        book = plane.update_traces
        for name, pair in pairs:
            document = docs_by_name[name]
            try:
                if pair is None:
                    # empty window (e.g. presync-only records): close any
                    # flushed traces anyway — fan-out was a no-op
                    book.finish(name)
                    continue
                update, cross_update = pair
                # window frames ride the document's broadcast tick
                # (server/fanout.py): one merged frame per audience,
                # catch-up tiering for slow sockets — and the lifecycle
                # trace closes at LAST-SOCKET-ENQUEUE via the tick's
                # completion callback, keeping the span-sum invariant
                # honest about when fan-out actually finished
                document.queue_broadcast(
                    update,
                    on_complete=(
                        lambda t_last, _name=name: book.finish(_name, t_last)
                    ),
                )
                queued.append(document)
                if (
                    cross_instance
                    and cross_update is not None
                    and self._instance is not None
                ):
                    # cross-instance fan-out rides the merged window
                    # frame (extensions like Redis publish it) minus
                    # remote-origin ops, replacing per-op SyncStep1
                    # chatter with one coalesced message per window
                    self._spawn_tracked(
                        self._instance.hooks(
                            "on_plane_broadcast",
                            Payload(
                                instance=self._instance,
                                document_name=name,
                                document=document,
                                update=cross_update,
                            ),
                        )
                    )
            except Exception:
                self._degrade_one(name, document)

    def _degrade_one(self, name: str, document) -> None:
        from ..server import logger as _logger_mod

        _logger_mod.log_error(
            f"plane broadcast failed for {name!r}; degrading to CPU path"
        )
        try:
            self._fallback_to_cpu(document)
        except Exception:
            _logger_mod.log_error(f"CPU fallback failed for {name!r}")

    async def _flush_now(
        self, max_batches: Optional[int] = 1, final: bool = False
    ) -> None:
        """Flush+serve with the DEVICE step off the event loop.

        plane.flush() host-syncs on the integrate step; running it
        inline froze the loop for the duration of every device step
        (measured 16x send-throughput loss on the CPU backend at config2
        shape). The executor hop keeps websockets pumping while the
        device integrates; the lock serializes against the batched
        catch-up drain and unload-time registry mutation.

        Broadcasts do NOT run here: they build from the host serve logs
        on their own timer (_schedule_broadcast), so the device cycle —
        upload + kernel + one combined health readback, each transfer ~a
        full RTT on a remote-attached chip — only gates validation and
        sync serves, never the edit->observe path. The default of ONE
        kernel batch per cycle keeps cycles short; the remainder
        reschedules. on_destroy passes final=True with max_batches=None
        for a pause-exempt full drain — no timer fires after teardown.

        The cycle admits through the device lane as INTERACTIVE before
        touching the flush lock (tpu/scheduler.py): background clients
        — hydration batches, compaction sweeps, warm compiles — queue
        behind it and yield between their own microbatches, so a 2-doc
        flush never sits behind a full-population sweep. A parked lane
        (supervisor breaker open) defers the cycle instead of stacking
        blocked tasks onto a wedged device.
        """
        from .scheduler import CLASS_INTERACTIVE, CLASS_NAMES, LaneDeferred

        if self._flush_inflight and not final:
            return  # the in-flight cycle reschedules; don't stack waiters
        self._flush_inflight = True
        try:
            ticket = None
            if self.lane is not None:
                try:
                    ticket = await self.lane.admit(
                        CLASS_INTERACTIVE,
                        site="flush",
                        ignore_pause=final,
                        deadline_s=5.0 if final else None,
                    )
                except LaneDeferred as deferred:
                    get_flight_recorder().record(
                        "__plane__",
                        "flush_deferred",
                        lane_class=CLASS_NAMES[deferred.lane_class],
                        wait_ms=round(deferred.waited_s * 1000.0, 3),
                        reason=deferred.reason,
                    )
                    if final:
                        ticket = None  # teardown drain proceeds unarbitrated
                    elif self.plane.pending_ops() > 0:
                        # parked: retry on a slow cadence (the supervisor
                        # resumes the lane at re-attach; a tight retry loop
                        # would just churn timers against a wedged device)
                        self._schedule_flush(delay_override=0.25)
                        return
                    else:
                        return
            try:
                if self.governor is not None and max_batches == 1:
                    congested = self.lane is not None and self.lane.contended()
                    max_batches = self.governor.max_batches(
                        self._policy_depth(), congested
                    )
                async with self.plane.flush_lock:
                    try:
                        await asyncio.get_event_loop().run_in_executor(
                            None, lambda: self.plane.flush(max_batches)
                        )
                    except Exception:
                        self._degrade_all_served()
                        return
                    if self.serve and not self._post_flush():
                        return
                if self.governor is not None:
                    self.governor.note_cycle(self.plane.flush_stats)
            finally:
                if ticket is not None:
                    ticket.release()
            if self.plane.pending_ops() > 0:
                self._schedule_flush()
            elif self.governor is not None:
                self.governor.note_park()
        finally:
            self._flush_inflight = False

    def _post_flush(self) -> bool:
        """What a flush cycle runs back on the loop once the executor
        returns: the serving refresh, then the desync sweep. False when
        the refresh failed and every served doc was degraded."""
        with get_tracer().span("plane.post_flush"):
            try:
                self.serving.refresh()
            except Exception:
                self._degrade_all_served()
                return False
            self._validate_served()
            return True

    def _validate_served(self) -> None:
        """Post-flush desync sweep, vectorized over every slot.

        Broadcasts run optimistically ahead of the device, so this
        sweep — one numpy compare of the flush's combined readback
        against the validated dispatch tallies — is what catches a
        device-side op rejection even when no further edit or sync
        would touch the doc. Affected served docs degrade to the CPU
        path via the usual full-state fallback broadcast.
        """
        plane = self.plane
        if plane.last_lengths is None or plane.last_gen is None:
            return
        bad = (
            plane.slot_live
            & (plane.last_gen == plane.slot_gen)
            & ((plane.validated_units != plane.last_lengths) | plane.last_overflows)
        )
        if not bad.any():
            return
        for slot in np.nonzero(bad)[0]:
            name = plane.slot_owner.get(int(slot))
            if name is None:
                continue
            # doc_healthy retires with the right reason; served docs
            # then fall back with the one-time full-state broadcast
            if self.serving.doc_healthy(name) is None:
                document = self._docs.get(name)
                if document is not None:
                    self._fallback_to_cpu(document)

    def _schedule_flush(self, delay_override: Optional[float] = None) -> None:
        if self._flush_handle is not None:
            return

        def run() -> None:
            self._flush_handle = None
            self._spawn_tracked(self._flush_now())

        if delay_override is not None:
            delay = delay_override
        elif self.governor is not None:
            # arrival-aware cadence: immediate full drain past the
            # queue-depth watermark, base cadence under steady load or
            # lane congestion, stretched ticks when arrivals are sparse
            congested = self.lane is not None and self.lane.contended()
            delay = self.governor.flush_delay_s(
                self._policy_depth(), congested
            )
        else:
            delay = self.flush_interval_ms / 1000
        if delay:
            # sustained-cadence ticks quantize onto the shard's phase
            # grid; the watermark's zero-delay drain stays IMMEDIATE
            # (same exemption as the broadcast scheduler's idle path)
            delay = self._align_to_phase(delay, self.flush_interval_ms / 1000)
        self._flush_handle = asyncio.get_event_loop().call_later(delay, run)

    def _policy_depth(self) -> int:
        """Queued-op depth for GOVERNOR decisions only (5ms-stale)."""
        now = time.monotonic()
        if now - self._depth_cache_at > 0.005:
            self._depth_cache = self.plane.pending_ops()
            self._depth_cache_at = now
        return self._depth_cache

    def _align_to_phase(self, delay: float, interval_s: float) -> float:
        """Deterministic per-shard timer stagger: quantize the fire time
        onto this shard's phase grid (offset i/N of the interval, set by
        the sharded router) so N shards stop tick-aligning their device
        dispatches. Never fires earlier than asked — alignment only adds
        up to one interval. No-op for unsharded extensions."""
        if self.phase_offset_ms is None or interval_s <= 0:
            return delay
        now = asyncio.get_event_loop().time()
        phase = (self.phase_offset_ms / 1000.0) % interval_s
        fire = now + delay
        aligned = (
            math.ceil((fire - phase) / interval_s) * interval_s + phase
        )
        return max(aligned - now, delay)

    def _schedule_residency(self) -> None:
        """Periodic residency maintenance (eviction + proactive
        compaction sweeps), riding its own timer like the flush and
        broadcast cadences."""
        if self.residency is None or self._residency_handle is not None:
            return

        def run() -> None:
            self._residency_handle = None
            self._spawn_tracked(self._residency_tick())

        self._residency_handle = asyncio.get_event_loop().call_later(
            self.residency.maintenance_interval, run
        )

    async def _residency_tick(self) -> None:
        try:
            await self.residency.run_maintenance()
        except Exception:
            from ..server import logger as _logger_mod

            _logger_mod.log_error("residency maintenance failed (continuing)")
        self._schedule_residency()

    def _schedule_broadcast(self) -> None:
        if not self.serve or self._broadcast_handle is not None:
            return
        loop = asyncio.get_event_loop()

        scheduled_at = loop.time()

        def run() -> None:
            self._broadcast_handle = None
            now = self._last_broadcast_at = loop.time()
            counters = self.plane.counters
            counters["broadcast_passes"] += 1
            counters["broadcast_wait_ms_total"] += (now - scheduled_at) * 1000.0
            self._broadcast_served()

        # coalescing window only under sustained traffic: a lone edit
        # after an idle gap broadcasts on the next loop tick (the
        # window would be pure added latency), while back-to-back edits
        # within the window share one frame per doc. Sustained-traffic
        # windows quantize onto the shard's phase grid (sharded router)
        # so N shards' broadcast passes stop landing on the same tick.
        window = self.broadcast_interval_ms / 1000
        idle = scheduled_at - self._last_broadcast_at
        delay = 0.0 if idle >= window else window
        if delay:
            delay = self._align_to_phase(delay, window)
        self._broadcast_handle = loop.call_later(delay, run)
