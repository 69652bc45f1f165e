"""Multi-chip sharding for the merge plane (jax.sharding + jit).

The doc axis is the data-parallel dimension (SURVEY.md §5.7: documents
are the scaling dimension); the arena (unit) axis is the
sequence-parallel dimension. Shardings are annotated and XLA inserts the
collectives (all-gathers for cross-shard gathers, all-reduce for the
global op count) — the ICI-riding equivalent of the reference's
Redis fan-out data plane.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .kernels import DocState, OpBatch, integrate_op_slots, make_empty_state


def enumerate_devices(count: int = 0) -> list:
    """The device roster for the per-chip cell plane (tpu/cells.py).

    count <= 0 means "every local device"; a count smaller than the
    roster uses the first `count` chips. A count LARGER than the roster
    is an error on an accelerator — wrapping would stack several cells'
    arenas on one chip while the deployment believes it has one each.
    On the CPU platform it wraps (cell i pins to device i % n) so CI
    hosts with one forced-host device can still exercise an 8-cell
    plane."""
    devices = jax.local_devices()
    if count <= 0:
        return list(devices)
    if count > len(devices) and devices[0].platform != "cpu":
        raise ValueError(
            f"{count} device cells requested but only {len(devices)} "
            f"{devices[0].platform} device(s) are visible; one cell per "
            "chip is the contract (--tpu-devices 0 uses every chip)"
        )
    return [devices[i % len(devices)] for i in range(count)]


def make_mesh(devices: Optional[list] = None, doc_axis: Optional[int] = None) -> Mesh:
    """1D or 2D mesh over (doc, unit). Defaults to all devices on doc."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if doc_axis is None:
        doc_axis = n
    unit_axis = n // doc_axis
    device_array = np.asarray(devices).reshape(doc_axis, unit_axis)
    return Mesh(device_array, ("doc", "unit"))


def state_sharding(mesh: Mesh) -> DocState:
    """NamedShardings for each DocState field."""
    arena = NamedSharding(mesh, P("doc", "unit"))
    per_doc = NamedSharding(mesh, P("doc"))
    return DocState(
        id_client=arena,
        id_clock=arena,
        rank=arena,
        origin_rank=arena,
        deleted=arena,
        length=per_doc,
        overflow=per_doc,
    )


def ops_sharding(mesh: Mesh) -> OpBatch:
    slot_doc = NamedSharding(mesh, P(None, "doc"))
    return OpBatch(
        kind=slot_doc,
        client=slot_doc,
        clock=slot_doc,
        run_len=slot_doc,
        left_client=slot_doc,
        left_clock=slot_doc,
        right_client=slot_doc,
        right_clock=slot_doc,
    )


def make_sharded_step(mesh: Mesh, use_pallas: Optional[bool] = None, interpret: bool = False):
    """Jitted multi-chip integrate step with explicit in/out shardings.

    The returned callable takes (DocState, OpBatch with (K, D, ...) op
    slots) and returns (DocState, integrated-op count). The op count is
    a global reduction — XLA lowers it to an all-reduce over the mesh.

    Two lowering strategies:
    - XLA scan (default off-TPU, and whenever the arena axis is itself
      sharded): plain jit with shardings; XLA inserts the collectives
      that the arena-axis reductions need.
    - Pallas per shard (default on TPU with a doc-only mesh): shard_map
      over the 'doc' axis runs the VMEM-resident kernel independently
      on each device's doc shard — zero cross-device traffic in the hot
      loop, one psum for the global count. Documents never interact, so
      doc-parallelism is embarrassingly parallel by construction.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and mesh.shape["unit"] == 1
    if use_pallas and mesh.shape["unit"] != 1:
        raise ValueError("the Pallas sharded step requires a doc-only mesh")

    if not use_pallas:
        st_shard = state_sharding(mesh)
        op_shard = ops_sharding(mesh)
        count_sharding = NamedSharding(mesh, P())
        return jax.jit(
            integrate_op_slots.__wrapped__,  # re-jit with shardings
            in_shardings=(st_shard, op_shard),
            out_shardings=(st_shard, count_sharding),
            donate_argnums=(0,),
        )

    from .pallas_kernels import integrate_op_slots_pallas

    arena = P("doc", None)
    per_doc = P("doc")
    st_spec = DocState(arena, arena, arena, arena, arena, per_doc, per_doc)
    op_spec_p = P(None, "doc")
    ops_spec = OpBatch(*([op_spec_p] * 8))

    def local_step(state: DocState, ops: OpBatch):
        new_state, count = integrate_op_slots_pallas(state, ops, interpret=interpret)
        return new_state, jax.lax.psum(count, "doc")

    return jax.jit(
        jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(st_spec, ops_spec),
            out_specs=(st_spec, P()),
            # pallas_call out_shapes carry no varying-mesh-axes info
            check_vma=False,
        ),
        donate_argnums=(0,),
    )


def sparse_ops_sharding(mesh: Mesh) -> "tuple[OpBatch, NamedSharding]":
    """(K, B) sparse op batches + the (B,) slot-routing vector are tiny
    (B = busy docs, not the population) — replicate them across the
    mesh and let XLA route each busy row's gather/scatter to the shard
    that owns it. Returns (op shardings, slots sharding)."""
    replicated = NamedSharding(mesh, P(None, None))
    return OpBatch(*([replicated] * 8)), NamedSharding(mesh, P(None))


def make_sharded_sparse_step(mesh: Mesh):
    """Jitted multi-chip SPARSE integrate step: (K, B) ops + (B,) slot
    routing against the doc-sharded arenas. The gather/scatter pair is
    partitioned by XLA — each shard materializes only its own busy
    rows' updates (collectives route rows whose owner differs from the
    batch layout), so per-flush traffic scales with B, not D."""
    from .kernels import integrate_op_slots_sparse

    st_shard = state_sharding(mesh)
    op_shard, slot_shard = sparse_ops_sharding(mesh)
    count_sharding = NamedSharding(mesh, P())
    return jax.jit(
        integrate_op_slots_sparse.__wrapped__,
        in_shardings=(st_shard, op_shard, slot_shard),
        out_shardings=(st_shard, count_sharding),
        donate_argnums=(0,),
    )


def make_sharded_append_step(mesh: Mesh):
    """Jitted multi-chip run-append step (the sequential fast path):
    three replicated (K, B) run fields + the (B,) slot routing vector
    against the doc-sharded arenas — the same small-batch replication
    discipline as make_sharded_sparse_step, so per-flush traffic scales
    with B whichever path the classifier picks."""
    from .kernels import append_run_slots_sparse

    st_shard = state_sharding(mesh)
    _, slot_shard = sparse_ops_sharding(mesh)
    replicated = NamedSharding(mesh, P(None, None))
    count_sharding = NamedSharding(mesh, P())
    return jax.jit(
        append_run_slots_sparse.__wrapped__,
        in_shardings=(st_shard, replicated, replicated, replicated, slot_shard),
        out_shardings=(st_shard, count_sharding),
        donate_argnums=(0,),
    )


def make_sharded_rle_append_step(mesh: Mesh):
    """RLE twin of make_sharded_append_step."""
    from .kernels_rle import append_run_slots_rle_sparse

    st_shard = rle_state_sharding(mesh)
    _, slot_shard = sparse_ops_sharding(mesh)
    replicated = NamedSharding(mesh, P(None, None))
    count_sharding = NamedSharding(mesh, P())
    return jax.jit(
        append_run_slots_rle_sparse.__wrapped__,
        in_shardings=(st_shard, replicated, replicated, replicated, slot_shard),
        out_shardings=(st_shard, count_sharding),
        donate_argnums=(0,),
    )


def make_sharded_compact_step(mesh: Mesh):
    """Jitted multi-chip compact (tombstone-GC) step: the (B,) slot
    routing vector replicates like the sparse op batches, the
    doc-sharded arenas stay in place, and XLA partitions the
    gather/compact/scatter so only the shards owning routed rows do
    work (residency compaction touches a handful of rows at a time)."""
    from .kernels import compact_doc_rows

    st_shard = state_sharding(mesh)
    _, slot_shard = sparse_ops_sharding(mesh)
    lengths_sharding = NamedSharding(mesh, P(None))
    return jax.jit(
        compact_doc_rows.__wrapped__,
        in_shardings=(st_shard, slot_shard),
        out_shardings=(st_shard, lengths_sharding),
        donate_argnums=(0,),
    )


def make_sharded_rle_compact_step(mesh: Mesh):
    """RLE twin of make_sharded_compact_step (defragmentation)."""
    from .kernels_rle import compact_doc_rows_rle

    st_shard = rle_state_sharding(mesh)
    _, slot_shard = sparse_ops_sharding(mesh)
    counts_sharding = NamedSharding(mesh, P(None))
    return jax.jit(
        compact_doc_rows_rle.__wrapped__,
        in_shardings=(st_shard, slot_shard),
        out_shardings=(st_shard, counts_sharding),
        donate_argnums=(0,),
    )


def make_sharded_rle_sparse_step(mesh: Mesh):
    """RLE twin of make_sharded_sparse_step."""
    from .kernels_rle import integrate_op_slots_rle_sparse

    st_shard = rle_state_sharding(mesh)
    op_shard, slot_shard = sparse_ops_sharding(mesh)
    count_sharding = NamedSharding(mesh, P())
    return jax.jit(
        integrate_op_slots_rle_sparse.__wrapped__,
        in_shardings=(st_shard, op_shard, slot_shard),
        out_shardings=(st_shard, count_sharding),
        donate_argnums=(0,),
    )


def make_sharded_state(mesh: Mesh, num_docs: int, capacity: int) -> DocState:
    state = make_empty_state(num_docs, capacity)
    shardings = state_sharding(mesh)
    return DocState(
        *(jax.device_put(field, sharding) for field, sharding in zip(state, shardings))
    )


# -- run-length arena ---------------------------------------------------------


def rle_state_sharding(mesh: Mesh):
    """NamedShardings for each RleState field: entry axis rides the
    mesh's 'unit' axis (the sequence-parallel dimension), doc axis is
    data-parallel — same layout discipline as the unit arena."""
    from .kernels_rle import RleState

    arena = NamedSharding(mesh, P("doc", "unit"))
    per_doc = NamedSharding(mesh, P("doc"))
    return RleState(
        run_client=arena,
        run_clock=arena,
        run_len=arena,
        run_rank=arena,
        run_orank=arena,
        run_deleted=arena,
        num_runs=per_doc,
        total_units=per_doc,
        overflow=per_doc,
    )


def make_sharded_rle_state(mesh: Mesh, num_docs: int, entries: int):
    from .kernels_rle import make_empty_rle_state

    state = make_empty_rle_state(num_docs, entries)
    shardings = rle_state_sharding(mesh)
    return type(state)(
        *(jax.device_put(field, sharding) for field, sharding in zip(state, shardings))
    )


def make_sharded_rle_step(mesh: Mesh, use_pallas: Optional[bool] = None, interpret: bool = False):
    """Jitted multi-chip RLE integrate step; same two lowering
    strategies as make_sharded_step (XLA scan with shardings, or
    shard_map(Pallas) over a doc-only mesh)."""
    from .kernels_rle import RleState, integrate_op_slots_rle

    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and mesh.shape["unit"] == 1
    if use_pallas and mesh.shape["unit"] != 1:
        raise ValueError("the Pallas sharded RLE step requires a doc-only mesh")

    if not use_pallas:
        st_shard = rle_state_sharding(mesh)
        op_shard = ops_sharding(mesh)
        count_sharding = NamedSharding(mesh, P())
        return jax.jit(
            integrate_op_slots_rle.__wrapped__,
            in_shardings=(st_shard, op_shard),
            out_shardings=(st_shard, count_sharding),
            donate_argnums=(0,),
        )

    from .pallas_kernels_rle import integrate_op_slots_rle_pallas

    arena = P("doc", None)
    per_doc = P("doc")
    st_spec = RleState(*([arena] * 6 + [per_doc] * 3))
    ops_spec = OpBatch(*([P(None, "doc")] * 8))

    def local_step(state, ops):
        new_state, count = integrate_op_slots_rle_pallas(state, ops, interpret=interpret)
        return new_state, jax.lax.psum(count, "doc")

    return jax.jit(
        jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(st_spec, ops_spec),
            out_specs=(st_spec, P()),
            check_vma=False,
        ),
        donate_argnums=(0,),
    )
