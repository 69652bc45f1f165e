"""TPU merge plane correctness: device kernel vs CPU CRDT reference.

Runs on the virtual CPU backend (conftest forces JAX_PLATFORMS=cpu with
8 devices); the same code paths run on a real TPU in chip_smoke.py
and the benchmark (bench/).
"""

import random

import numpy as np

from hocuspocus_tpu.crdt import Doc, encode_state_as_update
from hocuspocus_tpu.tpu.kernels import make_empty_state
from hocuspocus_tpu.tpu.merge_plane import MergePlane


def mirror_doc_updates(plane: MergePlane, name: str, doc: Doc):
    """Wire a CPU doc's update events into the plane (as the extension does)."""
    plane.register(name)
    doc.on("update", lambda update, *rest: plane.enqueue_update(name, update))


def test_single_doc_insert_matches_cpu():
    plane = MergePlane(num_docs=4, capacity=256)
    doc = Doc()
    mirror_doc_updates(plane, "d", doc)
    text = doc.get_text("t")
    text.insert(0, "hello")
    text.insert(5, " world")
    text.insert(5, ",")
    plane.flush()
    assert plane.text("d") == text.to_string() == "hello, world"


def test_delete_matches_cpu():
    plane = MergePlane(num_docs=4, capacity=256)
    doc = Doc()
    mirror_doc_updates(plane, "d", doc)
    text = doc.get_text("t")
    text.insert(0, "hello world")
    text.delete(2, 5)
    plane.flush()
    assert plane.text("d") == text.to_string()


def test_concurrent_edits_converge_on_device():
    """Two CPU docs edit concurrently; device mirrors the merged doc."""
    plane = MergePlane(num_docs=4, capacity=512)
    a, b = Doc(), Doc()
    from hocuspocus_tpu.crdt import apply_update

    a.get_text("t").insert(0, "base")
    apply_update(b, encode_state_as_update(a))
    # concurrent same-position inserts (conflict resolution on device)
    a.get_text("t").insert(4, "-AA")
    b.get_text("t").insert(4, "-BB")
    merged = Doc()
    mirror_doc_updates(plane, "d", merged)
    apply_update(merged, encode_state_as_update(a))
    apply_update(merged, encode_state_as_update(b))
    plane.flush()
    assert plane.text("d") == merged.get_text("t").to_string()


def test_many_docs_batched():
    plane = MergePlane(num_docs=16, capacity=256)
    docs = {}
    for i in range(10):
        doc = Doc()
        name = f"doc-{i}"
        mirror_doc_updates(plane, name, doc)
        docs[name] = doc
        doc.get_text("t").insert(0, f"content {i}")
    plane.flush()
    for name, doc in docs.items():
        assert plane.text(name) == doc.get_text("t").to_string()


def test_fuzz_random_edits_match_cpu():
    random.seed(7)
    plane = MergePlane(num_docs=4, capacity=2048)
    doc = Doc()
    mirror_doc_updates(plane, "d", doc)
    text = doc.get_text("t")
    alphabet = "abcdefghij😀é"
    for _ in range(120):
        if random.random() < 0.7 or len(text) == 0:
            pos = random.randint(0, len(text))
            text.insert(pos, random.choice(alphabet) * random.randint(1, 20))
        else:
            pos = random.randrange(len(text))
            text.delete(pos, min(random.randint(1, 8), len(text) - pos))
        if random.random() < 0.2:
            plane.flush()
    plane.flush()
    assert plane.text("d") == text.to_string()


def test_fuzz_concurrent_multi_client_matches_cpu():
    random.seed(13)
    from hocuspocus_tpu.crdt import apply_update

    docs = [Doc() for _ in range(3)]
    queues = {i: [] for i in range(3)}
    for i, d in enumerate(docs):
        d.on(
            "update",
            lambda update, origin, dd, tr, i=i: [
                queues[j].append(update) for j in range(3) if j != i
            ],
        )
    merged = Doc()
    plane = MergePlane(num_docs=2, capacity=4096)
    mirror_doc_updates(plane, "d", merged)
    for _ in range(150):
        i = random.randrange(3)
        t = docs[i].get_text("t")
        if random.random() < 0.75 or len(t) == 0:
            t.insert(random.randint(0, len(t)), random.choice("xyz") * random.randint(1, 4))
        else:
            pos = random.randrange(len(t))
            t.delete(pos, min(random.randint(1, 3), len(t) - pos))
        if random.random() < 0.4:
            j = random.randrange(3)
            while queues[j]:
                apply_update(docs[j], queues[j].pop(0))
    for j in range(3):
        while queues[j]:
            apply_update(docs[j], queues[j].pop(0))
    # everyone converged on CPU
    assert len({d.get_text("t").to_string() for d in docs}) == 1
    apply_update(merged, encode_state_as_update(docs[0]))
    plane.flush()
    assert plane.text("d") == docs[0].get_text("t").to_string()


def test_map_content_stays_on_plane():
    """Map entries are host-side LWW records — they no longer retire the
    doc (round-2 verdict item: BASELINE config-4 shapes on the plane)."""
    plane = MergePlane(num_docs=4, capacity=256)
    doc = Doc()
    mirror_doc_updates(plane, "d", doc)
    doc.get_map("m").set("k", 1)
    plane.flush()
    assert plane.is_supported("d")
    assert plane.counters["docs_retired_unsupported"] == 0
    # map items land in the serve log, not the device queue
    rec = plane.docs["d"].serve_log[-1]
    assert rec.slot is None and rec.op.parent_sub == "k"


def test_gc_structs_stay_on_plane():
    """GC structs (collected subtrees) are pure clock ranges: recorded
    host-side and re-encoded verbatim — the doc stays plane-served
    (reloaded ProseMirror docs with deleted paragraphs hit this)."""
    from hocuspocus_tpu.crdt.encoding import Encoder

    enc = Encoder()
    enc.write_var_uint(1)  # sections
    enc.write_var_uint(1)  # structs
    enc.write_var_uint(9)  # client
    enc.write_var_uint(0)  # clock
    enc.write_uint8(0x00)  # GC ref
    enc.write_var_uint(3)  # gc length
    enc.write_var_uint(0)  # ds clients
    plane = MergePlane(num_docs=4, capacity=256)
    plane.register("d")
    assert plane.enqueue_update("d", enc.to_bytes()) == 1
    assert plane.is_supported("d")
    assert plane.docs["d"].lowerer.known == {9: 3}
    rec = plane.docs["d"].serve_log[-1]
    assert rec.op.gc and rec.op.run_len == 3


def test_skip_content_falls_back():
    """Skip structs (partial-update placeholders) stay host-only."""
    from hocuspocus_tpu.crdt.encoding import Encoder

    enc = Encoder()
    enc.write_var_uint(1)  # sections
    enc.write_var_uint(1)  # structs
    enc.write_var_uint(9)  # client
    enc.write_var_uint(0)  # clock
    enc.write_uint8(0x0A)  # Skip ref
    enc.write_var_uint(3)  # skip length
    enc.write_var_uint(0)  # ds clients
    plane = MergePlane(num_docs=4, capacity=256)
    plane.register("d")
    plane.enqueue_update("d", enc.to_bytes())
    assert not plane.is_supported("d")
    assert plane.counters["docs_retired_unsupported"] == 1
    assert plane.text("d") is None


def test_slot_release_and_reuse():
    plane = MergePlane(num_docs=2, capacity=64)
    doc = Doc()
    mirror_doc_updates(plane, "a", doc)
    doc.get_text("t").insert(0, "aaa")
    plane.flush()
    assert plane.text("a") == "aaa"
    plane.release("a")
    doc2 = Doc()
    mirror_doc_updates(plane, "b", doc2)
    doc2.get_text("t").insert(0, "bbb")
    plane.flush()
    assert plane.text("b") == "bbb"


def test_sharded_step_multichip():
    """Full merge step jitted over a (doc, unit) mesh on 8 virtual devices."""
    import jax

    from hocuspocus_tpu.tpu.sharding import (
        make_mesh,
        make_sharded_state,
        make_sharded_step,
        ops_sharding,
    )
    from hocuspocus_tpu.tpu.kernels import OpBatch

    n = len(jax.devices())
    assert n == 8, f"expected 8 virtual devices, got {n}"
    mesh = make_mesh(doc_axis=4)  # 4-way doc parallel × 2-way unit parallel
    state = make_sharded_state(mesh, num_docs=8, capacity=64)
    step = make_sharded_step(mesh)

    import jax.numpy as jnp
    import numpy as np

    d, k = 8, 2
    from hocuspocus_tpu.tpu.kernels import NONE_CLIENT

    kind = np.zeros((k, d), np.int32)
    client = np.zeros((k, d), np.uint32)
    clock = np.zeros((k, d), np.int32)
    run_len = np.zeros((k, d), np.int32)
    left_client = np.full((k, d), NONE_CLIENT, np.uint32)
    left_clock = np.zeros((k, d), np.int32)
    right_client = np.full((k, d), NONE_CLIENT, np.uint32)
    right_clock = np.zeros((k, d), np.int32)
    for doc_i in range(d):
        kind[0, doc_i] = 1  # insert
        client[0, doc_i] = 42
        run_len[0, doc_i] = 3
        kind[1, doc_i] = 2  # delete one unit
        client[1, doc_i] = 42
        clock[1, doc_i] = 1
        run_len[1, doc_i] = 1
    ops = OpBatch(
        kind=jnp.asarray(kind),
        client=jnp.asarray(client),
        clock=jnp.asarray(clock),
        run_len=jnp.asarray(run_len),
        left_client=jnp.asarray(left_client),
        left_clock=jnp.asarray(left_clock),
        right_client=jnp.asarray(right_client),
        right_clock=jnp.asarray(right_clock),
    )
    op_shards = ops_sharding(mesh)
    ops = OpBatch(*(jax.device_put(f, s) for f, s in zip(ops, op_shards)))
    new_state, count = step(state, ops)
    assert int(count) == 2 * d
    lengths = np.asarray(new_state.length)
    assert (lengths == 3).all()
    deleted = np.asarray(new_state.deleted)
    assert deleted[:, 1].all() and not deleted[:, 0].any()


def test_overflow_stops_queueing_and_logging():
    """Once a doc can't fit the arena, the plane stops retaining payloads."""
    plane = MergePlane(num_docs=2, capacity=32)
    doc = Doc()
    mirror_doc_updates(plane, "d", doc)
    text = doc.get_text("t")
    text.insert(0, "x" * 16)
    plane.flush()
    assert plane.text("d") == text.to_string()
    (slot,) = plane.docs["d"].seqs.values()
    text.insert(0, "y" * 64)  # exceeds capacity
    assert not plane.is_supported("d")
    assert plane.queues[slot] == []
    log_len = len(plane.unit_logs[slot])
    text.insert(0, "z" * 100)  # further edits must not grow host state
    assert len(plane.unit_logs[slot]) == log_len
    assert plane.queues[slot] == []
    plane.flush()
    assert plane.text("d") is None


def test_overlapping_snapshot_emits_tail():
    """A re-enqueued snapshot whose merged items span the known boundary
    must contribute exactly the unseen tail units (yjs offset splice)."""
    d = Doc()
    t = d.get_text("t")
    t.insert(0, "abc")
    u1 = encode_state_as_update(d)
    t.insert(3, "def")
    full = encode_state_as_update(d)  # items may merge into one struct
    plane = MergePlane(num_docs=2, capacity=64)
    plane.register("d")
    plane.enqueue_update("d", u1)
    plane.flush()
    assert plane.text("d") == "abc"
    plane.enqueue_update("d", full)
    plane.flush()
    assert plane.text("d") == "abcdef"
    # and a pure duplicate is a no-op
    plane.enqueue_update("d", full)
    plane.flush()
    assert plane.text("d") == "abcdef"


def test_partial_delete_range_applies_known_prefix():
    """A delete set covering a partially-known range must tombstone the
    known prefix immediately (CPU _read_and_apply_delete_set parity) —
    deferring the whole range would let a sync serve omit deletions the
    CPU document already applied."""
    from hocuspocus_tpu.crdt.encoding import Encoder
    from hocuspocus_tpu.tpu.kernels import KIND_DELETE
    from hocuspocus_tpu.tpu.lowering import DocLowerer

    # hand-built update: client 9 structs "abc" (clocks 0-2), plus a
    # delete set claiming (client 9, clock 0, len 5) — clocks 3-4 unknown
    enc = Encoder()
    enc.write_var_uint(1)  # sections
    enc.write_var_uint(1)  # structs
    enc.write_var_uint(9)  # client
    enc.write_var_uint(0)  # clock
    enc.write_uint8(0x04)  # ContentString, no origins
    enc.write_var_uint(1)  # parent isYKey
    enc.write_var_string("t")
    enc.write_var_string("abc")
    enc.write_var_uint(1)  # ds clients
    enc.write_var_uint(9)
    enc.write_var_uint(1)  # ranges
    enc.write_var_uint(0)  # clock
    enc.write_var_uint(5)  # len
    lowerer = DocLowerer()
    seq_ops, _, _ = lowerer.lower_update(enc.to_bytes())
    ops = [op for ops in seq_ops.values() for op in ops]
    deletes = [op for op in ops if op.kind == KIND_DELETE]
    assert [(d.clock, d.run_len) for d in deletes] == [(0, 3)]
    assert lowerer.pending_deletes == [(9, 3, 2)]

    # once clocks 3-4 arrive, the remainder of the range applies
    enc2 = Encoder()
    enc2.write_var_uint(1)
    enc2.write_var_uint(1)
    enc2.write_var_uint(9)
    enc2.write_var_uint(3)
    enc2.write_uint8(0x84)  # origin present
    enc2.write_var_uint(9)
    enc2.write_var_uint(2)
    enc2.write_var_string("de")
    enc2.write_var_uint(0)  # empty ds
    seq_ops2, _, _ = lowerer.lower_update(enc2.to_bytes())
    ops2 = [op for ops in seq_ops2.values() for op in ops]
    deletes2 = [op for op in ops2 if op.kind == KIND_DELETE]
    assert [(d.clock, d.run_len) for d in deletes2] == [(3, 2)]
    assert lowerer.pending_deletes == []


def test_broadcast_delete_sets_are_window_sized():
    """Broadcast ds carries the WINDOW's delete ranges only: with N
    delete rounds, broadcast sizes must stay bounded instead of growing
    with the doc's full tombstone history (previously every broadcast
    containing a delete shipped the complete device delete set)."""
    from hocuspocus_tpu.crdt import apply_update
    from hocuspocus_tpu.tpu.serving import PlaneServing

    plane = MergePlane(num_docs=4, capacity=4096)
    serving = PlaneServing(plane)
    doc = Doc()
    mirror_doc_updates(plane, "d", doc)
    text = doc.get_text("t")
    text.insert(0, "x" * 1024)
    plane.flush()
    serving.refresh()
    assert serving.build_broadcast("d")  # drain the seed window

    sizes = []
    peer = Doc()
    apply_update(peer, encode_state_as_update(doc))
    for round_no in range(30):
        text.delete(0, 4)  # steadily accumulate tombstones
        plane.flush()
        serving.refresh()
        payload = serving.build_broadcast("d")
        assert payload is not None
        apply_update(peer, payload)
        sizes.append(len(payload))
        assert peer.get_text("t").to_string() == text.to_string(), round_no
    # each round deletes the same amount; payloads must not trend up
    # with tombstone history (allow codec jitter from varint widths)
    assert max(sizes) <= min(sizes) + 8, sizes
