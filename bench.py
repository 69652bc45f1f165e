"""Benchmark: CRDT update merges/sec on the TPU merge plane.

Drives the batched integrate kernel with a synthetic random-position
insert/delete stream (BASELINE.md config 2 shape) across thousands of
documents and reports sustained struct integrations ("merges") per
second on the real chip, followed by the side passes below.

The op stream is generated on-device (jax.random inside jit): in the
live server the host lowers client updates and stages them
asynchronously while the previous step runs; generating on device keeps
the benchmark measuring integrate throughput rather than the test
harness's host->device link.

Measures IN PLACE, in this process (the one that holds the chip):

    python bench.py                          # headline + side passes
    BENCH_DOCS=100000 BENCH_STEPS=8 python bench.py   # the 100k-doc scale point
    BENCH_MODE=sharded100k python bench.py   # 13 x 8,192 shard planes
    BENCH_MODE=served100k python bench.py    # 100k docs served through loadgen

Exits non-zero when JAX finds no TPU (nothing is measured, nothing is
re-cited) or when any pass raises; each side pass can be skipped with
its BENCH_<PASS>=0 variable. Prints ONE JSON line: {"metric", "value",
"unit", "vs_baseline"} where vs_baseline is value / 1e6 (the
BASELINE.json north-star target of 1M merges/sec). The replacement —
one benchmark with a `workloads` table — is ROADMAP S0/D1.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _measure_served_scale() -> dict:
    """BENCH_MODE=served100k inner: loadgen harness at the 100k-doc
    served population, 2 instances through mini-Redis (config4 topology
    at BASELINE scale)."""
    import asyncio

    from hocuspocus_tpu.loadgen import run_served_load

    docs = int(os.environ.get("BENCH_SERVED_DOCS", 100_000))
    return asyncio.run(
        run_served_load(
            num_docs=docs,
            instances=int(os.environ.get("BENCH_SERVED_INSTANCES", 2)),
            sampled=int(os.environ.get("BENCH_SERVED_SAMPLED", 48)),
            edits=int(os.environ.get("BENCH_SERVED_EDITS", 150)),
            shards=int(os.environ.get("BENCH_SERVED_SHARDS", 13)),
            capacity=int(os.environ.get("BENCH_SERVED_CAPACITY", 1024)),
            docs_per_socket=1024,
            sync_timeout=float(os.environ.get("BENCH_SERVED_SYNC_TIMEOUT", 700)),
            budget_s=float(os.environ.get("BENCH_SERVED_BUDGET", 1100)),
            progress=_log,
        )
    )


MAX_RUN = 16  # UTF-16 units per synthetic insert op (typing-burst sized)


def _make_op_builder(num_docs: int):
    """Jitted random-position insert/delete stream builder, entirely on
    device (see run_bench docstring for why generation stays on-chip).
    Returns build_ops(key, next_clock, slots) -> (next_clock, ops)."""
    from functools import partial as _partial

    import jax
    import jax.numpy as jnp

    from hocuspocus_tpu.tpu.kernels import NONE_CLIENT, OpBatch

    client_id = jnp.uint32(7)

    @_partial(jax.jit, static_argnums=(2,))
    def build_ops(key, next_clock, slots):
        def one_slot(carry, slot_key):
            next_clock = carry
            k_del, k_ori, k_len = jax.random.split(slot_key, 3)
            deletes = (jax.random.uniform(k_del, (num_docs,)) < 0.15) & (
                next_clock > MAX_RUN
            )
            origin = jax.random.randint(
                k_ori, (num_docs,), 0, jnp.maximum(next_clock, 1)
            ).astype(jnp.int32)
            del_clock = jax.random.randint(
                k_len, (num_docs,), 0, jnp.maximum(next_clock - MAX_RUN, 1)
            ).astype(jnp.int32)
            op = OpBatch(
                kind=jnp.where(deletes, 2, 1).astype(jnp.int32),
                client=jnp.full((num_docs,), client_id, jnp.uint32),
                clock=jnp.where(deletes, del_clock, next_clock),
                run_len=jnp.where(
                    deletes, 1 + del_clock % (MAX_RUN - 1), MAX_RUN
                ).astype(jnp.int32),
                left_client=jnp.where(
                    next_clock > 0, client_id, jnp.uint32(NONE_CLIENT)
                ),
                left_clock=jnp.maximum(origin - 1, 0),
                right_client=jnp.full((num_docs,), NONE_CLIENT, jnp.uint32),
                right_clock=jnp.zeros((num_docs,), jnp.int32),
            )
            next_clock = jnp.where(deletes, next_clock, next_clock + MAX_RUN)
            return next_clock, op

        keys = jax.random.split(key, slots)
        next_clock, ops = jax.lax.scan(one_slot, next_clock, keys)
        return next_clock, ops

    return build_ops


def run_bench() -> None:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        # a CPU figure is not this system's performance, and an older
        # capture is not this tree's: fail, measure nothing
        print(
            f"bench: no TPU — JAX found platform {device.platform!r} "
            f"({device.device_kind}); nothing measured",
            file=sys.stderr,
        )
        sys.exit(3)
    if os.environ.get("BENCH_MODE") == "sharded100k":
        print(json.dumps(_measure_sharded_scale()))
        return
    if os.environ.get("BENCH_MODE") == "served100k":
        print(json.dumps(_measure_served_scale()))
        return
    import jax.numpy as jnp

    from hocuspocus_tpu.tpu.kernels import (
        NONE_CLIENT,
        OpBatch,
        make_empty_state,
    )
    from hocuspocus_tpu.tpu.pallas_kernels import integrate_op_slots_fast

    # defaults size the BASELINE 10KB-doc regime: capacity 5632 holds a
    # 5,120-unit (10,240-byte UTF-16) document with headroom. HBM model:
    # ~17 B/unit (4+4+4+4+1) -> 8192 docs x 5632 x 17 B = 0.78 GB;
    # the 100k-doc pass (below) = 9.6 GB, inside a v5e chip's 16 GB.
    num_docs = int(os.environ.get("BENCH_DOCS", 8192))
    capacity = int(os.environ.get("BENCH_CAPACITY", 5632))
    k = int(os.environ.get("BENCH_SLOTS", 64))
    steps = int(os.environ.get("BENCH_STEPS", 20))

    build_ops = _make_op_builder(num_docs)

    def sync(st):
        """Content readback of the per-doc lengths (32KB): a completion
        barrier by data dependence, mirroring the serving flow, where
        the host reads lengths/overflow back after every flush anyway.
        """
        return int(np.asarray(st.length).sum())

    # stage logs (stderr): a hung device call must be localizable
    _log(f"inner: start docs={num_docs} capacity={capacity} backend={jax.default_backend()}")
    key = jax.random.PRNGKey(0)
    state = make_empty_state(num_docs, capacity)
    next_clock = jnp.zeros((num_docs,), jnp.int32)

    # seed phase: fill docs to ~25% capacity so origin searches touch
    # realistic arena occupancy (10KB-doc regime)
    seed_slots = max(capacity // 4 // MAX_RUN, 1)
    key, sub = jax.random.split(key)
    next_clock, seed_ops = build_ops(sub, next_clock, seed_slots)
    _log("inner: seed phase (first compile) ...")
    state, seed_count = integrate_op_slots_fast(state, seed_ops)
    sync(state)
    _log("inner: seed done")

    # warmup/compile at the timed shape
    key, sub = jax.random.split(key)
    next_clock, ops = build_ops(sub, next_clock, k)
    state, count = integrate_op_slots_fast(state, ops)
    sync(state)
    _log("inner: warmup compiled; timed loop ...")

    op_batches = []
    for _ in range(steps):
        key, sub = jax.random.split(key)
        next_clock, ops = build_ops(sub, next_clock, k)
        op_batches.append(ops)
    jax.block_until_ready(op_batches)

    start = time.perf_counter()
    counts = []
    for ops in op_batches:
        state, count = integrate_op_slots_fast(state, ops)
        counts.append(count)
    sync(state)
    elapsed = time.perf_counter() - start
    total_ops = int(sum(int(c) for c in counts))
    _log(f"inner: timed loop done ({total_ops} ops in {elapsed:.2f}s); latency probes ...")

    # latency: individually timed 8-slot micro-batches, each synced to
    # host-visible results (= merge-to-broadcast readiness)
    key, sub = jax.random.split(key)
    next_clock, ops = build_ops(sub, next_clock, 8)
    state, count = integrate_op_slots_fast(state, ops)
    sync(state)  # warm the 8-slot compile
    latencies = []
    for _ in range(20):
        key, sub = jax.random.split(key)
        next_clock, ops = build_ops(sub, next_clock, 8)
        jax.block_until_ready(ops)
        t0 = time.perf_counter()
        state, count = integrate_op_slots_fast(state, ops)
        sync(state)
        latencies.append(time.perf_counter() - t0)

    # A side pass that raises fails the run: an error must never turn
    # into an `extra` key beside a headline that looks healthy.

    def side_pass(flag: str, label: str, measure):
        if os.environ.get(flag, "1") == "0":
            return None
        _log(f"inner: {label} pass ...")
        return measure()

    # end-to-end merge-to-broadcast p99 THROUGH THE SERVER: real ws
    # providers, plane serving path (device flush + merged broadcast) —
    # the BASELINE metric is end-to-end, not kernel-microbatch
    server_p99_ms, server_p99_extra = side_pass(
        "BENCH_SERVER_P99", "server p99", _measure_server_p99
    ) or (None, None)
    # catch-up storm serving rate (BASELINE config 5's plane replay):
    # cold/stale SyncStep2s served from plane state + host logs
    catchup = side_pass("BENCH_CATCHUP", "catch-up serving", _measure_catchup_serving)
    # run-length arena microbatch at the same population
    rle = side_pass(
        "BENCH_RLE", "RLE microbatch", lambda: _measure_rle_microbatch(num_docs)
    )
    # sparse-load flush engine pass (D docs resident, ~1% busy): the
    # per-flush host build / upload / device breakdown must scale with
    # BUSY docs, not the resident population
    sparse = side_pass("BENCH_SPARSE", "sparse-load flush", _measure_sparse_load)
    # catch-up storm admission (config 5 miniature): cold snapshots
    # burst into the residency hydration queue + SV-diff tail replay
    storm = side_pass("BENCH_CATCHUP_STORM", "catch-up storm", _measure_catchup_storm)
    # wire-path load (socket edge): msgs/s, bytes in/out, send-queue
    # peak and ingress-stage quantiles through the full provider pipe
    wire_load = side_pass("BENCH_WIRE", "wire-load", _measure_wire_load)
    # wire-saturation + headroom-model closure (observability/costs.py):
    # direct-drive ingress ramp to the loop thread's measured wall, with
    # the per-frame cost ledger on
    wire_saturation = side_pass(
        "BENCH_WIRE_SATURATION", "wire-saturation", _measure_wire_saturation
    )
    # broadcast fan-out storm (server/fanout.py): frames saved by
    # per-tick coalescing, catch-up tiering, join-storm cache hit rate
    fanout = side_pass("BENCH_FANOUT", "fanout-storm", _measure_fanout_storm)
    # durability plane (storage/wal.py): WAL group-commit overhead on
    # the broadcast path (on vs off), append->durable p50/p99, fsync
    # batch amortization and the 10k-update recovery replay time
    wal_load = side_pass("BENCH_WAL", "wal-load", _measure_wal_load)
    # cross-instance replication storm (net/resp.py pipelined lane +
    # extensions/redis.py per-tick coalescing)
    replica = side_pass("BENCH_REPLICA", "replica-storm", _measure_replica_storm)
    # adaptive merge scheduling (tpu/scheduler.py): interactive
    # merge->broadcast latency under concurrent hydration storm +
    # proactive compaction, device-lane arbiter + governor ON vs OFF
    mixed = side_pass("BENCH_MIXED", "mixed-load scheduling", _measure_mixed_load)
    # scenario traffic suite (hocuspocus_tpu/loadgen): named production
    # mixes judged by SloEngine multi-window burn rates — the pass/fail
    # signal tools/bench_gate.py gates on (extra.scenario_suite.verdict)
    scenario_suite = side_pass("BENCH_SCENARIO", "scenario-suite", _measure_scenario_suite)
    _log("inner: all passes done")

    merges_per_sec = total_ops / elapsed
    p99_ms = float(np.percentile(np.array(latencies) * 1000, 99))
    from hocuspocus_tpu.tpu.pallas_kernels import _pick_block

    result = {
        "metric": "crdt_update_merges_per_sec",
        "value": round(merges_per_sec, 1),
        "unit": "merges/s",
        "vs_baseline": round(merges_per_sec / 1_000_000, 3),
        "extra": {
            "docs": num_docs,
            "capacity": capacity,
            "op_slots": k,
            "steps": steps,
            "total_merges": total_ops,
            "p99_microbatch_ms": round(p99_ms, 2),
            "backend": jax.default_backend(),
            "device": str(device),
            "platform": device.platform,
            "device_kind": device.device_kind,
            "device_count": len(jax.devices()),
            # kernel-path diagnosis: the doc block the Pallas sweep used
            "pallas_block": _pick_block(num_docs, capacity),
        },
    }
    if server_p99_ms is not None:
        result["extra"]["server_merge_to_broadcast_p99_ms"] = round(server_p99_ms, 2)
    if server_p99_extra is not None:
        result["extra"]["server_p99_detail"] = server_p99_extra
    if catchup is not None:
        result["extra"]["catchup"] = catchup
    if rle is not None:
        result["extra"]["rle"] = rle
    if sparse is not None:
        # hoist the stage-latency trajectory to its own extra key (the
        # per-stage p50/p99 from the e2e lifecycle histograms)
        if isinstance(sparse, dict) and sparse.get("update_e2e"):
            result["extra"]["update_e2e"] = sparse.pop("update_e2e")
        result["extra"]["sparse_load"] = sparse
    if storm is not None:
        result["extra"]["catchup_storm"] = storm
    if wire_load is not None:
        result["extra"]["wire_load"] = wire_load
    if wire_saturation is not None:
        result["extra"]["wire_saturation"] = wire_saturation
    if wal_load is not None:
        result["extra"]["wal_load"] = wal_load
    if fanout is not None:
        result["extra"]["fanout_storm"] = fanout
    if replica is not None:
        result["extra"]["replica_storm"] = replica
    if mixed is not None:
        result["extra"]["mixed_load"] = mixed
    if scenario_suite is not None:
        result["extra"]["scenario_suite"] = scenario_suite
    print(json.dumps(result))


def _measure_scenario_suite() -> dict:
    """Scenario traffic simulator suite (docs/guides/load-testing.md):
    each named production mix compiles to a seeded, hash-stamped
    schedule and runs through the real-server loadgen path; the
    per-scenario verdict is the SLO engine's multi-window burn-rate
    breach status. The suite verdict is the field tools/bench_gate.py
    gates on — a failing scenario fails the round even when every raw
    p99 stayed inside tolerance."""
    import asyncio

    from hocuspocus_tpu.loadgen import ScenarioRunner, get_scenario
    from hocuspocus_tpu.loadgen.scenarios import BENCH_SUITE

    names = [
        name
        for name in os.environ.get(
            "BENCH_SCENARIOS", ",".join(BENCH_SUITE)
        ).split(",")
        if name
    ]
    seed = int(os.environ.get("BENCH_SCENARIO_SEED", 0))
    time_scale = float(os.environ.get("BENCH_SCENARIO_TIMESCALE", 2.0))
    suite: dict = {"seed": seed, "time_scale": time_scale, "scenarios": {}}
    verdict = "pass"
    for name in names:
        try:
            schedule = get_scenario(name).compile(seed)
            runner = ScenarioRunner(
                schedule,
                time_scale=time_scale,
                progress=lambda msg, n=name: _log(f"scenario {n}: {msg}"),
            )
            result = asyncio.run(runner.run())
            suite["scenarios"][name] = {
                "verdict": result["verdict"],
                "schedule_hash": result["schedule_hash"],
                "breached": result["slo"]["breached_targets"],
                "phase_p99_ms": {
                    phase["name"]: phase["latency_p99_ms"]
                    for phase in result["phases"]
                },
                "ops_measured": result["extra"]["ops_measured"],
                "ops_failed": result["extra"]["ops_failed"],
            }
            wire_sat = result["extra"].get("wire_saturation")
            if wire_sat is not None:
                # headroom evidence (wire_saturation scenario): per-rung
                # offered vs achieved frames/s + the cost attribution
                suite["scenarios"][name]["wire_saturation"] = wire_sat
            fleet = result["extra"].get("fleet")
            if fleet is not None:
                # fleet plane evidence (edge topologies): digest counts,
                # stale peers and the cross-tier e2e quantiles the
                # bench gate's edge_fanout.cross_tier_e2e_p99 stage reads
                suite["scenarios"][name]["fleet"] = fleet
            if result["verdict"] != "pass":
                verdict = "fail"
        except Exception as error:
            suite["scenarios"][name] = {
                "verdict": "error",
                "error": repr(error)[:300],
            }
            verdict = "fail"
    suite["verdict"] = verdict
    return suite


def _measure_rle_microbatch(num_docs: int) -> dict:
    """Run-length arena microbatch p99 at the same doc population.

    The unit arena's microbatch latency is VPU-bound on per-op masked
    reductions over (docs, capacity); RLE entries are ~4-16x fewer than
    units for typing-burst workloads, shrinking the sweep accordingly —
    the on-device path to the <50 ms budget at the 10KB-doc regime."""
    import time as _time

    import jax
    import numpy as _np

    from hocuspocus_tpu.tpu.kernels_rle import make_empty_rle_state
    from hocuspocus_tpu.tpu.pallas_kernels_rle import integrate_op_slots_rle_fast

    entries = int(os.environ.get("BENCH_RLE_ENTRIES", 1024))
    build_ops = _make_op_builder(num_docs)
    state = make_empty_rle_state(num_docs, entries)
    key = jax.random.PRNGKey(3)
    import jax.numpy as jnp

    next_clock = jnp.zeros((num_docs,), jnp.int32)

    def sync(st):
        return int(_np.asarray(st.total_units).sum())

    # seed via repeated 8-slot batches (reuses the timed shape's compile)
    seed_batches = max(entries // 3 // 8, 1)
    for _ in range(seed_batches):
        key, sub = jax.random.split(key)
        next_clock, ops = build_ops(sub, next_clock, 8)
        state, _count = integrate_op_slots_rle_fast(state, ops)
    sync(state)
    lat = []
    total = 0
    for _ in range(20):
        key, sub = jax.random.split(key)
        next_clock, ops = build_ops(sub, next_clock, 8)
        jax.block_until_ready(ops)
        t0 = _time.perf_counter()
        state, count = integrate_op_slots_rle_fast(state, ops)
        sync(state)
        lat.append(_time.perf_counter() - t0)
        total += int(count)
    overflows = int(_np.asarray(state.overflow).sum())
    return {
        "docs": num_docs,
        "entries": entries,
        "p99_microbatch_ms": round(float(_np.percentile(_np.array(lat) * 1000, 99)), 2),
        "merges_per_sec": round(total / sum(lat), 1),
        "overflow_docs": overflows,
    }


def _measure_sparse_load() -> dict:
    """Flush-engine breakdown at a sparse-load shape: D docs resident,
    ~1% busy per flush window (the steady-state regime of a 100k-doc
    deployment, scaled to fit this pass's budget).

    Drives MergePlane's own flush pipeline — busy-set depth scan, drain
    into the reusable staging buffers, compact (K, B) upload with slot
    routing, sparse gather/integrate/scatter, single health readback —
    with synthetic append ops injected straight into the slot queues
    (the lowerer is bypassed on purpose: this pass measures the flush
    engine, and at 1% busy the dense layout's O(K*D) host build would
    otherwise hide in lowering noise). Reports the per-stage stats the
    plane itself records (build/upload/device ms, upload bytes, busy
    fraction) plus per-flush wall latency percentiles."""
    import time as _time

    import numpy as _np

    from hocuspocus_tpu.tpu.kernels import KIND_INSERT, NONE_CLIENT
    from hocuspocus_tpu.tpu.lowering import DenseOp
    from hocuspocus_tpu.tpu.merge_plane import MergePlane

    num_docs = int(os.environ.get("BENCH_SPARSE_DOCS", 8192))
    busy = max(int(os.environ.get("BENCH_SPARSE_BUSY", num_docs // 100)), 1)
    capacity = int(os.environ.get("BENCH_SPARSE_CAPACITY", 2048))
    cycles = int(os.environ.get("BENCH_SPARSE_CYCLES", 12))
    ops_per_doc = 4
    run = 8

    plane = MergePlane(
        num_docs=num_docs, capacity=capacity, max_slots_per_flush=ops_per_doc
    )
    rng = _np.random.default_rng(5)
    slots = []
    for d in range(num_docs):
        doc = plane.register(f"sparse-{d}")
        slots.append(plane._alloc_seq(doc, ("root", "t")))
    clocks = _np.zeros(num_docs, _np.int64)

    def enqueue_round(subset) -> int:
        count = 0
        for s in subset:
            slot = slots[s]
            queue = plane.queues[slot]
            for _ in range(ops_per_doc):
                clock = int(clocks[s])
                queue.append(
                    DenseOp(
                        kind=KIND_INSERT,
                        client=7,
                        clock=clock,
                        run_len=run,
                        left_client=7 if clock else NONE_CLIENT,
                        left_clock=clock - 1 if clock else 0,
                    )
                )
                clocks[s] += run
                count += 1
            plane.projected_len[slot] += ops_per_doc * run
            plane._busy_slots.add(slot)
        return count

    # warm the shape this pass will hit (K maxes out at ops_per_doc),
    # exactly as a live server warms at listen
    plane.warmup_compiles((plane._k_buckets()[-1], plane._bucket_b(busy)))

    lat = []
    stats = []
    total = 0
    for _ in range(cycles):
        subset = rng.choice(num_docs, size=busy, replace=False)
        total += enqueue_round(subset)
        t0 = _time.perf_counter()
        plane.flush()
        lat.append(_time.perf_counter() - t0)
        stats.append(dict(plane.flush_stats))
    lat_ms = _np.array(lat) * 1000
    # snapshot the flush-engine counters NOW: the traced pass below runs
    # extra cycles on the same plane, and the reported batch/staging
    # tallies must cover exactly the measured untraced loop
    flush_counters = {
        key: plane.counters[key]
        for key in (
            "flush_batches_sparse", "flush_batches_dense",
            "flush_staging_allocs", "flush_staging_reuses",
        )
    }

    # traced pass: the same shape with update-lifecycle tracing on,
    # feeding the per-stage e2e histograms — BENCH_*.json captures a
    # latency trajectory (extra.update_e2e), not just throughput
    from hocuspocus_tpu.observability.metrics import Histogram
    from hocuspocus_tpu.observability.tracing import Tracer

    book = plane.update_traces
    book.tracer = Tracer(enabled=True, max_spans=256)
    book.histogram = Histogram(
        "bench_update_e2e",
        "",
        buckets=(
            0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
            0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
        ),
    )
    for _ in range(max(cycles // 2, 4)):
        subset = rng.choice(num_docs, size=busy, replace=False)
        # deliberately NOT added to `total`: merges_per_sec divides
        # `total` by the untraced loop's latencies only
        enqueue_round(subset)
        for s in subset[:64]:  # bounded stamps per cycle
            plane.note_trace(f"sparse-{s}")
        plane.flush()
        book.finish_all()  # no serving here: broadcast closes immediately
    update_e2e = {}
    for stage_name in (
        "queue_wait", "build", "upload", "device", "readback", "broadcast", "total",
    ):
        count = book.histogram.series_count(stage=stage_name)
        if count:
            update_e2e[stage_name] = {
                "p50_ms": round(
                    (book.histogram.quantile(0.5, stage=stage_name) or 0.0) * 1000, 3
                ),
                "p99_ms": round(
                    (book.histogram.quantile(0.99, stage=stage_name) or 0.0) * 1000, 3
                ),
                "count": count,
            }

    def stage(key):
        return round(float(_np.mean([s[key] for s in stats])), 3)

    return {
        "docs": num_docs,
        "busy_docs": busy,
        "busy_fraction": round(busy / num_docs, 4),
        "ops_per_flush": busy * ops_per_doc,
        "merges_per_sec": round(total / max(sum(lat), 1e-9), 1),
        "p50_flush_ms": round(float(_np.percentile(lat_ms, 50)), 2),
        "p99_flush_ms": round(float(_np.percentile(lat_ms, 99)), 2),
        "host_build_ms": stage("build_ms"),
        "upload_ms": stage("upload_ms"),
        "dispatch_ms": stage("dispatch_ms"),
        "device_sync_ms": stage("device_sync_ms"),
        "upload_bytes_per_cycle": int(_np.mean([s["upload_bytes"] for s in stats])),
        # what the same cycles would have shipped under the old dense
        # (K, D) layout — the sparse win, in one ratio
        "dense_equiv_upload_bytes": plane._staging[0].nbytes(
            int(stats[-1]["batch_k"]), num_docs, False
        ),
        "batch_b": int(stats[-1]["batch_b"]),
        "batch_k": int(stats[-1]["batch_k"]),
        "sparse_batches": flush_counters["flush_batches_sparse"],
        "dense_batches": flush_counters["flush_batches_dense"],
        "staging_allocs": flush_counters["flush_staging_allocs"],
        "staging_reuses": flush_counters["flush_staging_reuses"],
        "update_e2e": update_e2e,
    }


def _measure_wire_load() -> dict:
    """Wire-path load characterization (the socket edge of the request
    path): drives loadgen's ServedLoadHarness — real providers, the
    full auth/SyncStep1/2 pipeline, served planes — with wire telemetry
    and lifecycle tracing enabled, and reports msgs/s, bytes in/out,
    send-queue peak and the ingress-stage (ws receive → decode → apply
    → capture) p50/p99 from the e2e histograms."""
    import asyncio

    from hocuspocus_tpu.loadgen import ServedLoadHarness
    from hocuspocus_tpu.observability import (
        disable_tracing,
        enable_tracing,
        get_wire_telemetry,
    )

    docs = int(os.environ.get("BENCH_WIRE_DOCS", 64))
    edits = int(os.environ.get("BENCH_WIRE_EDITS", 80))
    budget_s = int(os.environ.get("BENCH_WIRE_TIMEOUT", 240))

    wire = get_wire_telemetry()
    wire.enable()
    before = wire.totals()
    tracer = enable_tracing(max_spans=8192)
    tracer.sample = 1
    harness = ServedLoadHarness(
        num_docs=docs,
        sampled=min(16, docs),
        edits=edits,
        shards=1,
        capacity=1024,
        flush_interval_ms=2.0,
        docs_per_socket=min(64, docs),
        with_metrics=True,
    )
    started = time.perf_counter()
    try:
        served = asyncio.run(harness.run(budget_s=budget_s))
    finally:
        disable_tracing()
    elapsed = max(time.perf_counter() - started, 1e-9)
    after = wire.totals()

    hist = harness.metrics[0].update_e2e if harness.metrics else None

    def quantile_ms(stage: str, q: float):
        if hist is None or not hist.series_count(stage=stage):
            return None  # distinguish "no data" from the 0.0 sentinel
        return round(hist.quantile(q, stage=stage) * 1000, 3)

    msgs_in = after["messages_in"] - before["messages_in"]
    return {
        "docs": docs,
        "samples": served["extra"]["samples"],
        "msgs_in": int(msgs_in),
        "msgs_out": int(after["messages_out"] - before["messages_out"]),
        "msgs_per_sec": round(msgs_in / elapsed, 1),
        "bytes_in": int(after["bytes_in"] - before["bytes_in"]),
        "bytes_out": int(after["bytes_out"] - before["bytes_out"]),
        "send_queue_peak": int(after["send_queue_peak"]),
        "backpressure_events": int(
            after["backpressure_events"] - before["backpressure_events"]
        ),
        "wire_errors": int(after["errors"] - before["errors"]),
        "ingress": {
            "p50_ms": quantile_ms("ingress", 0.5),
            "p99_ms": quantile_ms("ingress", 0.99),
            "count": 0 if hist is None else hist.series_count(stage="ingress"),
        },
        "served_p99_ms": served["value"],
        "elapsed_s": round(elapsed, 1),
    }


def _native_codec_active() -> bool:
    """Whether the wire path ran the C++ codec this round (a silent
    fallback to Python invalidates throughput comparisons)."""
    try:
        from hocuspocus_tpu.native import get_codec

        return get_codec() is not None
    except Exception:
        return False


def _measure_wire_saturation() -> dict:
    """Wire-saturation + headroom-model closure (docs/guides/load-testing.md
    "profiling & cost attribution"): a direct-drive micro-harness —
    real Document, Connection and CallbackWebSocketTransport, frames
    through the full ingress decode/apply/fan-out pipeline — ramps the
    offered ingress rate rung by rung until the loop thread can no
    longer keep up (achieved < ``sat_ratio`` x offered). The per-frame
    cost ledger is on for the ramp, so the same run yields BOTH the
    measured saturation point and the headroom model's predicted
    sustainable rate — acceptance is the model landing within 2x of
    the measurement, plus a non-empty top-5 cost attribution."""
    import asyncio

    from hocuspocus_tpu.crdt import Doc
    from hocuspocus_tpu.observability.costs import get_cost_ledger
    from hocuspocus_tpu.protocol.frames import build_update_frame
    from hocuspocus_tpu.server.connection import Connection
    from hocuspocus_tpu.server.document import Document
    from hocuspocus_tpu.server.transports import CallbackWebSocketTransport

    writers = int(os.environ.get("BENCH_WIRE_SAT_WRITERS", 4))
    pool_frames = int(os.environ.get("BENCH_WIRE_SAT_POOL", 2048))
    rung_s = float(os.environ.get("BENCH_WIRE_SAT_RUNG_S", 0.4))
    start_rate = float(os.environ.get("BENCH_WIRE_SAT_START", 500.0))
    max_rate = float(os.environ.get("BENCH_WIRE_SAT_MAX", 64000.0))
    sat_ratio = float(os.environ.get("BENCH_WIRE_SAT_RATIO", 0.85))

    # pre-generate the ingress frames OUTSIDE the measured ramp: one
    # client Doc per writer, small concurrent inserts, each transaction's
    # v1 wire delta framed exactly as a provider would send it
    doc_name = "wire-sat"
    pool: "list[bytes]" = []
    for w in range(writers):
        client = Doc()
        client.on("update", lambda update, *rest: pool.append(
            build_update_frame(doc_name, update)
        ))
        text = client.get_text("t")
        for i in range(pool_frames // writers):
            text.insert(len(text) % 64, f"w{w}:{i} ")

    ledger = get_cost_ledger()
    ledger.reset()
    ledger.enable()

    async def ramp() -> "tuple[list[dict], float]":
        document = Document(doc_name)
        sends = {"count": 0}

        async def send_async(data: bytes) -> None:
            sends["count"] += 1

        async def close_async(code: int, reason: str) -> None:
            pass

        writer_transport = CallbackWebSocketTransport(send_async, close_async)
        writer = Connection(writer_transport, None, document, "w0", {})
        # one reader so every applied update pays the real fan-out
        # (coalesce + frame_encode + socket write), not just the decode
        reader_transport = CallbackWebSocketTransport(send_async, close_async)
        Connection(reader_transport, None, document, "r0", {})

        rungs = []
        sustained = 0.0
        rate = start_rate
        idx = 0
        while rate <= max_rate:
            target = max(int(rate * rung_s), 1)
            interval = 1.0 / rate
            sent = 0
            t0 = time.perf_counter()
            while sent < target:
                due = int((time.perf_counter() - t0) / interval) + 1
                while sent < min(due, target):
                    await writer.handle_message(pool[idx % len(pool)])
                    idx += 1
                    sent += 1
                if sent < target:
                    await asyncio.sleep(max(interval * 8, 0.001))
            elapsed = max(time.perf_counter() - t0, 1e-9)
            achieved = sent / elapsed
            rungs.append(
                {
                    "offered_frames_per_s": round(rate, 1),
                    "achieved_frames_per_s": round(achieved, 1),
                    "frames": sent,
                    "fanout_frames": sends["count"],
                }
            )
            sustained = max(sustained, achieved)
            if achieved < sat_ratio * rate:
                break  # the loop thread saturated: this rung is the wall
            rate *= 2
        # let the trailing fan-out ticks drain before reading the ledger
        await asyncio.sleep(0.05)
        writer_transport.abort()
        reader_transport.abort()
        return rungs, sustained

    try:
        rungs, sustained = asyncio.run(ramp())
        headroom = ledger.headroom_frames_per_s()
        top = ledger.top_costs(5)
        loop_ns = ledger.loop_ns_per_frame()
    finally:
        ledger.disable()

    ratio = round(headroom / sustained, 3) if sustained else None
    return {
        "writers": writers,
        "pool_frames": len(pool),
        "rung_s": rung_s,
        "sat_ratio": sat_ratio,
        "rungs": rungs,
        "saturated": rungs[-1]["achieved_frames_per_s"]
        < sat_ratio * rungs[-1]["offered_frames_per_s"]
        if rungs
        else False,
        # the gated headlines: measured saturation + model prediction
        # (sustained_frames_per_s is the canonical gate key; frames_per_s
        # stays for older rounds' artifacts)
        "frames_per_s": round(sustained, 1),
        "sustained_frames_per_s": round(sustained, 1),
        "codec_path": "native" if _native_codec_active() else "fallback",
        "headroom_frames_per_s": round(headroom, 1),
        "headroom_ratio": ratio,
        "headroom_within_2x": bool(ratio is not None and 0.5 <= ratio <= 2.0),
        "loop_ns_per_frame": round(loop_ns, 1),
        "ingress_frames": ledger.ingress_frames(),
        "top_costs": top,
    }


def _measure_fanout_storm() -> dict:
    """Broadcast fan-out engine under two storm shapes (all production
    code: real Documents, Connections, CallbackWebSocketTransports and
    the per-tick coalescing engine — only the network framing is
    absent):

    - hot_doc: 1 document x N connections, bursty writers — the shape
      where per-update fan-out melts the event loop. Reports the
      frames-saved ratio vs per-update fan-out (acceptance: >=2x) and
      the merge -> LAST-socket-write p99.
    - wide: M documents x few connections each — the sharded steady
      state; reports aggregate frames/s.
    - cache: a cold join storm against a served plane; reports the
      join-storm sync cache hit rate.
    """
    import asyncio

    from hocuspocus_tpu.observability.wire import get_wire_telemetry
    from hocuspocus_tpu.server.connection import Connection
    from hocuspocus_tpu.server.document import Document
    from hocuspocus_tpu.server.transports import CallbackWebSocketTransport

    hot_conns = int(os.environ.get("BENCH_FANOUT_CONNS", 512))
    wide_docs = int(os.environ.get("BENCH_FANOUT_DOCS", 256))
    wide_conns = int(os.environ.get("BENCH_FANOUT_WIDE_CONNS", 8))
    rounds = int(os.environ.get("BENCH_FANOUT_ROUNDS", 24))
    burst = int(os.environ.get("BENCH_FANOUT_BURST", 4))

    wire = get_wire_telemetry()
    wire.enable()
    before = wire.totals()

    async def storm(num_docs: int, conns_per_doc: int) -> dict:
        documents = [Document(f"storm-{i}") for i in range(num_docs)]
        writes = {"count": 0, "t_last": 0.0}
        pending = asyncio.Event()

        async def send_async(data: bytes) -> None:
            writes["count"] += 1
            writes["t_last"] = time.perf_counter()
            if writes["count"] >= writes.get("target", 1 << 62):
                pending.set()

        async def close_async(code: int, reason: str) -> None:
            pass

        transports = []
        for document in documents:
            for c in range(conns_per_doc):
                transport = CallbackWebSocketTransport(send_async, close_async)
                Connection(transport, None, document, f"s{c}", {})
                transports.append(transport)
        total_conns = num_docs * conns_per_doc
        latencies = []
        t_start = time.perf_counter()
        for _ in range(rounds):
            # bursty writers: `burst` updates per doc land in ONE tick
            writes["target"] = writes["count"] + total_conns
            pending.clear()
            t0 = time.perf_counter()
            for document in documents:
                text = document.get_text("t")
                for _ in range(burst):
                    text.insert(len(text), "x" * 24)
            await asyncio.wait_for(pending.wait(), timeout=60)
            latencies.append(writes["t_last"] - t0)
        elapsed = max(time.perf_counter() - t_start, 1e-9)
        for transport in transports:
            transport.abort()
        lat_ms = np.array(latencies) * 1000
        return {
            "docs": num_docs,
            "connections": total_conns,
            "rounds": rounds,
            "burst": burst,
            "frames_sent": writes["count"],
            "frames_per_sec": round(writes["count"] / elapsed, 1),
            "sends_baseline_per_update": rounds * burst * total_conns,
            "frames_saved_ratio": round(
                (rounds * burst * total_conns) / max(writes["count"], 1), 2
            ),
            "merge_to_last_write_p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "merge_to_last_write_p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        }

    hot = asyncio.run(storm(1, hot_conns))
    wide = asyncio.run(storm(wide_docs, wide_conns))

    # join-storm sync cache hit rate (serving path, CPU or chip alike)
    from hocuspocus_tpu.crdt import Doc, encode_state_as_update
    from hocuspocus_tpu.tpu.merge_plane import MergePlane
    from hocuspocus_tpu.tpu.serving import PlaneServing

    plane = MergePlane(num_docs=4, capacity=1024)
    serving = PlaneServing(plane)
    ref = Doc()
    ref.get_text("t").insert(0, "join-storm payload " * 8)
    plane.register("joiner")
    plane.enqueue_update("joiner", encode_state_as_update(ref))
    joiners = int(os.environ.get("BENCH_FANOUT_JOINERS", 256))
    for _ in range(joiners):
        serving.encode_state_as_update("joiner", ref, None)
    hits = plane.counters["sync_cache_hits"]
    misses = plane.counters["sync_cache_misses"]

    after = wire.totals()
    return {
        "hot_doc": hot,
        "wide": wide,
        "sends_elided_coalesce": int(
            after["sends_elided_coalesce"] - before["sends_elided_coalesce"]
        ),
        "sends_elided_catchup": int(
            after["sends_elided_catchup"] - before["sends_elided_catchup"]
        ),
        "tier_entries": int(after["tier_entries"] - before["tier_entries"]),
        "cache": {
            "joiners": joiners,
            "hits": hits,
            "misses": misses,
            "hit_rate": round(hits / max(hits + misses, 1), 4),
        },
        # the gated headline: the hot-doc shape is the pathological one
        "merge_to_last_write_p99_ms": hot["merge_to_last_write_p99_ms"],
    }


def _measure_wal_load() -> dict:
    """Durability-plane characterization (docs/guides/durability.md):

    - broadcast overhead: the sparse busy-doc shape (many docs, few
      busy per tick, real Documents/Connections/transports) measured
      merge -> LAST-socket-write with the WAL capture seam + broadcast
      gate attached (`--wal-fsync=tick` semantics) vs detached. The
      acceptance bar is <15% p99 overhead.
    - append latency: append -> group-commit-durable p50/p99 and the
      fsync amortization actually achieved (records per fsync).
    - recovery: wall time to scan + replay a 10k-update log into a
      fresh document (the restart-after-kill-9 cost).
    """
    import asyncio
    import shutil
    import tempfile

    from hocuspocus_tpu.server.connection import Connection
    from hocuspocus_tpu.server.document import Document
    from hocuspocus_tpu.server.transports import CallbackWebSocketTransport
    from hocuspocus_tpu.storage import WalManager

    num_docs = int(os.environ.get("BENCH_WAL_DOCS", 64))
    conns_per_doc = int(os.environ.get("BENCH_WAL_CONNS", 4))
    rounds = int(os.environ.get("BENCH_WAL_ROUNDS", 24))
    burst = int(os.environ.get("BENCH_WAL_BURST", 4))
    replay_updates = int(os.environ.get("BENCH_WAL_REPLAY", 10_000))

    async def storm(wal: "WalManager | None") -> dict:
        documents = [Document(f"wal-{i}") for i in range(num_docs)]
        if wal is not None:
            # warm the log exactly as a live server does at load time
            # (first append per doc pays the mkdir+open once): the
            # timed rounds measure the steady-state group commit
            for document in documents:
                wal.append(document.name, b"\x00\x00")
            await wal.flush()
            for document in documents:
                name = document.name
                document.wal_sink = (
                    lambda update, origin, n=name: wal.append(n, update)
                )
        writes = {"count": 0, "t_last": 0.0, "target": 1 << 62}
        pending = asyncio.Event()

        async def send_async(data: bytes) -> None:
            writes["count"] += 1
            writes["t_last"] = time.perf_counter()
            if writes["count"] >= writes["target"]:
                pending.set()

        async def close_async(code: int, reason: str) -> None:
            pass

        transports = []
        for document in documents:
            for c in range(conns_per_doc):
                transport = CallbackWebSocketTransport(send_async, close_async)
                Connection(transport, None, document, f"s{c}", {})
                transports.append(transport)
        total_conns = num_docs * conns_per_doc
        latencies = []
        # one untimed round first: doc/fanout/transport machinery and
        # (in the wal pass) the gate/commit path warm symmetrically, so
        # the on-vs-off ratio compares steady states — first-run
        # warm-up must not masquerade as WAL overhead
        for round_no in range(rounds + 1):
            writes["target"] = writes["count"] + total_conns
            pending.clear()
            t0 = time.perf_counter()
            for document in documents:
                text = document.get_text("t")
                for _ in range(burst):
                    text.insert(len(text), "x" * 24)
            await asyncio.wait_for(pending.wait(), timeout=60)
            if round_no > 0:
                latencies.append(writes["t_last"] - t0)
        if wal is not None:
            await wal.flush()
        for transport in transports:
            transport.abort()
        lat_ms = np.array(latencies) * 1000
        return {
            "merge_to_last_write_p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "merge_to_last_write_p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        }

    wal_dir = tempfile.mkdtemp(prefix="hocuspocus-wal-bench-")
    try:
        wal = WalManager(os.path.join(wal_dir, "storm"), fsync="tick")
        with_wal = asyncio.run(storm(wal))
        baseline = asyncio.run(storm(None))
        appended = wal.stats["appended_records"]
        fsyncs = max(wal.stats["fsyncs"], 1)

        # append -> durable latency distribution (its own loop: each
        # await resolves at that tick's group commit)
        async def append_latency() -> "list[float]":
            lat = []
            wal2 = WalManager(os.path.join(wal_dir, "lat"), fsync="tick")
            payload = b"y" * 64
            for i in range(256):
                t0 = time.perf_counter()
                await wal2.append("append-doc", payload)
                lat.append(time.perf_counter() - t0)
            return lat

        append_ms = np.array(asyncio.run(append_latency())) * 1000

        # recovery replay: scan + apply a 10k-update log
        from hocuspocus_tpu.crdt import Doc, apply_update

        async def build_and_replay() -> "tuple[float, int]":
            wal3 = WalManager(os.path.join(wal_dir, "replay"), fsync="off")
            seed = Doc()
            updates: "list[bytes]" = []
            seed.on("update", lambda update, *rest: updates.append(update))
            text = seed.get_text("t")
            for i in range(replay_updates):
                text.insert(len(text), "z")
            for update in updates:
                wal3.append("replay-doc", update)
            await wal3.flush()
            wal3.close()
            cold = WalManager(os.path.join(wal_dir, "replay"), fsync="off")
            t0 = time.perf_counter()
            records, report = await cold.replay("replay-doc")
            doc = Doc()
            for _rec_type, payload in records:
                apply_update(doc, payload)
            elapsed = time.perf_counter() - t0
            assert len(str(doc.get_text("t"))) == replay_updates
            return elapsed, report["records"]

        replay_s, replayed = asyncio.run(build_and_replay())
        on_p99 = with_wal["merge_to_last_write_p99_ms"]
        off_p99 = baseline["merge_to_last_write_p99_ms"]
        return {
            "docs": num_docs,
            "connections": num_docs * conns_per_doc,
            "rounds": rounds,
            "burst": burst,
            "wal_on": with_wal,
            "wal_off": baseline,
            # the gated headline: fractional p99 overhead of tick-fsync
            # group commit on the merge->broadcast path (budget: <0.15)
            "broadcast_p99_overhead": round(
                (on_p99 - off_p99) / max(off_p99, 1e-9), 4
            ),
            "append_p50_ms": round(float(np.percentile(append_ms, 50)), 3),
            "append_p99_ms": round(float(np.percentile(append_ms, 99)), 3),
            "records_per_fsync": round(appended / fsyncs, 2),
            "fsyncs": int(wal.stats["fsyncs"]),
            "appended_records": int(appended),
            "replay_updates": int(replayed),
            "replay_seconds": round(replay_s, 3),
            "replay_updates_per_sec": round(replayed / max(replay_s, 1e-9), 1),
        }
    finally:
        shutil.rmtree(wal_dir, ignore_errors=True)


def _measure_replica_storm() -> dict:
    """Cross-instance replication lane under storm load (all production
    code: two real Server instances, full provider pipeline, a real
    MiniRedis between them — only websocket framing is absent, via the
    in-process provider socket):

    2 instances x N docs, every doc with a writer on instance A and a
    reader on instance B, bursty concurrent edits. Reports publishes/s,
    the pipelined flush batch profile (publishes-per-RTT), the
    frames-saved ratio vs per-update publishing (one publish per local
    update, what the extension did before the lane), and the
    merge -> remote-broadcast p50/p99 (writer insert at A to the
    reader's CPU doc reflecting it at B, through redis).
    """
    import asyncio

    from hocuspocus_tpu.aio import await_synced
    from hocuspocus_tpu.extensions import Redis
    from hocuspocus_tpu.net.mini_redis import MiniRedis
    from hocuspocus_tpu.observability.wire import get_wire_telemetry
    from hocuspocus_tpu.provider import HocuspocusProvider
    from hocuspocus_tpu.provider.inprocess import InProcessProviderSocket
    from hocuspocus_tpu.server import Configuration, Server

    num_docs = int(os.environ.get("BENCH_REPLICA_DOCS", 256))
    rounds = int(os.environ.get("BENCH_REPLICA_ROUNDS", 12))
    burst = int(os.environ.get("BENCH_REPLICA_BURST", 4))
    docs_per_socket = int(os.environ.get("BENCH_REPLICA_DOCS_PER_SOCKET", 128))

    async def run() -> dict:
        redis = await MiniRedis().start()
        ext_a = Redis(port=redis.port, identifier="replica-a", disconnect_delay=100)
        ext_b = Redis(port=redis.port, identifier="replica-b", disconnect_delay=100)
        server_a = Server(Configuration(quiet=True, extensions=[ext_a]))
        await server_a.listen(port=0)
        server_b = Server(Configuration(quiet=True, extensions=[ext_b]))
        await server_b.listen(port=0)
        writers: list = []
        readers: list = []
        for base in range(0, num_docs, docs_per_socket):
            hi = min(base + docs_per_socket, num_docs)
            socket_a = InProcessProviderSocket(server_a)
            socket_b = InProcessProviderSocket(server_b)
            chunk_w = []
            for d in range(base, hi):
                p = HocuspocusProvider(name=f"rep-{d}", websocket_provider=socket_a)
                p.attach()
                chunk_w.append(p)
            await await_synced(chunk_w, 300, f"replica writers @{base}")
            chunk_r = []
            for d in range(base, hi):
                p = HocuspocusProvider(name=f"rep-{d}", websocket_provider=socket_b)
                p.attach()
                chunk_r.append(p)
            await await_synced(chunk_r, 300, f"replica readers @{base}")
            writers.extend(chunk_w)
            readers.extend(chunk_r)
        _log(f"replica: topology up ({num_docs} docs x 2 instances)")

        wire = get_wire_telemetry()
        wire.enable()
        before = wire.totals()
        pub_counters = getattr(ext_a.pub, "counters", {})
        pub_before = dict(pub_counters)
        stats_before = dict(ext_a.replication_stats)

        async def storm_round() -> list:
            t0: dict = {}
            lat: list = []
            handlers = []
            events = []
            for d in range(num_docs):
                wtext = writers[d].document.get_text("body")
                rdoc = readers[d].document
                rtext = rdoc.get_text("body")
                expected = len(wtext) + 8 * burst
                event = asyncio.Event()

                def handler(*args, d=d, rtext=rtext, expected=expected, event=event):
                    if not event.is_set() and len(rtext) >= expected:
                        lat.append(time.perf_counter() - t0[d])
                        event.set()

                rdoc.on("update", handler)
                handlers.append((rdoc, handler))
                events.append(event)
            try:
                # bursty concurrent writers: every doc's burst lands in
                # one event-loop tick at instance A
                for d in range(num_docs):
                    t0[d] = time.perf_counter()
                    wtext = writers[d].document.get_text("body")
                    for _ in range(burst):
                        wtext.insert(len(wtext), "z" * 8)
                await asyncio.wait_for(
                    asyncio.gather(*(event.wait() for event in events)), timeout=120
                )
            finally:
                for rdoc, handler in handlers:
                    rdoc.off("update", handler)
            return lat

        latencies: list = []
        t_start = time.perf_counter()
        for _ in range(rounds):
            latencies.extend(await storm_round())
        elapsed = max(time.perf_counter() - t_start, 1e-9)

        after = wire.totals()
        pub_after = dict(pub_counters)
        stats_after = dict(ext_a.replication_stats)
        publishes = int(after["pubsub_publishes"] - before["pubsub_publishes"])
        flushes = int(pub_after.get("flushes", 0) - pub_before.get("flushes", 0))
        commands = int(
            pub_after.get("commands_flushed", 0) - pub_before.get("commands_flushed", 0)
        )
        updates_enqueued = int(
            stats_after["updates_enqueued"] - stats_before["updates_enqueued"]
        )
        frames_published = int(
            stats_after["update_frames_published"]
            - stats_before["update_frames_published"]
        )
        lat_ms = np.array(latencies) * 1000

        for p in writers + readers:
            p.destroy()
        await server_a.destroy()
        await server_b.destroy()
        await redis.stop()
        return {
            "docs": num_docs,
            "instances": 2,
            "rounds": rounds,
            "burst": burst,
            "samples": len(latencies),
            "publishes": publishes,
            "publishes_per_sec": round(publishes / elapsed, 1),
            # publishes-per-RTT: commands shipped per pipelined flush
            # (>1 means the lane amortized round trips; the per-command
            # client is exactly 1.0)
            "pipeline_flushes": flushes,
            "avg_flush_batch": round(commands / max(flushes, 1), 2),
            "max_flush_batch": int(pub_after.get("max_batch", 0)),
            # frames-saved vs per-update publishing (one publish per
            # local update, the pre-lane behavior)
            "updates_enqueued": updates_enqueued,
            "update_frames_published": frames_published,
            "frames_saved_ratio": round(
                updates_enqueued / max(frames_published, 1), 2
            ),
            "merge_to_remote_broadcast_p50_ms": round(
                float(np.percentile(lat_ms, 50)), 3
            ),
            "merge_to_remote_broadcast_p99_ms": round(
                float(np.percentile(lat_ms, 99)), 3
            ),
        }

    return asyncio.run(run())


def _measure_mixed_load() -> dict:
    """Adaptive-scheduling differential (docs/guides/tpu-scheduling.md):
    interactive merge->broadcast latency while a hydration storm and
    proactive compaction churn run CONCURRENTLY against the same
    device, measured with the lane arbiter + batching governor ON vs
    OFF. The OFF leg is the pre-scheduler world: hydration's full-drain
    flushes and compaction sweeps contend blindly with the interactive
    flush pipeline; the ON leg admits them as catch-up/background lane
    classes that defer and yield to interactive work between
    microbatches. Gated by tools/bench_gate.py on
    mixed_load.interactive_p99 (the ON leg)."""
    import asyncio as _asyncio
    import time as _time

    from hocuspocus_tpu.crdt import (
        Doc,
        apply_update,
        encode_state_as_update,
        encode_state_vector,
    )
    from hocuspocus_tpu.server.types import Payload
    from hocuspocus_tpu.tpu.merge_plane import TpuMergeExtension
    from hocuspocus_tpu.tpu.residency import EvictedDoc
    from hocuspocus_tpu.tpu.scheduler import DeviceLane

    interactive_docs = int(os.environ.get("BENCH_MIXED_INTERACTIVE", 8))
    cold_docs = int(os.environ.get("BENCH_MIXED_DOCS", 2048))
    churn_docs = int(os.environ.get("BENCH_MIXED_CHURN", 4))
    edits = int(os.environ.get("BENCH_MIXED_EDITS", 2000))
    hydrate_batch = int(os.environ.get("BENCH_MIXED_HYDRATE", 128))
    budget_s = int(os.environ.get("BENCH_MIXED_TIMEOUT", 300))

    class _BenchDoc(Doc):
        """Server-document double: records broadcast frames so the
        merge->broadcast latency is measured at frame-enqueue time,
        exactly where the fan-out engine takes over."""

        def __init__(self, name: str) -> None:
            super().__init__()
            self.name = name
            self.sync_source = None
            self.broadcast_source = None
            self.frames = 0
            self.frame_event = _asyncio.Event()

        def get_connections_count(self) -> int:
            return 1

        def queue_broadcast(self, update, on_complete=None) -> None:
            self.frames += 1
            self.frame_event.set()
            if on_complete is not None:
                on_complete(_time.perf_counter())

        def broadcast_update_frame(self, update) -> None:
            self.frames += 1
            self.frame_event.set()

    async def leg(scheduled: bool) -> dict:
        ext = TpuMergeExtension(
            serve=True,
            num_docs=cold_docs + 64,
            capacity=2048,
            flush_interval_ms=2.0,
            broadcast_interval_ms=1.0,
            compact_threshold=0.6,
            hydrate_batch=hydrate_batch,
            governor=scheduled,
            lane=DeviceLane() if scheduled else False,
            native_lane=False,
        )
        # bench scaffolding, not the scheduled pipeline: warm the flush
        # grid outside the lane so the reported dispatch accounting
        # covers only the measured serving paths
        lane0, ext.plane.lane = ext.plane.lane, None
        ext.plane.warmup_compiles()
        ext.plane.lane = lane0
        # per-microbatch wall time: every plane.flush in the leg —
        # interactive drains, hydration rounds, compaction presyncs —
        # so the minimal-work run merge's cost shows up as a p99 drop
        # HERE (the fast columns skip the full-row integrate sweep)
        flush_ms: list = []
        orig_flush = ext.plane.flush

        def timed_flush(*f_args, **f_kwargs):
            f_t0 = _time.perf_counter()
            result = orig_flush(*f_args, **f_kwargs)
            flush_ms.append((_time.perf_counter() - f_t0) * 1000.0)
            return result

        ext.plane.flush = timed_flush
        docs: dict = {}
        sources: dict = {}

        async def onboard(name: str) -> "_BenchDoc":
            doc = _BenchDoc(name)
            source = Doc()
            source.client_id = 7000 + len(sources)
            docs[name], sources[name] = doc, source
            await ext.after_load_document(
                Payload(instance=None, document_name=name, document=doc)
            )
            return doc

        def edit(name: str, text: str, delete: "tuple | None" = None) -> bool:
            source = sources[name]
            prev_sv = encode_state_vector(source)
            body = source.get_text("t")
            if delete is not None:
                body.delete(*delete)
            if text:
                body.insert(len(body.to_string()), text)
            update = encode_state_as_update(source, prev_sv)
            doc = docs[name]
            apply_update(doc, update)
            captured = ext.try_capture(doc, update, origin=None)
            if not captured:
                # the real server's per-update CPU fan-out is immediate
                # when the capture seam declines (degrade/compaction
                # windows): emulate it so declined edits still broadcast
                doc.frame_event.set()
            return captured

        for i in range(interactive_docs):
            await onboard(f"live-{i}")
        for i in range(churn_docs):
            await onboard(f"churn-{i}")
        # cold population: stored eviction snapshots that will storm the
        # hydration queue mid-measurement
        snapshot_source = Doc()
        snapshot_source.get_text("t").insert(0, "cold payload " * 24)
        snapshot = encode_state_as_update(snapshot_source)
        mgr = ext.residency
        for i in range(cold_docs):
            mgr.evicted[f"cold-{i}"] = EvictedDoc(snapshot, 0.0)

        stop = False

        async def churn() -> None:
            """Tombstone pressure: fill churn rows, delete most of the
            content, let the compaction sweep rewrite them — repeatedly."""
            while not stop:
                for i in range(churn_docs):
                    name = f"churn-{i}"
                    edit(name, "x" * 64)
                    length = len(sources[name].get_text("t").to_string())
                    if length > 1024:
                        edit(name, "", delete=(0, length - 64))
                    await _asyncio.sleep(0.003)
                    if stop:
                        return
                try:
                    await mgr._compact_sweep()
                except Exception:
                    pass
                await _asyncio.sleep(0.01)

        async def one_edit(i: int) -> float:
            name = f"live-{i % interactive_docs}"
            doc = docs[name]
            doc.frame_event.clear()
            # bound the live text (tombstone churn the compaction sweep
            # reclaims) so a long measurement never overflows the row
            length = len(sources[name].get_text("t").to_string())
            t0 = _time.perf_counter()
            if length > 800:
                edit(name, "y" * 16, delete=(0, 400))
            else:
                edit(name, "y" * 16)
            await _asyncio.wait_for(doc.frame_event.wait(), 30)
            return _time.perf_counter() - t0

        async def one_sync(i: int) -> float:
            """Cold-joiner SyncStep2 through the batched serving path —
            the interactive DEVICE-GATED request: it drains the flush
            queue under the flush lock, so without the arbiter it
            FIFO-queues behind whole hydration rounds."""
            name = f"live-{i % interactive_docs}"
            t0 = _time.perf_counter()
            payload = await ext.serving.batched_sync(name, docs[name], None)
            elapsed = _time.perf_counter() - t0
            return elapsed if payload is not None else -elapsed

        # warm the pipeline before the storm lands
        for i in range(interactive_docs * 2):
            await one_edit(i)
        await one_sync(0)
        sync_lat: list = []
        sync_fallbacks = 0
        sync_stop = False

        async def sync_probes() -> None:
            """Concurrent cold-joiner stream: each probe is a device-
            gated SyncStep2 racing the hydration rounds for the chip."""
            nonlocal sync_fallbacks
            j = 0
            while not sync_stop:
                elapsed = await one_sync(j)
                j += 1
                if elapsed >= 0:
                    sync_lat.append(elapsed)
                else:
                    sync_fallbacks += 1
                # a joiner every ~50ms: sample the queue-wait a cold
                # sync pays, without the probe stream itself saturating
                # the device
                await _asyncio.sleep(0.05)

        churn_task = _asyncio.ensure_future(churn())
        for i in range(cold_docs):
            mgr.request_hydration(f"cold-{i}")
        sync_task = _asyncio.ensure_future(sync_probes())
        lat: list = []
        in_storm = 0
        try:
            # sample the edit stream densely WHILE the storm drains (the
            # regime the arbiter exists for), topping up to a stable
            # sample floor if the storm finishes early
            i = 0
            while len(lat) < edits and (mgr._queue or mgr._drain_running):
                lat.append(await one_edit(i))
                i += 1
                in_storm += 1
                await _asyncio.sleep(0.001)
            while len(lat) < min(edits, 100):
                lat.append(await one_edit(i))
                i += 1
                await _asyncio.sleep(0.001)
        finally:
            stop = True
            sync_stop = True
            await churn_task
            await sync_task
        storm_live = bool(mgr._queue or mgr._drain_running)
        deadline = _time.perf_counter() + 60
        while (mgr._queue or mgr._drain_running) and _time.perf_counter() < deadline:
            await _asyncio.sleep(0.005)
        ext.cancel_timers()
        ext.plane.flush = orig_flush
        arr = np.array(lat) * 1000.0
        sync_arr = np.array(sync_lat or [0.0]) * 1000.0
        flush_arr = np.array(flush_ms or [0.0])
        # minimal-work merge accounting: what fraction of integrated
        # ops rode the append program vs the full integrate, and what
        # fraction of SyncStep2 delete-set reads came off the device
        # pack vs the host row gather
        fast_ops = ext.plane.counters["flush_fast_ops"]
        slow_ops = ext.plane.counters["flush_slow_ops"]
        enc_dev = ext.plane.counters["sync_encode_device"]
        enc_host = ext.plane.counters["sync_encode_host"]
        out = {
            "interactive_p50_ms": round(float(np.percentile(arr, 50)), 3),
            "interactive_p99_ms": round(float(np.percentile(arr, 99)), 3),
            "microbatch_p50_ms": round(float(np.percentile(flush_arr, 50)), 3),
            "microbatch_p99_ms": round(float(np.percentile(flush_arr, 99)), 3),
            "microbatches": len(flush_ms),
            "fast_path_fraction": round(fast_ops / max(fast_ops + slow_ops, 1), 3),
            "fast_path_ops": fast_ops,
            "slow_path_ops": slow_ops,
            "device_encode_share": round(enc_dev / max(enc_dev + enc_host, 1), 3),
            "interactive_sync_p50_ms": round(float(np.percentile(sync_arr, 50)), 3),
            "interactive_sync_p99_ms": round(float(np.percentile(sync_arr, 99)), 3),
            "samples": len(lat),
            "in_storm_samples": in_storm,
            "sync_samples": len(sync_lat),
            "sync_fallbacks": sync_fallbacks,
            "storm_overlapped": storm_live,
            "hydrated": ext.plane.counters["docs_hydrated"],
            "compacted": ext.plane.counters["docs_compacted"],
        }
        if scheduled and ext.lane is not None:
            counters = ext.lane.counters
            out["lane"] = {
                "admissions": counters["admissions"],
                "preemptions": counters["preemptions"],
                "starved_promotions": counters["starved_promotions"],
                "deferrals": counters["deferrals"],
                "dispatches_in_lane": counters["dispatches_in_lane"],
                "dispatches_bypass": counters["dispatches_bypass"],
            }
            out["governor"] = ext.governor.snapshot()["counters"]
        return out

    async def run() -> dict:
        # discarded pre-warm leg: exercises hydration + compaction once
        # so the process-wide jit cache holds every kernel BOTH measured
        # legs will hit — otherwise the first leg pays the compiles and
        # the comparison measures XLA, not scheduling
        nonlocal cold_docs, edits
        full = (cold_docs, edits)
        cold_docs, edits = min(cold_docs, 48), 12
        await leg(scheduled=True)
        cold_docs, edits = full
        # interleaved A/B rounds: machine-load drift on a shared CPU
        # runner otherwise biases whichever mode ran last. The
        # representative leg per mode is its best (min-p99) round —
        # both modes judged under their least-disturbed conditions.
        rounds = int(os.environ.get("BENCH_MIXED_ROUNDS", 2))
        on_rounds, off_rounds = [], []
        for _ in range(rounds):
            on_rounds.append(await leg(scheduled=True))
            off_rounds.append(await leg(scheduled=False))
        on = min(on_rounds, key=lambda r: r["interactive_p99_ms"])
        off = min(off_rounds, key=lambda r: r["interactive_p99_ms"])
        on["round_p99s_ms"] = [r["interactive_p99_ms"] for r in on_rounds]
        off["round_p99s_ms"] = [r["interactive_p99_ms"] for r in off_rounds]
        on_p99 = max(on["interactive_p99_ms"], 1e-6)
        on_sync_p99 = max(on["interactive_sync_p99_ms"], 1e-6)
        return {
            "interactive_docs": interactive_docs,
            "cold_docs": cold_docs,
            "churn_docs": churn_docs,
            "edits": edits,
            "hydrate_batch": hydrate_batch,
            "governor_on": on,
            "governor_off": off,
            # merge->broadcast rides host serve logs (PR 7) so parity
            # here is the architecture working; the device-GATED
            # interactive path (sync serves) is where arbitration pays
            "interactive_p99_improvement": round(
                off["interactive_p99_ms"] / on_p99, 3
            ),
            "interactive_sync_p50_improvement": round(
                off["interactive_sync_p50_ms"]
                / max(on["interactive_sync_p50_ms"], 1e-6),
                3,
            ),
            "interactive_sync_p99_improvement": round(
                off["interactive_sync_p99_ms"] / on_sync_p99, 3
            ),
        }

    async def bounded() -> dict:
        return await _asyncio.wait_for(run(), timeout=budget_s)

    return _asyncio.run(bounded())


def _measure_catchup_storm() -> dict:
    """Cold-doc hydration storm through the residency manager
    (BASELINE config 5 miniature, docs/guides/tpu-residency.md): N
    stored snapshots burst into the admission queue at once; a quarter
    of the docs also replay a post-snapshot live tail (the lowerer's
    known-clock dedup makes that a state-vector-diff replay). Reports
    hydration p50/p99, peak admission-queue depth, and the in-flight
    bound actually observed — plus a full zero-lost-updates sweep."""
    import asyncio as _asyncio
    import time as _time

    from hocuspocus_tpu.crdt import Doc, encode_state_as_update
    from hocuspocus_tpu.tpu.merge_plane import MergePlane
    from hocuspocus_tpu.tpu.residency import EvictedDoc, ResidencyManager
    from hocuspocus_tpu.tpu.serving import PlaneServing

    storm = int(os.environ.get("BENCH_STORM_DOCS", 10_000))
    batch = int(os.environ.get("BENCH_STORM_BATCH", 128))
    budget_s = int(os.environ.get("BENCH_STORM_TIMEOUT", 300))

    async def run() -> dict:
        plane = MergePlane(num_docs=storm + 64, capacity=64)
        serving = PlaneServing(plane)
        mgr = ResidencyManager(
            plane=plane, serving=serving, hydrate_batch=batch
        )
        texts: dict = {}
        tails: dict = {}
        sample_refs: dict = {}
        probe_sample = int(os.environ.get("BENCH_STORM_PROBES", 256))
        for i in range(storm):
            ref = Doc()
            ref.get_text("t").insert(0, "cold doc %05d " % i + "payload " * 3)
            snapshot = encode_state_as_update(ref)
            if i % 4 == 0:
                # edits that landed after the eviction snapshot: the
                # hydration live-tail replay must carry them
                ref.get_text("t").insert(0, "tail %d " % i)
                tails[f"storm-{i}"] = ref
            texts[f"storm-{i}"] = ref.get_text("t").to_string()
            if len(sample_refs) < probe_sample:
                sample_refs[f"storm-{i}"] = ref
            mgr.evicted[f"storm-{i}"] = EvictedDoc(snapshot, 0.0)

        inflight_max = 0
        orig_flush = plane.flush

        def spy_flush(*args, **kwargs):
            nonlocal inflight_max
            inflight_max = max(inflight_max, mgr.inflight)
            return orig_flush(*args, **kwargs)

        plane.flush = spy_flush
        t0 = _time.perf_counter()
        for name in texts:
            mgr.request_hydration(name, tails.get(name))
        deadline = t0 + budget_s
        while (mgr._queue or mgr._drain_running) and _time.perf_counter() < deadline:
            await _asyncio.sleep(0.005)
        elapsed = _time.perf_counter() - t0
        plane.flush = orig_flush
        completed = not mgr._queue and not mgr._drain_running

        serving.refresh()
        lost = sum(
            1
            for name, want in texts.items()
            if not (plane.is_supported(name) and plane.text(name) == want)
        )
        # post-storm cold joiners: every probe is a fresh SyncStep2
        # (sv=None, no cache priors) through the serving encode — the
        # path the on-device catch-up pack exists for. Gated by
        # tools/bench_gate.py as catchup_storm.cold_sync_p99.
        cold_lat: list = []
        for name, ref in sample_refs.items():
            p0 = _time.perf_counter()
            payload = serving.encode_state_as_update(name, ref, None)
            if payload is not None:
                cold_lat.append(_time.perf_counter() - p0)
        cold_arr = np.array(cold_lat or [0.0]) * 1000.0
        enc_dev = plane.counters["sync_encode_device"]
        enc_host = plane.counters["sync_encode_host"]
        stats = mgr.stats_snapshot()
        hydrated = plane.counters["docs_hydrated"]
        return {
            "docs": storm,
            "hydrate_batch": batch,
            "tail_replays": len(tails),
            "elapsed_s": round(elapsed, 2),
            "hydrations_per_sec": round(hydrated / elapsed, 1) if elapsed else 0.0,
            "hydrated": hydrated,
            "declined": plane.counters["hydrations_declined"],
            "hydration_p50_ms": stats["hydration_p50_ms"],
            "hydration_p99_ms": stats["hydration_p99_ms"],
            "queue_peak": int(plane.residency_stats["hydration_queue_peak"]),
            "max_inflight": inflight_max,
            "completed": completed,
            "lost_updates": lost,
            "cold_sync_probes": len(cold_lat),
            "cold_sync_p50_ms": round(float(np.percentile(cold_arr, 50)), 3),
            "cold_sync_p99_ms": round(float(np.percentile(cold_arr, 99)), 3),
            "device_encode_share": round(enc_dev / max(enc_dev + enc_host, 1), 3),
        }

    return _asyncio.run(run())


def _measure_sharded_scale() -> dict:
    """The 100k-doc regime as PRODUCTION runs it: doc-partitioned
    planes (ShardedTpuMergeExtension's layout) flushing independently.
    Each microbatch sweeps ONE shard's arena; this measures per-flush
    latency across every shard under sustained all-shard load —
    including the queueing a flush pays behind other shards' kernels —
    plus the aggregate merge throughput."""
    import time as _time

    import jax
    import numpy as _np

    from hocuspocus_tpu.tpu.kernels import make_empty_state
    from hocuspocus_tpu.tpu.pallas_kernels import integrate_op_slots_fast

    shards = int(os.environ.get("BENCH_SHARDS", 13))
    docs = int(os.environ.get("BENCH_SHARD_DOCS", 8192))
    capacity = int(os.environ.get("BENCH_CAPACITY", 5632))
    rounds = int(os.environ.get("BENCH_SHARD_ROUNDS", 4))
    build_ops = _make_op_builder(docs)
    import jax.numpy as jnp

    def sync(st):
        return int(_np.asarray(st.length).sum())

    states, clocks = [], []
    key = jax.random.PRNGKey(11)
    for s in range(shards):
        states.append(make_empty_state(docs, capacity))
        clocks.append(jnp.zeros((docs,), jnp.int32))
    # seed every shard to ~25% occupancy with 8-slot batches (one
    # compiled shape shared across all shards)
    seed_batches = max(capacity // 4 // MAX_RUN // 8, 1)
    for s in range(shards):
        for _ in range(seed_batches):
            key, sub = jax.random.split(key)
            clocks[s], ops = build_ops(sub, clocks[s], 8)
            states[s], _count = integrate_op_slots_fast(states[s], ops)
        sync(states[s])
    lat = []
    total = 0
    t_wall = _time.perf_counter()
    for _ in range(rounds):
        for s in range(shards):
            key, sub = jax.random.split(key)
            clocks[s], ops = build_ops(sub, clocks[s], 8)
            jax.block_until_ready(ops)
            t0 = _time.perf_counter()
            states[s], count = integrate_op_slots_fast(states[s], ops)
            sync(states[s])
            lat.append(_time.perf_counter() - t0)
            total += int(count)
    wall = _time.perf_counter() - t_wall
    return {
        "shards": shards,
        "docs_per_shard": docs,
        "docs_total": shards * docs,
        "capacity": capacity,
        "flushes": len(lat),
        "p99_flush_ms": round(float(_np.percentile(_np.array(lat) * 1000, 99)), 2),
        "p50_flush_ms": round(float(_np.percentile(_np.array(lat) * 1000, 50)), 2),
        "merges_per_sec": round(total / wall, 1),
        "backend": jax.default_backend(),
    }


def _measure_catchup_serving() -> dict:
    """Plane-served catch-up replay rate (config5 part-2 shape, bounded).

    10KB documents on a MergePlane; alternating cold/stale reconnects
    served via PlaneServing.encode_state_as_update — gather programs
    warmed first, exactly as a live server warms them at listen."""
    from hocuspocus_tpu.crdt import (
        Doc,
        encode_state_as_update,
        encode_state_vector,
    )
    from hocuspocus_tpu.tpu.merge_plane import MergePlane
    from hocuspocus_tpu.tpu.serving import PlaneServing

    num_docs = int(os.environ.get("BENCH_CATCHUP_DOCS", 128))
    serves = int(os.environ.get("BENCH_CATCHUP_SERVES", 1000))
    budget_s = int(os.environ.get("BENCH_CATCHUP_TIMEOUT", 120))

    source = Doc()
    text = source.get_text("t")
    for i in range(19):
        text.insert(len(text), ("line %04d " % i) * 25)
    mid_sv = encode_state_vector(source)
    text.insert(len(text), "tail content after the client went offline " * 9)
    snapshot = encode_state_as_update(source)

    plane = MergePlane(num_docs=num_docs, capacity=8192)
    use_lane = os.environ.get("BENCH_CATCHUP_LANE", "1") != "0" and plane.enable_lane()
    for d in range(num_docs):
        if use_lane:
            plane.register_lane(f"cold-{d}")
        else:
            plane.register(f"cold-{d}")
        plane.enqueue_update(f"cold-{d}", snapshot)
    plane.flush()
    serving = PlaneServing(plane)
    serving.refresh()
    serving.warmup_gathers()

    start = time.perf_counter()
    served_bytes = 0
    serving.prefetch_tombstones(
        [plane.docs[f"cold-{d}"] for d in range(num_docs)]
    )
    # alternate whole cold and stale WAVES over the doc fleet: every doc
    # sees both request kinds, and repeated cold waves hit the per-doc
    # payload cache exactly as a real reconnect storm's joiners do (the
    # number measures the production storm path, caches included —
    # cold_serves/stale_serves record the mix)
    done = cold = fallbacks = 0
    for i in range(serves):
        is_cold = (i // num_docs) % 2 == 0
        data = serving.encode_state_as_update(
            f"cold-{i % num_docs}", source, None if is_cold else mid_sv
        )
        if data is None:  # doc degraded to the CPU path mid-run
            fallbacks += 1
            continue
        served_bytes += len(data)
        done += 1
        cold += is_cold
        if time.perf_counter() - start > budget_s:
            break
    elapsed = time.perf_counter() - start
    return {
        "catchups_per_sec": round(done / elapsed, 1) if done else 0.0,
        "native_lane": bool(use_lane),
        "docs": num_docs,
        "serves": done,
        "cold_serves": cold,
        "stale_serves": done - cold,
        "fallbacks": fallbacks,
        "served_mb": round(served_bytes / 1e6, 2),
    }


def _measure_server_p99() -> "tuple[float, dict]":
    """Merge-to-broadcast p99 through the live server on the plane path.

    Boots the real aiohttp server with the serve-mode merge plane and
    measures client-A-insert → client-B-observes latency. The BASELINE
    budget (<50 ms p99) is specified AT SCALE: on TPU the population
    defaults to 10,240 live docs across a doc-partitioned
    ShardedTpuMergeExtension (each shard sweeping its own arena — the
    production topology for the 100k regime), falling back to 1,024 on
    a single plane if the big run can't complete. Every doc gets a
    writer providing steady background load (multiplexed over shared
    sockets), and a sampled subset gets a second (reader) provider on
    which latency is timed end-to-end (queue wait + lowering + device
    flush + merged broadcast + fan-out).
    """
    import jax as _jax

    on_tpu = _jax.default_backend() == "tpu"
    default_docs = 10240 if on_tpu else 8
    num_docs = int(os.environ.get("BENCH_SERVER_DOCS", default_docs))
    budget_s = int(os.environ.get("BENCH_SERVER_TIMEOUT", 420))
    if on_tpu and "BENCH_SERVER_DOCS" not in os.environ:
        # the at-scale attempt and its fallback SHARE the one budget —
        # two full budgets would push the inner bench past the
        # subprocess deadline and cost the already-computed headline
        try:
            return _measure_server_p99_at(num_docs, shards=8, budget_s=budget_s * 2 // 3)
        except Exception as error:
            p99, extra = _measure_server_p99_at(1024, shards=0, budget_s=budget_s // 3)
            extra["scale_fallback"] = repr(error)[:200]
            return p99, extra
    return _measure_server_p99_at(
        num_docs,
        shards=int(os.environ.get("BENCH_SERVER_SHARDS", 0)),
        budget_s=budget_s,
    )


def _measure_server_p99_at(num_docs: int, shards: int, budget_s: int) -> "tuple[float, dict]":
    import asyncio
    import time as _time

    from hocuspocus_tpu.provider import HocuspocusProvider, HocuspocusProviderWebsocket
    from hocuspocus_tpu.server import Configuration, Server
    from hocuspocus_tpu.tpu import ShardedTpuMergeExtension, TpuMergeExtension

    edits = int(os.environ.get("BENCH_SERVER_EDITS", 200))
    sampled = min(int(os.environ.get("BENCH_SERVER_SAMPLED", 32)), num_docs)
    docs_per_socket = int(os.environ.get("BENCH_SERVER_DOCS_PER_SOCKET", 128))

    async def run() -> "tuple[float, dict]":
        if shards > 0:
            ext = ShardedTpuMergeExtension(
                shards=shards,
                num_docs=max(num_docs * 2 // shards, 256),
                capacity=8192,
                flush_interval_ms=2.0,
                serve=True,
            )
            warm_planes = [s.plane for s in ext.shards]
            counters = lambda: ext.counters  # noqa: E731
            served = lambda: ext.served_docs()  # noqa: E731
        else:
            ext = TpuMergeExtension(
                num_docs=num_docs * 2, capacity=8192, flush_interval_ms=2.0, serve=True
            )
            warm_planes = [ext.plane]
            counters = lambda: ext.plane.counters  # noqa: E731
            served = lambda: len(ext._docs)  # noqa: E731
        server = Server(Configuration(quiet=True, extensions=[ext]))
        await server.listen(port=0)
        # compile every flush batch shape up front so first edits pay
        # serving latency, not XLA compile time
        for plane in warm_planes:
            plane.warmup_compiles()
        url = server.web_socket_url
        writers, readers, sockets = [], [], []
        try:
            # multiplex docs over shared sockets (fd budget at 10k docs)
            # and connect in chunks so the sync storm stays within the
            # provider backoff budget
            for base in range(0, num_docs, docs_per_socket):
                socket = HocuspocusProviderWebsocket(url=url)
                sockets.append(socket)
                chunk = []
                for d in range(base, min(base + docs_per_socket, num_docs)):
                    p = HocuspocusProvider(
                        name=f"bench-{d}", websocket_provider=socket
                    )
                    p.attach()  # explicit-socket providers don't auto-attach
                    chunk.append(p)
                writers.extend(chunk)
                deadline = _time.monotonic() + 120
                for p in chunk:
                    while not p.synced:
                        if _time.monotonic() > deadline:
                            raise TimeoutError("bench writers never synced")
                        await asyncio.sleep(0.005)
            reader_socket = HocuspocusProviderWebsocket(url=url)
            sockets.append(reader_socket)
            for d in range(sampled):
                reader = HocuspocusProvider(
                    name=f"bench-{d}", websocket_provider=reader_socket
                )
                reader.attach()
                readers.append(reader)
            deadline = _time.monotonic() + 60
            for p in readers:
                while not p.synced:
                    if _time.monotonic() > deadline:
                        raise TimeoutError("bench readers never synced")
                    await asyncio.sleep(0.005)

            # steady background load across the whole population: each
            # tick, ~6% of non-sampled docs take an insert, so flushes
            # run at real batch width during the latency measurement.
            # Lengths are tracked host-side (O(1), not to_string()) and
            # the loop yields between inserts so harness CPU stalls
            # don't masquerade as server latency in the timed samples.
            stop_load = False
            bg_len = [0] * num_docs

            async def background_load() -> None:
                tick = 0
                while not stop_load:
                    for d in range(sampled + tick % 16, num_docs, 16):
                        writers[d].document.get_text("body").insert(bg_len[d], "y" * 8)
                        bg_len[d] += 8
                        await asyncio.sleep(0)
                        if stop_load:
                            return
                    tick += 1
                    await asyncio.sleep(0.01)

            async def one_edit(i: int) -> float:
                d = i % sampled
                wtext = writers[d].document.get_text("body")
                rtext = readers[d].document.get_text("body")
                expected = len(rtext.to_string()) + 16
                t0 = _time.perf_counter()
                wtext.insert(len(wtext.to_string()), "x" * 16)
                while len(rtext.to_string()) < expected:
                    if _time.perf_counter() - t0 > 10:
                        raise TimeoutError(f"edit {i} never observed by reader")
                    await asyncio.sleep(0.0005)
                return _time.perf_counter() - t0

            # warmup covers EVERY sampled doc (first-touch costs: doc
            # materialization, serve-log path, flush-shape compiles)
            for i in range(max(10, sampled)):
                await one_edit(i)
            load_task = asyncio.ensure_future(background_load())
            try:
                lat = []
                deadline = _time.monotonic() + budget_s * 0.5
                for i in range(edits):
                    lat.append(await one_edit(i))
                    if _time.monotonic() > deadline and len(lat) >= 50:
                        break  # enough samples; protect the headline
            finally:
                stop_load = True
                await load_task
            totals = counters()
            assert totals["plane_broadcasts"] > 0, "plane never served"
            extra = {
                "server_docs": num_docs,
                "shards": shards,
                "sampled_docs": sampled,
                "samples": len(lat),
                "served_docs": served(),
                "plane_broadcasts": totals["plane_broadcasts"],
                "cpu_fallbacks": totals["cpu_fallbacks"],
            }
            return float(np.percentile(np.array(lat) * 1000, 99)), extra
        finally:
            for p in writers + readers:
                p.destroy()
            for socket in sockets:
                socket.destroy()
            await server.destroy()

    async def bounded() -> "tuple[float, dict]":
        return await asyncio.wait_for(run(), timeout=budget_s)

    return asyncio.run(bounded())


if __name__ == "__main__":
    run_bench()
