"""BASELINE config 5: cold docs, snapshot load + state-vector diff replay.

The catch-up storm: a fleet of cold documents reconnects and each client
needs the diff between its state vector and the server's. Four parts:

1. Device: batched state-vector diff for ~1M (doc, client) pairs in one
   kernel call (the O(docs) triage that decides who needs what).
2. Plane-served replay: a MergePlane loaded with 10KB documents serves
   actual sv-diff update bytes to a storm of cold/stale clients through
   PlaneServing.encode_state_as_update — the catch-up pipeline
   (device health+tombstone readback, host item encode), exactly what a
   reconnecting provider receives as SyncStep2.
3. END-TO-END storm through the LIVE server (round-2 verdict item 6):
   real ws providers cold-reconnect against a serve-mode plane; their
   concurrent SyncStep1s are batch-triaged by the state_vector_diff
   kernel (PlaneServing.batched_sync); reports time-to-synced p99 and
   the plane's sync_serves delta.
4. Host snapshot load + diff_update for a sample (the CPU-path floor).

Env: C5_DOCS (default 1_000_000 device pairs), C5_HOST_DOCS (default 200),
C5_PLANE_DOCS (default 128), C5_CATCHUPS (default 1000),
C5_SERVER_DOCS (default 16), C5_SERVER_WAVES (default 4).
"""

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


async def server_storm(num_docs: int, waves: int) -> dict:
    """Cold-reconnect storm against the live serve-mode server."""
    import numpy as np

    from hocuspocus_tpu.provider import HocuspocusProvider
    from hocuspocus_tpu.server import Configuration, Server
    from hocuspocus_tpu.tpu import TpuMergeExtension

    from _common import wait_synced

    from hocuspocus_tpu.extensions import SQLite

    # BASELINE config 5 is "database snapshot load + state-vector diff
    # replay": persistence is part of the config. It also makes the
    # storm robust — docs that unload between waves (store debounce
    # fired, all connections gone) reload from their snapshot instead
    # of silently coming back empty when waves outlast the debounce.
    ext = TpuMergeExtension(
        num_docs=num_docs * 2, capacity=8192, flush_interval_ms=2.0, serve=True
    )
    server = Server(
        Configuration(
            quiet=True,
            extensions=[SQLite(), ext],
            unload_immediately=False,
        )
    )
    await server.listen(port=0)
    url = server.web_socket_url

    try:
        # seed: each doc gets ~2KB of content, then the seeders leave
        seeders = [HocuspocusProvider(name=f"cold-{d}", url=url) for d in range(num_docs)]
        await wait_synced(seeders, "seeders never synced")
        for d, p in enumerate(seeders):
            p.document.get_text("t").insert(0, (f"doc {d} line " * 16 + "\n") * 16)
        await asyncio.sleep(0.3)  # let the plane flush the seeds
        for p in seeders:
            p.destroy()
        await asyncio.sleep(0.1)

        serves_before = ext.plane.counters["sync_serves"]
        latencies: list[float] = []
        total_joiners = 0
        for _ in range(waves):
            t0 = time.perf_counter()
            storm = [HocuspocusProvider(name=f"cold-{d}", url=url) for d in range(num_docs)]
            total_joiners += len(storm)
            per_join = {id(p): None for p in storm}

            deadline = time.monotonic() + 60
            pending = set(storm)
            while pending:
                for p in list(pending):
                    if p.synced:
                        per_join[id(p)] = time.perf_counter() - t0
                        pending.discard(p)
                if time.monotonic() > deadline:
                    raise TimeoutError("storm wave never fully synced")
                await asyncio.sleep(0.002)
            latencies.extend(v for v in per_join.values() if v is not None)
            for d, p in enumerate(storm):
                # identity check: the joiner for cold-<d> must receive
                # doc d's payload, not just any doc's
                assert p.document.get_text("t").to_string().startswith(f"doc {d} line")
                p.destroy()
            await asyncio.sleep(0.05)

        serves = ext.plane.counters["sync_serves"] - serves_before
        assert serves >= total_joiners, (serves, total_joiners)
        lat_ms = np.array(latencies) * 1000
        return {
            "joiners": total_joiners,
            "docs": num_docs,
            "waves": waves,
            "sync_serves_delta": serves,
            "time_to_synced_p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
            "time_to_synced_p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        }
    finally:
        await server.destroy()


def main() -> None:
    import numpy as np

    device_docs = int(os.environ.get("C5_DOCS", 1_000_000))
    host_docs = int(os.environ.get("C5_HOST_DOCS", 200))

    # -- part 1: device SV diff -------------------------------------------
    import jax
    import jax.numpy as jnp

    from hocuspocus_tpu.tpu.kernels import state_vector_diff

    clients_per_doc = 4
    rng = np.random.default_rng(0)
    server_clocks = jnp.asarray(
        rng.integers(0, 10_000, size=(device_docs, clients_per_doc)), jnp.int32
    )
    client_clocks = jnp.maximum(
        server_clocks
        - jnp.asarray(rng.integers(0, 500, size=(device_docs, clients_per_doc)), jnp.int32),
        0,
    )
    # warm
    missing_from, missing_len = state_vector_diff(server_clocks, client_clocks)
    jax.block_until_ready((missing_from, missing_len))
    t0 = time.perf_counter()
    missing_from, missing_len = state_vector_diff(server_clocks, client_clocks)
    total_missing = int(jnp.sum(missing_len))  # blocks
    device_elapsed = time.perf_counter() - t0

    # -- part 2: plane-served catch-up replay ------------------------------
    from hocuspocus_tpu.crdt import (
        Doc,
        apply_update,
        diff_update,
        encode_state_as_update,
        encode_state_vector,
    )
    from hocuspocus_tpu.tpu.merge_plane import MergePlane
    from hocuspocus_tpu.tpu.serving import PlaneServing

    plane_docs = int(os.environ.get("C5_PLANE_DOCS", 128))
    catchups = int(os.environ.get("C5_CATCHUPS", 1000))

    # a representative 10KB document (BASELINE regime: 10,240 bytes of
    # UTF-16 ≈ 5,120 units; 19 lines x 250 + 390-unit tail = 5,140)
    source = Doc()
    text = source.get_text("t")
    for i in range(19):
        text.insert(len(text), ("line %04d " % i) * 25)
    mid_sv = encode_state_vector(source)  # the stale client's state
    text.insert(len(text), "tail content after client went offline " * 10)
    snapshot_bytes = encode_state_as_update(source)
    full_text = text.to_string()

    plane = MergePlane(num_docs=plane_docs, capacity=8192)
    for d in range(plane_docs):
        name = f"cold-{d}"
        plane.register(name)
        plane.enqueue_update(name, snapshot_bytes)
    plane.flush()
    serving = PlaneServing(plane)
    serving.refresh()

    # correctness spot check: a cold client's served reply reproduces
    # the full document
    served = serving.encode_state_as_update("cold-0", source, None)
    assert served is not None, "plane must serve a healthy doc"
    probe = Doc()
    apply_update(probe, served)
    assert probe.get_text("t").to_string() == full_text

    serving.warmup_gathers()  # a live server compiles these at listen
    t0 = time.perf_counter()
    served_bytes = 0
    # what the live storm path does per drain: one gathered tombstone
    # read for the whole doc batch instead of a per-slot RTT each
    serving.prefetch_tombstones([plane.docs[f"cold-{d}"] for d in range(plane_docs)])
    for i in range(catchups):
        name = f"cold-{i % plane_docs}"
        sv = None if i % 2 == 0 else mid_sv  # alternate cold / stale
        data = serving.encode_state_as_update(name, source, sv)
        served_bytes += len(data)
    replay_elapsed = time.perf_counter() - t0

    # -- part 3: end-to-end storm through the live server ------------------
    server_docs = int(os.environ.get("C5_SERVER_DOCS", 16))
    server_waves = int(os.environ.get("C5_SERVER_WAVES", 4))
    e2e = asyncio.run(server_storm(server_docs, server_waves))

    # -- part 4: CPU-path floor (snapshot load + diff_update) -------------
    t0 = time.perf_counter()
    replayed = 0
    for _ in range(host_docs):
        server_doc = Doc()
        apply_update(server_doc, snapshot_bytes)
        diff = diff_update(encode_state_as_update(server_doc), mid_sv)
        client_doc = Doc()
        apply_update(client_doc, encode_state_as_update(source, encode_state_vector(client_doc)))
        replayed += len(diff)
    host_elapsed = time.perf_counter() - t0

    print(
        json.dumps(
            {
                "metric": "config5_catchups_per_sec",
                "value": round(catchups / replay_elapsed, 1),
                "unit": "catchups/s",
                "extra": {
                    "plane_docs": plane_docs,
                    "catchups": catchups,
                    "served_mb": round(served_bytes / 1e6, 2),
                    "served_mb_per_sec": round(served_bytes / 1e6 / replay_elapsed, 2),
                    "device_sv_pairs_per_sec": round(
                        device_docs * clients_per_doc / device_elapsed, 1
                    ),
                    "device_pairs": device_docs * clients_per_doc,
                    "device_ms": round(device_elapsed * 1000, 2),
                    "total_missing_clocks": total_missing,
                    "host_cpu_docs_per_sec": round(host_docs / host_elapsed, 1),
                    "snapshot_bytes": len(snapshot_bytes),
                    "server_storm": e2e,
                    "backend": jax.default_backend(),
                },
            }
        )
    )


if __name__ == "__main__":
    main()
