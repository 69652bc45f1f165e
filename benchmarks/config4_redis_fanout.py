"""BASELINE config 4: mixed Map/Array docs behind Redis fan-out,
multi-node, steady ops — SERVE-MODE planes on both instances (the
production topology, round-2 verdict item 5).

Two server instances share documents through (mini-)Redis; each runs a
serve=True TPU merge plane, so local fan-out rides plane broadcasts.
Clients on instance A stream steady mixed edits (text + Y.Map LWW
writes + Y.Array inserts), clients on instance B receive them. Measures
cross-instance propagation throughput and p99 latency, and asserts the
docs STAYED plane-served (zero unsupported retires / CPU fallbacks).

Env: C4_DOCS (default 10), C4_SECONDS (default 5),
REDIS_HOST/REDIS_PORT to target a real Redis.
"""

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


async def main() -> None:
    import numpy as np

    from hocuspocus_tpu.extensions import Redis
    from hocuspocus_tpu.net.mini_redis import MiniRedis
    from hocuspocus_tpu.provider import HocuspocusProvider
    from hocuspocus_tpu.server import Configuration, Server
    from hocuspocus_tpu.tpu import TpuMergeExtension

    num_docs = int(os.environ.get("C4_DOCS", 10))
    seconds = float(os.environ.get("C4_SECONDS", 5))

    redis_host = os.environ.get("REDIS_HOST")
    mini = None
    if redis_host:
        redis_port = int(os.environ.get("REDIS_PORT", 6379))
    else:
        mini = await MiniRedis().start()
        redis_host, redis_port = "127.0.0.1", mini.port

    planes = {}

    def make_server(ident):
        planes[ident] = TpuMergeExtension(
            num_docs=max(num_docs * 4, 64),
            capacity=4096,
            flush_interval_ms=2.0,
            serve=True,
        )
        return Server(
            Configuration(
                quiet=True,
                extensions=[
                    Redis(
                        host=redis_host,
                        port=redis_port,
                        identifier=ident,
                        disconnect_delay=100,
                    ),
                    planes[ident],
                ],
            )
        )

    server_a = make_server("bench-a")
    server_b = make_server("bench-b")
    await server_a.listen(port=0)
    await server_b.listen(port=0)

    writers = [
        HocuspocusProvider(name=f"doc-{d}", url=server_a.web_socket_url)
        for d in range(num_docs)
    ]
    readers = [
        HocuspocusProvider(name=f"doc-{d}", url=server_b.web_socket_url)
        for d in range(num_docs)
    ]
    while not all(p.synced for p in writers + readers):
        await asyncio.sleep(0.02)

    # settle phase: one mixed edit per doc, then wait for the planes to
    # reach steady serving state (listen-time warmup compiles + the
    # mixed-content docs' one-time native-lane demote/rebuild) so the
    # measured window reflects production steady state, not the one-off
    # compile/onboard transient
    settle = float(os.environ.get("C4_SETTLE", 30))
    for writer in writers:
        writer.document.get_map("meta").set("settle", 1)
    settle_deadline = time.perf_counter() + settle

    def steady() -> bool:
        for ext in planes.values():
            for name, doc in list(ext.plane.docs.items()):
                if doc.retired:
                    return False
            if not ext._docs:
                return False
        return True

    while time.perf_counter() < settle_deadline and not steady():
        await asyncio.sleep(0.1)

    # Window frames COALESCE many ops into one applied update on the
    # receiving instance, so counting reader update events undercounts
    # delivery. Measure instead by CONTENT: every op advances observable
    # state (text length / array length / map sentinel), delivery is
    # content equality, and latency is sampled per tick via a map
    # sentinel key (LWW — visible regardless of frame coalescing).
    sent = 0
    tick = 0
    map_ops_sent = [0] * num_docs
    latencies: list[float] = []
    pending_sentinels: dict[int, tuple[int, float]] = {}
    start = time.perf_counter()
    deadline = start + seconds
    while time.perf_counter() < deadline:
        for d, writer in enumerate(writers):
            # mixed Y.Map/Y.Array/Y.Text workload (BASELINE config 4)
            mode = (tick + d) % 3
            if mode == 0:
                writer.document.get_text("t").insert(0, "z")
            elif mode == 1:
                writer.document.get_map("meta").set(f"k{tick % 7}", tick)
                map_ops_sent[d] += 1
            else:
                writer.document.get_array("events").push([tick])
            sent += 1
        # one latency sample per tick: a sentinel key on a round-robin doc
        sd = tick % num_docs
        if sd not in pending_sentinels:
            writers[sd].document.get_map("meta").set("lat", tick)
            pending_sentinels[sd] = (tick, time.perf_counter())
            map_ops_sent[sd] += 1
            sent += 1
        for d, (value, t0) in list(pending_sentinels.items()):
            if readers[d].document.get_map("meta").get("lat") == value:
                latencies.append(time.perf_counter() - t0)
                del pending_sentinels[d]
        tick += 1
        await asyncio.sleep(0.02)  # ~50 ops/s/doc
    send_elapsed = time.perf_counter() - start

    # Convergence accounting by CONTENT. LWW map overwrites collapse on
    # the wire, so per-key presence can't count individual sets: credit
    # a doc's map sends IN FULL once every tracked key's FINAL value
    # matches the writer (delivery of the last write supersedes the
    # overwritten ones), else credit only the matching keys.
    TRACKED = ("lat", "settle", *[f"k{i}" for i in range(7)])

    def _map_delivery(d: int) -> "tuple[int, int]":
        wmap = writers[d].document.get_map("meta")
        rmap = readers[d].document.get_map("meta")
        set_keys = [k for k in TRACKED if wmap.get(k) is not None]
        matching = sum(1 for k in set_keys if rmap.get(k) == wmap.get(k))
        if matching == len(set_keys):
            return map_ops_sent[d], map_ops_sent[d]
        return matching, map_ops_sent[d]

    def delivered_ops(d: int) -> int:
        rdoc = readers[d].document
        return len(rdoc.get_text("t")) + len(rdoc.get_array("events")) + _map_delivery(d)[0]

    def target_ops(d: int) -> int:
        wdoc = writers[d].document
        return len(wdoc.get_text("t")) + len(wdoc.get_array("events")) + _map_delivery(d)[1]

    converge_deadline = time.perf_counter() + max(seconds, 30)
    while time.perf_counter() < converge_deadline:
        for d, (value, t0) in list(pending_sentinels.items()):
            if readers[d].document.get_map("meta").get("lat") == value:
                latencies.append(time.perf_counter() - t0)
                del pending_sentinels[d]
        if all(delivered_ops(d) >= target_ops(d) for d in range(num_docs)):
            break
        await asyncio.sleep(0.1)
    converged = all(delivered_ops(d) >= target_ops(d) for d in range(num_docs))
    received = sum(min(delivered_ops(d), target_ops(d)) for d in range(num_docs))
    total_target = sum(target_ops(d) for d in range(num_docs))
    elapsed = time.perf_counter() - start

    # verify the mixed docs actually stayed on the serve-mode planes
    plane_health = {}
    for ident, ext in planes.items():
        c = ext.plane.counters
        plane_health[ident] = {
            "plane_broadcasts": c["plane_broadcasts"],
            "sync_serves": c["sync_serves"],
            "docs_retired_unsupported": c["docs_retired_unsupported"],
            "cpu_fallbacks": c["cpu_fallbacks"],
            "docs_served": len(ext._docs),
        }
        assert c["docs_retired_unsupported"] == 0, plane_health
        assert c["cpu_fallbacks"] == 0, plane_health
    assert planes["bench-a"].plane.counters["plane_broadcasts"] > 0, plane_health

    p99 = float(np.percentile(np.array(latencies) * 1000, 99)) if latencies else None
    print(
        json.dumps(
            {
                "metric": "config4_cross_instance_ops_per_sec",
                "value": round(received / elapsed, 1),
                "unit": "ops/s",
                "extra": {
                    "docs": num_docs,
                    "sent": sent,
                    "delivered_ops": received,
                    "target_ops": total_target,
                    "converged": converged,
                    "send_window_s": round(send_elapsed, 2),
                    "propagation_p99_ms": round(p99, 2) if p99 else None,
                    "latency_samples": len(latencies),
                    "serve_mode": True,
                    "plane_health": plane_health,
                },
            }
        )
    )
    for p in writers + readers:
        p.destroy()
    await server_a.destroy()
    await server_b.destroy()
    if mini is not None:
        await mini.stop()


if __name__ == "__main__":
    asyncio.run(main())
