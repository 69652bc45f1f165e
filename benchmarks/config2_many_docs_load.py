"""BASELINE config 2: many docs, many clients, random insert/delete.

Real websocket providers spread over N documents drive a random-position
edit stream THROUGH the serve-mode TPU plane (fan-out rides plane
broadcasts; set C2_PLANE=0 for the bare CPU server); measures the
server's sustained applied-ops/sec and asserts plane health.

Env: C2_DOCS (default 20), C2_CLIENTS_PER_DOC (default 3),
C2_SECONDS (default 5), C2_PLANE (default 1).
"""

import asyncio
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


async def main() -> None:
    from hocuspocus_tpu.provider import HocuspocusProvider, HocuspocusProviderWebsocket
    from hocuspocus_tpu.server import Configuration, Server

    num_docs = int(os.environ.get("C2_DOCS", 20))
    clients_per_doc = int(os.environ.get("C2_CLIENTS_PER_DOC", 3))
    seconds = float(os.environ.get("C2_SECONDS", 5))
    use_plane = os.environ.get("C2_PLANE", "1") != "0"

    extensions = []
    ext = None
    if use_plane:
        from hocuspocus_tpu.tpu import TpuMergeExtension

        ext = TpuMergeExtension(
            num_docs=max(num_docs * 2, 64),
            capacity=8192,
            flush_interval_ms=2.0,
            serve=True,
        )
        extensions.append(ext)
    server = Server(Configuration(quiet=True, extensions=extensions))
    await server.listen(port=0)

    sockets = []
    providers = []
    for d in range(num_docs):
        for c in range(clients_per_doc):
            socket = HocuspocusProviderWebsocket(url=server.web_socket_url)
            provider = HocuspocusProvider(name=f"doc-{d}", websocket_provider=socket)
            provider.attach()
            sockets.append(socket)
            providers.append(provider)
    while not all(p.synced for p in providers):
        await asyncio.sleep(0.02)

    applied = 0
    for document in server.documents.values():
        document.on("update", lambda *a: None)

    rng = random.Random(0)
    sent = 0
    start = time.perf_counter()
    deadline = start + seconds
    while time.perf_counter() < deadline:
        for provider in providers:
            text = provider.document.get_text("t")
            if rng.random() < 0.8 or len(text) == 0:
                text.insert(rng.randint(0, len(text)), rng.choice("abcdef") * rng.randint(1, 10))
            else:
                pos = rng.randrange(len(text))
                text.delete(pos, min(rng.randint(1, 5), len(text) - pos))
            sent += 1
        await asyncio.sleep(0.01)
    elapsed = time.perf_counter() - start
    # wait for acks
    for _ in range(200):
        if all(not p.has_unsynced_changes for p in providers):
            break
        await asyncio.sleep(0.05)
    # let the async flush pipeline drain (first flushes may still be
    # paying compile time if the startup warmup hadn't finished)
    if ext is not None:
        for _ in range(600):
            if ext.plane.pending_ops() == 0 and ext.plane.counters["plane_broadcasts"] > 0:
                break
            await asyncio.sleep(0.05)

    extra = {
        "docs": num_docs,
        "clients": len(providers),
        "all_acked": all(not p.has_unsynced_changes for p in providers),
        "serve_mode": use_plane,
    }
    if ext is not None:
        counters = ext.plane.counters
        extra["plane_health"] = {
            "plane_broadcasts": counters["plane_broadcasts"],
            "docs_retired_unsupported": counters["docs_retired_unsupported"],
            "docs_retired_capacity": counters["docs_retired_capacity"],
            "cpu_fallbacks": counters["cpu_fallbacks"],
            "docs_served": len(ext._docs),
        }
        assert counters["docs_retired_unsupported"] == 0, extra
        assert counters["plane_broadcasts"] > 0, extra
    print(
        json.dumps(
            {
                "metric": "config2_applied_ops_per_sec",
                "value": round(sent / elapsed, 1),
                "unit": "ops/s",
                "extra": extra,
            }
        )
    )
    for provider in providers:
        provider.destroy()
    for socket in sockets:
        socket.destroy()
    await server.destroy()


if __name__ == "__main__":
    asyncio.run(main())
