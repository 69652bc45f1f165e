"""Host-side serve-plane scaling: the Python costs at large doc counts.

The device side of the 100k-doc regime is measured by bench.py
(`extra.baseline_scale`); this measures the HOST machinery the serving
path runs per window at scale, without websocket-harness limits:

1. enqueue: lowering + serve-log append per update (try_capture cost)
2. broadcast pass: one merged frame per dirty doc (native encoder)
3. flush host side: _build_batch scatter at full batch width
4. health-cache adoption (refresh) — timed separately; the broadcast
   pass includes the production per-doc doc_healthy check

Env: HPS_DOCS (default 8192), HPS_ROUNDS (default 3).
Prints one JSON line.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import numpy as np

    from hocuspocus_tpu.crdt import (
        Doc,
        diff_update,
        encode_state_as_update,
        encode_state_vector,
    )
    from hocuspocus_tpu.tpu.merge_plane import MergePlane
    from hocuspocus_tpu.tpu.serving import PlaneServing

    num_docs = int(os.environ.get("HPS_DOCS", 8192))
    rounds = int(os.environ.get("HPS_ROUNDS", 3))
    # native text lane (default): the C++ host path. HPS_LANE=0
    # measures the Python path for comparison.
    use_lane = os.environ.get("HPS_LANE", "1") != "0"

    # one canonical doc provides the snapshot and the per-window delta
    src = Doc()
    src.client_id = 9
    text = src.get_text("t")
    text.insert(0, "baseline content " * 8)
    snapshot = encode_state_as_update(src)
    sv = encode_state_vector(src)
    text.insert(0, "window edit ")
    delta = diff_update(encode_state_as_update(src), sv)

    plane = MergePlane(num_docs=num_docs, capacity=512)
    if use_lane:
        use_lane = plane.enable_lane()
    serving = PlaneServing(plane)
    names = [f"doc-{d}" for d in range(num_docs)]

    t0 = time.perf_counter()
    for name in names:
        if use_lane:
            plane.register_lane(name)
        else:
            plane.register(name)
        plane.enqueue_update(name, snapshot, presync=True)
    seed_s = time.perf_counter() - t0

    # steady-state window: every doc takes one delta (worst-case dirty
    # width — real windows are a few percent of the population)
    enq = []
    bcast = []
    flush = []
    health = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for name in names:
            plane.enqueue_update(name, delta)
        enq.append(time.perf_counter() - t0)

        t0 = time.perf_counter()
        plane.flush()
        flush.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        serving.refresh()
        health.append(time.perf_counter() - t0)

        # mirrors the production dirty-drain: per-doc health check then
        # the BATCHED window build (merge_plane._broadcast_served /
        # serving.build_broadcast_pairs — lane docs drain in one native
        # call)
        t0 = time.perf_counter()
        dirty = list(plane.dirty)
        plane.dirty.clear()
        healthy, suspects = serving.filter_healthy(dirty)
        healthy.extend(
            name for name in suspects if serving.doc_healthy(name) is not None
        )
        pairs, failed = serving.build_broadcast_pairs(healthy)
        assert not failed, failed
        made = sum(1 for _name, pair in pairs if pair)
        bcast.append(time.perf_counter() - t0)
        assert made == num_docs, made
        # fresh clocks for the next round's delta
        before = encode_state_vector(src)
        text.insert(0, "x")
        delta = diff_update(encode_state_as_update(src), before)

    result = {
        "metric": "host_plane_broadcast_us_per_doc",
        "value": round(min(bcast) / num_docs * 1e6, 2),
        "unit": "us/doc-window",
        "extra": {
            "docs": num_docs,
            "native_lane": bool(use_lane),
            "seed_s": round(seed_s, 2),
            "enqueue_us_per_doc": round(min(enq) / num_docs * 1e6, 2),
            "flush_host_s": round(min(flush), 3),
            "health_refresh_s": round(min(health), 4),
            "broadcast_pass_s": round(min(bcast), 3),
            "rounds": len(bcast),
        },
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
