#!/usr/bin/env python
"""Serve from the chip, end to end, and say whether what came out is right.

    python chip_smoke.py                  # one chip  (13 x 8,192 x 5,632 shard planes)
    python chip_smoke.py --devices 4      # four chips (4 x 32,768 x 5,632 cells)
    python chip_smoke.py --single-plane   # one 100,000 x 5,632 plane: which warm shapes fit
    python chip_smoke.py --rehearse       # tiny CPU run of the same code (no device claim)

Boots the server exactly as `hocuspocus_tpu.cli` wires `--tpu-serve`
(`cli.build_server`), at the BASELINE 100k-doc x 10 KB arena size, in
THIS process — the process that holds the chip — and drives it with
real `HocuspocusProvider`s over real websockets on the same loop: per
document a writer and an observer; a typing burst at the end of the
text (run-append fast path); both clients inserting at the same
mid-document position plus a ranged delete (the YATA slow path); then a
cold joiner and a stale rejoiner served SyncStep2 from the plane. Only
a few hundred documents are live: the other arena rows stay empty
(`live_docs` / `arena_rows`) — populating 100k documents through
providers is the benchmark's job, not a smoke's.

Every document is then checked against the plain reference, the
pure-Python CRDT engine: writer text == observer text == server CPU
doc == `plane.text(name)` read back from the device == both joiners,
with equal state vectors. While clients are connected JAX may compile
nothing (the warm grid has to have covered it) and the server's
overload ladder may not reach RED or refuse a connection. The one-chip
run also runs, with result checks, the kernels its topology does not
reach (`chip_checks.py`).

Standard output carries two lines and nothing else: the report (one
JSON object: versions, counters, set-up seconds, memory, every check),
then, last, the verdict `{"ok": true, "device": {"platform": "tpu",
"kind": "...", "count": 1}}` with exactly those keys. Exit 0 and
`"ok": true` only if every check holds. No accelerator: exit 3 within
seconds, nothing runs, no line on stdout. Times in the report are
set-up information (compile, warm grid, cache), never a performance
metric.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import faulthandler
import gc
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

EXIT_CHECK_FAILED = 1
EXIT_NO_ACCELERATOR = 3
EXIT_NO_REPO = 4

# the deployments the smoke boots, as the CLI flags an operator types
TOPOLOGIES = {
    "shards": ["--tpu-shards", "13", "--tpu-docs", "8192", "--tpu-capacity", "5632"],
    "cells": ["--tpu-devices", "4", "--tpu-docs", "32768", "--tpu-capacity", "5632"],
    "single": ["--tpu-docs", "100000", "--tpu-capacity", "5632"],
    # --rehearse: the same three code paths at a size a CPU compiles in seconds
    "shards-rehearse": ["--tpu-shards", "2", "--tpu-docs", "64", "--tpu-capacity", "512"],
    "cells-rehearse": ["--tpu-devices", "2", "--tpu-docs", "64", "--tpu-capacity", "512"],
    "single-rehearse": ["--tpu-docs", "64", "--tpu-capacity", "512"],
}

# live documents per plane: at least 16 busy in one flush window, so the
# sparse integrate runs at a Pallas-eligible width
DOCS_PER_PLANE = {"shards": 24, "cells": 48}
REHEARSAL_DOCS_PER_PLANE = 12
TYPING_ROUNDS = 10
CONFLICT_ROUNDS = 3

SENTENCE = "the quick brown fox jumps over the lazy dog "
WORDS = ("alpha ", "beta ", "gamma ", "delta ", "epsilon ", "zeta ", "eta ", "theta ")
OVERLOAD_RUNGS = ("green", "brownout1", "brownout2", "red")

_started = time.perf_counter()


def log(message: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _started:7.1f}s] {message}", file=sys.stderr, flush=True)


class CompileEvents:
    """JAX's own compile events (jax.monitoring): the name of every
    program handed to the backend compiler — one per jit-cache miss,
    whether or not the persistent cache then answered it — and how
    many of them the persistent cache did answer."""

    def __init__(self) -> None:
        import jax.monitoring

        self.programs: "list[str]" = []
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _seconds: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs.append(str(kwargs.get("fun_name")))

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self) -> "tuple[int, int]":
        return len(self.programs), self.hits

    def since(self, mark: "tuple[int, int]") -> dict:
        """{programs asked of the compiler, of which read back from the
        persistent cache} since `mark`."""
        return {
            "programs": len(self.programs) - mark[0],
            "persistent_cache_hits": self.hits - mark[1],
        }

    def names_since(self, mark: "tuple[int, int]") -> "list[str]":
        return self.programs[mark[0] :]


async def wait_for(check, what: str, timeout: float, interval: float = 0.05) -> None:
    deadline = time.monotonic() + timeout
    while not check():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out after {timeout:.0f}s waiting for {what}")
        await asyncio.sleep(interval)


def plane_index_of(runtime, name: str) -> int:
    """Which plane of the runtime a document name lands on."""
    if hasattr(runtime, "placement"):  # per-device cells
        return runtime.cell_index_for(name)
    if hasattr(runtime, "shard_for"):  # doc-partitioned shards
        return runtime.shards.index(runtime.shard_for(name))
    return 0


def pick_names(runtime, per_plane: int, seed: int) -> "list[str]":
    """`per_plane` document names for every plane, so each plane's
    flush windows see a Pallas-eligible busy width."""
    planes = len(runtime.planes())
    buckets: "list[list[str]]" = [[] for _ in range(planes)]
    index = 0
    while any(len(bucket) < per_plane for bucket in buckets):
        name = f"smoke-{seed}-{index}"
        index += 1
        bucket = buckets[plane_index_of(runtime, name)]
        if len(bucket) < per_plane:
            bucket.append(name)
    return [name for bucket in buckets for name in bucket]


def sparse_event_counts() -> dict:
    """Process-wide integrate_sparse dispatch counts by 'KxB' shape."""
    from hocuspocus_tpu.observability.device_watch import compile_metrics

    counts: dict = {}
    for key, value in compile_metrics()[1]._values.items():
        labels = dict(key)
        if labels.get("site") == "integrate_sparse":
            counts[labels["shape"]] = counts.get(labels["shape"], 0) + int(value)
    return counts


async def serve_and_drive(args, topology: str, compiles: CompileEvents) -> dict:
    import jax

    from hocuspocus_tpu.aio import await_synced
    from hocuspocus_tpu.cli import build_parser, build_server
    from hocuspocus_tpu.crdt import (
        Doc,
        apply_update,
        encode_state_as_update,
        encode_state_vector,
    )
    from hocuspocus_tpu.provider import HocuspocusProvider
    from hocuspocus_tpu.server.overload import get_overload_controller
    from hocuspocus_tpu.tpu.pallas_kernels import _pick_block
    from hocuspocus_tpu.tpu.supervisor import (
        BREAKER_CLOSED,
        STATE_BROKEN,
        STATE_READY,
        SupervisedTpuMergeExtension,
    )

    checks: dict = {}
    report: dict = {"topology": topology, "checks": checks}
    wal_dir = tempfile.mkdtemp(prefix="chip-smoke-wal-")
    flags = ["--tpu-serve", *TOPOLOGIES[topology], "--wal-dir", wal_dir]
    report["cli_flags"] = " ".join(flags[:-1] + ["<tmp>"])
    server = build_server(build_parser().parse_args(flags))
    extension = next(
        ext
        for ext in server.configuration.extensions
        if isinstance(ext, SupervisedTpuMergeExtension)
    )
    supervisor = extension.supervisor
    providers: list = []
    try:
        setup_mark = compiles.mark()
        boot_started = time.perf_counter()
        await server.listen(port=0, host="127.0.0.1")

        # -- set-up: READY, then the whole warm grid, before any client ----
        await wait_for(
            lambda: supervisor.state in (STATE_READY, STATE_BROKEN)
            and (supervisor.runtime is not None or supervisor.state == STATE_BROKEN),
            "supervisor READY",
            timeout=600,
        )
        report["supervisor"] = {
            "state_before_first_client": supervisor.state,
            "init_seconds": None
            if supervisor.init_elapsed is None
            else round(supervisor.init_elapsed, 2),
            "init_timeout_s": supervisor.init_timeout,
        }
        checks["ready_before_first_client"] = supervisor.state == STATE_READY
        if supervisor.state != STATE_READY:
            report["supervisor"]["counters"] = dict(supervisor.counters)
            return report
        runtime = supervisor.runtime
        planes = runtime.planes()
        log(
            f"READY after {supervisor.init_elapsed:.1f}s: {len(planes)} plane(s) of "
            f"{planes[0].num_docs} x {planes[0].capacity}; warming the grid ..."
        )
        await wait_for(
            lambda: all(plane.warm_stats["done"] for plane in planes),
            "the warm grid on every plane",
            timeout=900,
            interval=0.25,
        )
        warm = supervisor.warm_snapshot()
        warm["boot_to_warm_seconds"] = round(time.perf_counter() - boot_started, 2)
        warm["jax_compiles"] = compiles.since(setup_mark)
        report["warm"] = warm
        checks["warm_grid_complete"] = (
            warm["done"]
            and not warm["failures"]
            and warm["entries"] > 0
            and warm["compiled"] + warm["covered"] == warm["entries"]
        )
        log(
            f"warm grid: {warm['entries']} entries = {warm['compiled']} compiled + "
            f"{warm['covered']} covered in {warm['seconds']}s, "
            f"failures={len(warm['failures'])}, jax {warm['jax_compiles']}"
        )
        if args.single_plane:
            # the finding IS the warm report: no traffic on this topology
            report["health"] = server.hocuspocus.get_health()
            report["memory"] = [device_memory(d) for d in jax.local_devices()]
            return report

        # -- traffic ---------------------------------------------------------
        docs_per_plane = (
            REHEARSAL_DOCS_PER_PLANE if args.rehearse else DOCS_PER_PLANE[topology]
        )
        names = pick_names(runtime, docs_per_plane, args.seed)
        report["live_docs"] = len(names)
        report["arena_rows"] = sum(plane.num_docs for plane in planes)
        sparse_before = sparse_event_counts()
        traffic_mark = compiles.mark()
        # The listen-time warm pass shares the loop with the overload
        # sampler and can push it past RED on its own (it did on the
        # chip when all 82 programs came from the persistent cache).
        # At RED the server 503s upgrades, so clients come once it is
        # below RED, as a balancer reading /healthz would send them;
        # what the ladder did before that is reported, not judged.
        ladder = get_overload_controller()
        settle_started = time.perf_counter()
        await wait_for(
            lambda: ladder.status()["state"] != "red",
            "the overload ladder to step down from RED before the first client",
            timeout=120,
        )
        ladder_before_traffic = {
            "settle_seconds": round(time.perf_counter() - settle_started, 2),
            "state_at_first_client": ladder.status()["state"],
            "transitions": len(ladder.transitions),
        }
        loop_lags_ms: "list[float]" = []  # the server's own 250 ms samples
        ladder.on_loop_lag.append(loop_lags_ms.append)
        url = server.web_socket_url
        max_late_ms = report["generator_max_late_ms"] = {}

        async def breathe(phase: str) -> None:
            """Give the loop back until it has caught up. Server and
            providers share this loop: a burst that outruns it is loop
            lag on the server's overload ladder (RED refuses
            connections), so the generator paces itself on how late
            its own timer fires and reports the worst of each phase."""
            loop = asyncio.get_running_loop()
            late = 1.0
            while late > 0.02:
                before = loop.time()
                await asyncio.sleep(0.01)
                late = loop.time() - before - 0.01
                max_late_ms[phase] = max(
                    max_late_ms.get(phase, 0.0), round(late * 1000.0, 1)
                )

        # `names` is grouped by plane: one plane's documents at a time,
        # so they are busy in the same flush window
        plane_groups = [
            names[at : at + docs_per_plane]
            for at in range(0, len(names), docs_per_plane)
        ]

        async def join(phase: str, *documents_for) -> "list[dict]":
            """One provider per document and role, connected a plane's
            documents at a time (a role is a name -> Doc-or-None
            function: None = a fresh document); returns one
            {name: provider} dict per role."""
            roles: "list[dict]" = [{} for _ in documents_for]
            for group in plane_groups:
                for role, document_for in zip(roles, documents_for):
                    fresh = {
                        name: HocuspocusProvider(
                            name=name, url=url, document=document_for(name)
                        )
                        for name in group
                    }
                    role.update(fresh)
                    providers.extend(fresh.values())
                    await await_synced(
                        fresh.values(), timeout=180, what="chip_smoke providers"
                    )
                await breathe(phase)
            return roles

        def fresh_document(_name: str):
            return None

        writers, observers = await join("connect", fresh_document, fresh_document)
        log(f"{len(names)} documents connected (writer + observer each)")

        def text_of(provider):
            return provider.document.get_text("body")

        async def converged(what: str) -> None:
            await wait_for(
                lambda: all(
                    text_of(writers[n]).to_string() == text_of(observers[n]).to_string()
                    for n in names
                ),
                f"writer/observer convergence after {what}",
                timeout=180,
            )

        async def every_document(phase: str, edit) -> None:
            """Apply `edit(name)` to every document, a plane at a time."""
            for group in plane_groups:
                for name in group:
                    edit(name)
                await breathe(phase)

        # typing burst at the end of the text: the run-append fast path
        await every_document("typing", lambda n: text_of(writers[n]).insert(0, SENTENCE))
        for round_index in range(TYPING_ROUNDS):
            word = WORDS[round_index % len(WORDS)]

            def type_word(name: str) -> None:
                body = text_of(writers[name])
                body.insert(len(body), word)

            await every_document("typing", type_word)
        await converged("the typing burst")
        stale_snapshots: dict = {}

        def snapshot(name: str) -> None:
            stale_snapshots[name] = encode_state_as_update(writers[name].document)

        await every_document("typing", snapshot)
        log("typing burst converged")

        # both clients insert at the SAME mid-document position, plus a
        # ranged delete, with no await between them: a real YATA conflict
        for round_index in range(CONFLICT_ROUNDS):

            def conflict(name: str) -> None:
                mine, theirs = text_of(writers[name]), text_of(observers[name])
                mid = len(mine) // 2
                mine.insert(mid, f"<w{round_index}>")
                theirs.insert(mid, f"<o{round_index}>")
                mine.delete(1 + round_index, 3)

            await every_document("conflict", conflict)
            await converged(f"conflict round {round_index}")
        # and the tail keeps growing after the conflicts
        await every_document(
            "conflict",
            lambda n: text_of(observers[n]).insert(len(text_of(observers[n])), "end."),
        )
        await converged("the closing append")
        log("concurrent mid-document edits converged")

        # let the device catch up with the host logs before the joiners
        await wait_for(
            lambda: sum(plane.pending_ops() for plane in planes) == 0,
            "the device queues to drain",
            timeout=120,
        )
        serves_before = sum(plane.counters["sync_serves"] for plane in planes)

        def stale_document(name: str):
            document = Doc()
            apply_update(document, stale_snapshots[name])
            return document

        cold, stale = await join("joiners", fresh_document, stale_document)
        joiner_serves = (
            sum(plane.counters["sync_serves"] for plane in planes) - serves_before
        )
        log(f"{2 * len(names)} joiners synced ({joiner_serves} plane sync serves)")

        # the overload ladder from the first client to the last joiner
        # (its sampler runs every 250 ms; two periods let the last
        # stall register). At RED the server 503s upgrades and refuses
        # new document channels; a refusal counts from boot.
        await asyncio.sleep(0.6)
        overload = ladder.status()
        transitions = list(ladder.transitions)
        in_traffic = transitions[ladder_before_traffic["transitions"] :]
        before_traffic = transitions[: ladder_before_traffic["transitions"]]

        def worst(rungs) -> str:
            return max(rungs, key=OVERLOAD_RUNGS.index)

        def arrows(entries) -> "list[str]":
            return [
                f"{t['from_rung']}->{t['to_rung']} ({','.join(t['reasons'])})"
                for t in entries
            ]

        peak = worst(
            [ladder_before_traffic["state_at_first_client"]]
            + [t["to_rung"] for t in in_traffic]
        )
        refused = {
            reason: count
            for reason, count in overload["shed"].items()
            if reason.endswith("_rejected")
        }
        report["overload"] = {
            "state": overload["state"],
            "peak": peak,
            "max_loop_lag_ms_in_traffic": round(max(loop_lags_ms, default=0.0), 1),
            "red_at_loop_lag_ms": overload["signals"]["loop_lag_ms"]["thresholds"][-1],
            "transitions": arrows(in_traffic),
            "before_first_client": {
                "peak": worst(["green"] + [t["to_rung"] for t in before_traffic]),
                "transitions": arrows(before_traffic),
                "settle_seconds": ladder_before_traffic["settle_seconds"],
                "state_at_first_client": ladder_before_traffic["state_at_first_client"],
            },
            "shed": overload["shed"],
        }
        checks["admission_never_refused"] = peak != "red" and not refused

        # -- verdicts --------------------------------------------------------
        compiled_in_traffic = compiles.names_since(traffic_mark)
        wrong: list = []
        unserved: list = []
        log(f"traffic over (overload peak {peak}); reading every document back ...")
        loop = asyncio.get_running_loop()
        for name in names:
            want = text_of(writers[name]).to_string()
            server_doc = server.hocuspocus.documents.get(name)
            plane = planes[plane_index_of(runtime, name)]
            # the server is still live: read the device as its own
            # serving paths do, off the loop and under the flush lock
            async with plane.flush_lock:
                device_text = await loop.run_in_executor(None, plane.text, name)
            got = {
                "observer": text_of(observers[name]).to_string(),
                "server": None
                if server_doc is None
                else server_doc.get_text("body").to_string(),
                "device": device_text,
                "cold": text_of(cold[name]).to_string(),
                "stale": text_of(stale[name]).to_string(),
            }
            vector = encode_state_vector(writers[name].document)
            vectors_equal = all(
                encode_state_vector(peer[name].document) == vector
                for peer in (observers, cold, stale)
            )
            bad = [who for who, text in got.items() if text != want]
            if bad or not vectors_equal or not want.endswith("end."):
                wrong.append({"doc": name, "differs": bad, "vectors_equal": vectors_equal})
            if not runtime.is_served(name):
                unserved.append(name)
        report["sample"] = {"doc": names[0], "text": text_of(writers[names[0]]).to_string()}
        report["wrong_docs"] = wrong[:8]
        checks["all_texts_equal_reference"] = not wrong
        checks["all_docs_plane_served"] = not unserved

        counters: dict = {}
        for plane in planes:
            for key, value in plane.counters.items():
                counters[key] = counters.get(key, 0) + value
        report["counters"] = counters
        sparse_after = sparse_event_counts()
        live_sparse = {
            shape: sparse_after[shape] - sparse_before.get(shape, 0)
            for shape in sparse_after
            if sparse_after[shape] > sparse_before.get(shape, 0)
        }
        report["live_integrate_sparse"] = live_sparse
        capacity = planes[0].capacity
        pallas_widths = sorted(
            {
                int(shape.split("x")[1])
                for shape in live_sparse
                if int(shape.split("x")[1]) >= 16
                and _pick_block(int(shape.split("x")[1]), capacity) > 0
            }
        )
        report["live_pallas_sparse_widths"] = pallas_widths
        unexpected = [
            key for plane in planes for key in plane.compile_watch.unexpected_compiles
        ]
        # every program JAX handed to the compiler between the first
        # connect and the last joiner, tracked site or not: a cold
        # server must not compile on its first clients
        report["compiles_in_traffic"] = {
            "tracker_unexpected": unexpected,
            "jax_programs": compiled_in_traffic,
        }
        checks["no_fresh_compile_in_traffic"] = (
            not unexpected
            and not compiled_in_traffic
            # ... and the listener is not deaf: it heard the warm grid
            and warm["jax_compiles"]["programs"] > 0
        )
        checks["fast_path_live"] = counters["flush_fast_ops"] > 0
        checks["slow_path_live_at_pallas_width"] = (
            counters["flush_slow_ops"] > 0 and bool(pallas_widths)
        )
        checks["joiners_served_from_plane"] = joiner_serves >= 2 * len(names)
        checks["sync_encode_device"] = counters["sync_encode_device"] > 0
        checks["plane_broadcasts"] = counters["plane_broadcasts"] > 0
        checks["no_cpu_fallback"] = counters["cpu_fallbacks"] == 0
        checks["no_doc_retired"] = not any(
            value for key, value in counters.items() if key.startswith("docs_retired_")
        )

        health = supervisor.snapshot()
        report["supervisor"].update(
            state=health["state"],
            breaker=health["breaker"]["state"],
            counters=health["counters"],
            cells=health.get("cells"),
        )
        checks["supervisor_clean"] = (
            supervisor.state == STATE_READY
            and supervisor.counters["init_timeouts"] == 0
            and supervisor.counters["init_failures"] == 0
            and supervisor.counters["degrades"] == 0
            and supervisor.breaker.state == BREAKER_CLOSED
            and all(b.state == BREAKER_CLOSED for b in supervisor.cell_breakers)
        )
        # -- per-plane / per-device picture ----------------------------------
        per_plane = []
        for index, plane in enumerate(planes):
            # under the flush lock: a canary or flush in flight has
            # donated the arrays `plane.state` still names
            async with plane.flush_lock:
                device = next(iter(plane.state.id_client.devices()))
            per_plane.append(
                {
                    "plane": index,
                    "device": str(device),
                    "served_docs": sum(
                        1 for n in names if plane_index_of(runtime, n) == index
                    ),
                    "flush_fast_ops": plane.counters["flush_fast_ops"],
                    "flush_slow_ops": plane.counters["flush_slow_ops"],
                    "flush_batches_sparse": plane.counters["flush_batches_sparse"],
                    "sync_serves": plane.counters["sync_serves"],
                }
            )
        report["planes"] = per_plane if len(planes) <= 4 else per_plane[:2] + ["..."]
        report["memory"] = [device_memory(d) for d in jax.local_devices()]
        checks["every_plane_live"] = all(
            p["served_docs"] > 0 and p["flush_fast_ops"] > 0 and p["flush_slow_ops"] > 0
            for p in per_plane
        )
        if topology.startswith("cells"):
            arena_devices = [p["device"] for p in per_plane]
            report["arena_devices"] = arena_devices
            if not args.rehearse:  # a CPU rehearsal wraps its cells onto one device
                checks["one_arena_per_device"] = len(set(arena_devices)) == len(planes)
                checks["every_device_holds_memory"] = all(
                    (m.get("bytes_in_use") or 0) > 0 for m in report["memory"]
                ) and len(report["memory"]) == len(planes)
        return report
    finally:
        log("tearing down")
        for provider in providers:
            try:
                provider.destroy()
            except Exception:
                pass
        await asyncio.sleep(0.2)
        await server.destroy()
        shutil.rmtree(wal_dir, ignore_errors=True)


def device_memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {
        "device": str(device),
        "bytes_in_use": stats.get("bytes_in_use"),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
    }


def main(argv: "list[str] | None" = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--devices", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--single-plane",
        action="store_true",
        help="boot ONE 100,000 x 5,632 plane and report which warm-grid "
        "programs the chip can hold (no traffic; exit 0 = the probe ran)",
    )
    parser.add_argument(
        "--rehearse",
        action="store_true",
        help="run the same code at a tiny size on whatever JAX finds "
        "(the CPU here); proves the script, claims nothing about a chip",
    )
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(HERE, "hocuspocus_tpu")):
        print(
            f"chip_smoke: no hocuspocus_tpu package next to {__file__}; "
            "run it from a checkout of the repository",
            file=sys.stderr,
        )
        return EXIT_NO_REPO
    sys.path.insert(0, HERE)
    # a hang must end as a traceback and a non-zero exit inside the
    # caller's time limit, never as a silent timeout
    faulthandler.dump_traceback_later(1150, exit=True)

    import jax

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu" and not args.rehearse:
        print(
            f"chip_smoke: no accelerator — JAX found platform {device.platform!r} "
            f"({device.device_kind}, JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
            "Nothing was run. Use the chip tool, or --rehearse for a CPU rehearsal.",
            file=sys.stderr,
        )
        return EXIT_NO_ACCELERATOR
    if args.devices > len(devices) and not args.rehearse:
        print(
            f"chip_smoke: --devices {args.devices} but JAX sees {len(devices)}",
            file=sys.stderr,
        )
        return EXIT_NO_ACCELERATOR
    compiles = CompileEvents()
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu_version = version("libtpu")
    except Exception:
        libtpu_version = None
    result: dict = {
        "ok": False,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(devices),
        },
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(devices),
        "rehearsal": bool(args.rehearse),
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version,
            "python": sys.version.split()[0],
        },
        "seed": args.seed,
    }
    log(f"device: {device.platform} {device.device_kind} x{len(devices)}")

    # the native codec is built HERE from the committed sources, before
    # the first get_codec(): never a leftover binary, never a fallback
    from hocuspocus_tpu import native

    build_started = time.perf_counter()
    built = native.build(force=True)
    codec = native.get_codec()
    status, reason = native.codec_status()
    result["codec_path"] = status
    result["setup"] = {
        "native_build_seconds": round(time.perf_counter() - build_started, 2)
    }
    log(f"native codec: built={built} status={status} {reason or ''}")

    # importing the kernels places the compile cache
    from hocuspocus_tpu.tpu.kernels import COMPILE_CACHE_DIR

    result["compile_cache_dir"] = (
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE_DIR
    )

    topology = (
        "single" if args.single_plane else "cells" if args.devices == 4 else "shards"
    )
    if args.rehearse:
        topology += "-rehearse"
    # the CLI wiring includes the Logger extension, which prints a line per
    # connection to stdout: ours carries the report and the verdict only
    with contextlib.redirect_stdout(sys.stderr):
        served = asyncio.run(serve_and_drive(args, topology, compiles))
    checks = served.pop("checks")
    result.update(served)
    checks["codec_native_built_here"] = bool(built and codec is not None and status == "native")
    if not args.rehearse:
        checks["platform_is_tpu"] = device.platform == "tpu"

    if topology.startswith("shards") and all(checks.values()):
        # the kernels this topology's traffic does not reach, with the
        # arena it just freed
        gc.collect()
        from chip_checks import run_kernel_checks

        mark = compiles.mark()
        log("kernel checks: dense unit sweep + RLE arena programs ...")
        if args.rehearse:
            kernels = run_kernel_checks(
                num_docs=64, unit_capacity=512, rle_entries=256, num_slots=8,
                sparse_width=16, seed=args.seed, interpret=device.platform != "tpu",
            )
        else:
            kernels = run_kernel_checks(seed=args.seed)
        kernels["jax_compiles"] = compiles.since(mark)
        result["kernel_checks"] = kernels
        checks["kernel_checks"] = all(
            entry["ok"] for entry in kernels.values() if isinstance(entry, dict) and "ok" in entry
        )

    result["jax_compiles_total"] = compiles.since((0, 0))
    result["checks"] = checks
    result["total_seconds"] = round(time.perf_counter() - _started, 1)
    if args.single_plane:
        # a probe, not a verdict: it ran, and the refusal (if any) is loud
        failures = (result.get("warm") or {}).get("failures") or []
        health = (result.get("health") or {}).get("status")
        result["ok"] = "warm" in result and (not failures or health == "degraded")
    else:
        result["ok"] = bool(checks) and all(checks.values())
    failed = sorted(name for name, ok in checks.items() if not ok)
    if failed:
        log(f"FAILED checks: {failed}")
    out_dir = os.path.join(HERE, "chiprun_out")
    try:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"chip_smoke_{topology}.json"), "w") as fh:
            json.dump(result, fh, indent=1)
    except OSError:
        pass
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps(result), flush=True)
    # the verdict, alone on the last line, with exactly these keys
    print(json.dumps({"ok": result["ok"], "device": result["device"]}), flush=True)
    return 0 if result["ok"] else EXIT_CHECK_FAILED


if __name__ == "__main__":
    sys.exit(main())
