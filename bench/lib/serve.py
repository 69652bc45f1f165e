"""The system under test: the websocket server exactly as
`hocuspocus_tpu.cli` wires `--tpu-serve` (`cli.build_server`), booted in
this process, which holds the chip. Also the health facts of a run: a run
in which one fails did not measure the device path and is a failed run,
whatever its answers say.
"""

from __future__ import annotations

import asyncio
import shutil
import tempfile
import time

import seeded


class RunFailed(Exception):
    """The run measured nothing that can be reported: no metrics line."""


class CompileEvents:
    """Every program JAX hands to the backend compiler (one per jit-cache
    miss, whether or not the persistent cache then answers it)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.programs: "list[str]" = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _seconds: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs.append(str(kwargs.get("fun_name")))

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


async def wait_for(check, what: str, timeout: float, interval: float = 0.05) -> None:
    deadline = time.monotonic() + timeout
    while not check():
        if time.monotonic() > deadline:
            raise RunFailed(f"timed out after {timeout:.0f}s waiting for {what}")
        await asyncio.sleep(interval)


def dispatch_counts() -> dict:
    """Device dispatches so far by (site, 'KxB' shape), as the program's
    compile watch counts them."""
    from hocuspocus_tpu.observability.device_watch import compile_metrics

    counts: dict = {}
    for key, value in compile_metrics()[1]._values.items():
        labels = dict(key)
        if "site" in labels and "shape" in labels:
            at = (labels["site"], labels["shape"])
            counts[at] = counts.get(at, 0) + int(value)
    return counts


class LoopClock:
    """How long the server's event loop sleeps in its selector, waiting for
    sockets and timers: the rest of the time it runs the server's callbacks.
    With `annotate` (a traced run) every sleep is also a span of the trace,
    `bench.loop_asleep`, so that an idle gap of the device can be told by
    whether the host was busy or had nothing to do."""

    def __init__(self, loop, annotate=None) -> None:
        self.asleep_s = 0.0
        selector = loop._selector
        inner = selector.select

        def select(timeout=None):
            started = time.perf_counter()
            if annotate is None or timeout == 0:
                events = inner(timeout)
            else:
                with annotate("bench.loop_asleep"):
                    events = inner(timeout)
            self.asleep_s += time.perf_counter() - started
            return events

        selector.select = select


class Served:
    """One booted server with its planes, and the counters read at a
    window's edges."""

    def __init__(self, flags: "list[str]") -> None:
        from hocuspocus_tpu.cli import build_parser, build_server
        from hocuspocus_tpu.tpu.supervisor import SupervisedTpuMergeExtension

        self.wal_dir = tempfile.mkdtemp(prefix="bench-wal-")
        self.server = build_server(build_parser().parse_args([*flags, "--wal-dir", self.wal_dir]))
        self.supervisor = next(
            ext
            for ext in self.server.configuration.extensions
            if isinstance(ext, SupervisedTpuMergeExtension)
        ).supervisor
        self.durability = next(
            ext for ext in self.server.configuration.extensions if type(ext).__name__ == "Durability"
        )
        self.runtime = None
        self.planes: list = []
        self.warm: dict = {}
        self.direct: list = []  # the direct connections that keep resident documents loaded

    async def boot(self) -> None:
        """Listen, wait for READY and for the program's own warm grid, and
        for the overload ladder to be below RED (the warm pass can push it
        there; at RED the server refuses upgrades)."""
        from hocuspocus_tpu.server.overload import get_overload_controller
        from hocuspocus_tpu.tpu.supervisor import STATE_BROKEN, STATE_READY

        await self.server.listen(port=0, host="127.0.0.1")
        supervisor = self.supervisor
        await wait_for(
            lambda: supervisor.state in (STATE_READY, STATE_BROKEN)
            and (supervisor.runtime is not None or supervisor.state == STATE_BROKEN),
            "supervisor READY",
            timeout=600,
        )
        if supervisor.state != STATE_READY:
            raise RunFailed(f"supervisor is {supervisor.state}: {dict(supervisor.counters)}")
        self.runtime = supervisor.runtime
        self.planes = self.runtime.planes()
        await wait_for(
            lambda: all(plane.warm_stats["done"] for plane in self.planes),
            "the warm grid on every plane",
            timeout=900,
            interval=0.25,
        )
        self.warm = supervisor.warm_snapshot()
        if self.warm["failures"]:
            raise RunFailed(f"warm grid failures: {self.warm['failures']}")
        self.ladder = get_overload_controller()
        await wait_for(
            lambda: self.ladder.status()["state"] != "red",
            "the overload ladder to step down from RED",
            timeout=120,
        )

    @property
    def url(self) -> str:
        return self.server.web_socket_url

    def plane_index_of(self, name: str) -> int:
        runtime = self.runtime
        if hasattr(runtime, "placement"):  # per-device cells
            return runtime.cell_index_for(name)
        if hasattr(runtime, "shard_for"):  # doc-partitioned shards
            return runtime.shards.index(runtime.shard_for(name))
        return 0

    def pick_names(self, per_plane: int, seed: int, kind: str = "bench") -> "list[str]":
        """`per_plane` document names for every plane, grouped by plane."""
        buckets: "list[list[str]]" = [[] for _ in self.planes]
        index = 0
        while any(len(bucket) < per_plane for bucket in buckets):
            name = f"{kind}-{seed}-{index}"
            index += 1
            bucket = buckets[self.plane_index_of(name)]
            if len(bucket) < per_plane:
                bucket.append(name)
        return [name for bucket in buckets for name in bucket]

    async def write_log(self, names: "list[str]", firsts: list, kind) -> int:
        """The write-ahead log as a crashed server would have left it: one
        segment per document, holding the updates that make its first state
        (`kind.first_writes`). Written from a few threads (the directory may
        be on a slow mount). Returns the bytes written."""
        updates = [[update for _client, update in kind.first_writes(first)] for first in firsts]
        loop = asyncio.get_running_loop()
        shares = [(names[at::8], updates[at::8]) for at in range(8)]
        written = await asyncio.gather(
            *(loop.run_in_executor(None, seeded.write_wal, self.wal_dir, *share) for share in shares)
        )
        return sum(written)

    async def recover(self, names: "list[str]", group: int = 128) -> None:
        """Load documents with no socket, through the server's own cold path:
        a direct connection each, which replays the document's log, puts it
        on its plane and keeps it loaded until the server goes."""
        opening = self.server.hocuspocus.open_direct_connection
        for at in range(0, len(names), group):
            self.direct += await asyncio.gather(*(opening(name) for name in names[at : at + group]))
        await self.quiesce()
        unserved = [name for name in names if not self.runtime.is_served(name)]
        if unserved:
            raise RunFailed(f"{len(unserved)} recovered documents are not served from the plane: {unserved[:5]}")

    async def logged(self, names: "list[str]") -> "dict[str, list[bytes]]":
        """{name: the update payloads a recovery would replay for it}, once
        the log's group commit has landed everything buffered."""
        await self.durability.flush_wal()
        return await asyncio.get_running_loop().run_in_executor(None, seeded.read_wal, self.wal_dir, names)

    def counters(self) -> dict:
        """What is read at a window's edges: the planes' counters summed, the
        dispatch counts, and where the overload ladder stands."""
        total: dict = {}
        for plane in self.planes:
            for key, value in plane.counters.items():
                total[key] = total.get(key, 0) + value
        status = self.ladder.status()
        return {
            "plane": total,
            "wal": dict(self.durability.wal.stats),
            "dispatch": dispatch_counts(),
            "rung": status["state"],
            "rungs_seen": [t["to_rung"] for t in self.ladder.transitions],
            "refused": sum(v for k, v in status["shed"].items() if k.endswith("_rejected")),
            "unexpected_compiles": sum(len(p.compile_watch.unexpected_compiles) for p in self.planes),
        }

    async def settled(self) -> None:
        """Set-up is over when the overload ladder is back at green: the
        window has to start from the same rung in every run."""
        await wait_for(lambda: self.ladder.status()["state"] == "green", "the overload ladder at green", 90)

    async def quiesce(self) -> None:
        await wait_for(
            lambda: sum(plane.pending_ops() for plane in self.planes) == 0,
            "the device queues to drain",
            timeout=120,
        )

    async def views(self, names: "list[str]", kind) -> "tuple[dict, dict]":
        """({name: the server document's view}, {name: the device's view}),
        each as the document's kind reads it (`lib/kinds.py`)."""
        server_views, device_views = {}, {}
        for name in names:
            document = self.server.hocuspocus.documents.get(name)
            server_views[name] = None if document is None else kind.view(document)
            device_views[name] = await kind.device_view(self, name)
        return server_views, device_views

    async def on_plane(self, name: str, read):
        """`read(plane)` of the plane that serves `name`, as the server's own
        serving paths read it: off the loop and under the flush lock."""
        plane = self.planes[self.plane_index_of(name)]
        async with plane.flush_lock:
            return await asyncio.get_running_loop().run_in_executor(None, read, plane)

    async def device_update(self, name: str) -> "bytes | None":
        """The update the plane itself serves a joiner of `name` that brings
        an empty state vector (`PlaneServing.encode_state_as_update`), or
        None where the plane would leave the joiner to the CPU document. The
        view of a document the plane cannot materialise (a tree) is read
        from it by the kind's own reference."""
        document = self.server.hocuspocus.documents.get(name)
        plane = self.planes[self.plane_index_of(name)]
        serving = next((serving for serving in self.runtime.servings() if serving.plane is plane), None)
        if document is None or serving is None:
            return None
        return await self.on_plane(name, lambda _plane: serving.encode_state_as_update(name, document, None))

    @staticmethod
    def plane_delta(before: dict, after: dict) -> dict:
        """The planes' counters between two readings of `counters`."""
        return {
            key: value - before["plane"].get(key, 0)
            for key, value in after["plane"].items()
            if isinstance(value, (int, float))
        }

    @staticmethod
    def rungs_between(before: dict, after: dict) -> "list[str]":
        """The overload ladder's rungs from one reading to the next."""
        return [before["rung"]] + after["rungs_seen"][len(before["rungs_seen"]) :]

    def health(self, names: "list[str]", before: dict, after: dict, compiled: "list[str]") -> dict:
        """The health facts of the window, each true or false."""
        from hocuspocus_tpu.tpu.supervisor import BREAKER_CLOSED, STATE_READY

        supervisor = self.supervisor
        delta = self.plane_delta(before, after)
        return {
            "supervisor_ready_and_clean": supervisor.state == STATE_READY
            and not any(supervisor.counters[k] for k in ("init_timeouts", "init_failures", "degrades"))
            and supervisor.breaker.state == BREAKER_CLOSED,
            "no_cpu_fallback": delta.get("cpu_fallbacks", 0) == 0,
            "no_doc_retired": not any(v for k, v in delta.items() if k.startswith("docs_retired_")),
            "every_doc_plane_served": all(self.runtime.is_served(name) for name in names),
            "no_compile_in_window": not compiled
            and after["unexpected_compiles"] == before["unexpected_compiles"],
            "admission_never_refused": after["refused"] == before["refused"],
            "device_path_ran": delta.get("flush_fast_ops", 0) + delta.get("flush_slow_ops", 0) > 0,
        }

    async def close(self) -> None:
        await self.server.destroy()
        shutil.rmtree(self.wal_dir, ignore_errors=True)
