"""E2E test harness: in-process server + real websocket providers.

Mirrors the reference test strategy (`tests/utils/newHocuspocus.ts`):
every test boots a real server on an OS-assigned port and real provider
clients over real WebSockets, in one process.
"""

from __future__ import annotations

import asyncio
import threading
import time
from typing import Any, Optional

from hocuspocus_tpu.provider import HocuspocusProvider, HocuspocusProviderWebsocket
from hocuspocus_tpu.server import Configuration, Server
from hocuspocus_tpu.storage import FaultInjector


async def new_hocuspocus(**options: Any) -> Server:
    options.setdefault("quiet", True)
    configuration = Configuration(**options)
    server = Server(configuration)
    await server.listen(port=0)
    return server


def new_provider_websocket(server: Server, **options: Any) -> HocuspocusProviderWebsocket:
    return HocuspocusProviderWebsocket(url=server.web_socket_url, **options)


def new_provider(server: Server, name: str = "hocuspocus-test", **options: Any) -> HocuspocusProvider:
    return HocuspocusProvider(name=name, url=server.web_socket_url, **options)


async def retryable_assertion(fn, timeout: float = 10.0, interval: float = 0.05) -> Any:
    """Poll until `fn` stops raising (eventual-consistency assertions —
    reference `tests/utils/retryableAssertion.ts`)."""
    deadline = time.monotonic() + timeout
    while True:
        try:
            result = fn()
            if asyncio.iscoroutine(result):
                result = await result
            return result
        except AssertionError:
            if time.monotonic() > deadline:
                raise
            await asyncio.sleep(interval)


async def wait_synced(*providers, timeout: float = 30.0) -> None:
    """Wait until every provider has completed its first sync handshake.

    Event-driven (delegates to `hocuspocus_tpu.aio.await_synced`): the
    timeout is purely a liveness bound — a loaded runner slows the
    wait, never breaks it."""
    from hocuspocus_tpu.aio import await_synced

    await await_synced(providers, timeout=timeout, what="wait_synced")


async def assert_on_update(observable, fn, event: str = "update", timeout: float = 30.0):
    """Event-driven eventual assertion: run `fn` now and again after every
    `event` emission on `observable` (e.g. a provider's Y.Doc), returning
    as soon as it stops raising AssertionError. Unlike interval polling,
    the deadline only bounds liveness — it can't race the event itself."""
    loop = asyncio.get_running_loop()
    wake = asyncio.Event()

    def handler(*args) -> None:
        loop.call_soon_threadsafe(wake.set)

    observable.on(event, handler)
    deadline = time.monotonic() + timeout
    try:
        while True:
            try:
                result = fn()
                if asyncio.iscoroutine(result):
                    result = await result
                return result
            except AssertionError:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise
                wake.clear()
                try:
                    await asyncio.wait_for(wake.wait(), timeout=remaining)
                except asyncio.TimeoutError:
                    pass  # final re-check, then raise from fn
    finally:
        observable.off(event, handler)


async def wait_for(predicate, timeout: float = 10.0, interval: float = 0.02) -> None:
    deadline = time.monotonic() + timeout
    while not predicate():
        if time.monotonic() > deadline:
            raise TimeoutError("condition not met in time")
        await asyncio.sleep(interval)


class EventCollector:
    """Collects event payloads and lets tests await their arrival."""

    def __init__(self) -> None:
        self.events: list = []
        self._event = asyncio.Event()

    def __call__(self, *args: Any) -> None:
        self.events.append(args)
        self._event.set()

    async def wait(self, count: int = 1, timeout: float = 10.0) -> list:
        deadline = time.monotonic() + timeout
        while len(self.events) < count:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(
                    f"expected {count} events, got {len(self.events)}"
                )
            self._event.clear()
            try:
                await asyncio.wait_for(self._event.wait(), min(remaining, 0.5))
            except asyncio.TimeoutError:
                continue
        return self.events


class HoldingFaults(FaultInjector):
    """Holds the log's lane thread inside a commit (at the first disk
    check of the batch) until `release` is set; later commits pass
    straight through."""

    def __init__(self) -> None:
        super().__init__()
        self.entered = threading.Event()
        self.release = threading.Event()

    def check_disk_full(self) -> None:
        self.entered.set()
        assert self.release.wait(timeout=10), "the test never released the held commit"
        super().check_disk_full()

    async def held(self) -> None:
        """Wait until a commit sits on the lane thread, then let the loop
        turn a few times: whatever is going to wait for it waits by now."""
        await wait_for(self.entered.is_set, interval=0.001)
        for _ in range(5):
            await asyncio.sleep(0)


class TurnCounter:
    """Counts the loop's turns: a `call_soon` handle that re-arms itself
    runs once in every `_run_once`."""

    def __init__(self) -> None:
        self.turn = 0
        self._loop = asyncio.get_running_loop()
        self._handle = self._loop.call_soon(self._tick)

    def _tick(self) -> None:
        self.turn += 1
        self._handle = self._loop.call_soon(self._tick)

    def stop(self) -> None:
        self._handle.cancel()
