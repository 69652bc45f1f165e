"""The serving process owns its cyclic collector.

CPython starts a full collection from allocation counters: every 70,000
or so surviving containers, once they are a quarter of the old heap, it
walks the whole tracked heap, wherever the server's loop happens to be. With a few thousand CPU documents loaded
that walk is 250-420 ms of a stopped loop, several times a minute, and on
the served path it frees nothing: updates, frames and futures are acyclic
and die by reference count. The steward replaces the counters' decision
with the server's own:

- what is long-lived is frozen (`gc.freeze()`): after a pass it sits in
  the permanent generation and is never walked again;
- no automatic full pass: the generation-2 threshold is out of reach, and
  the generation-0 threshold is raised so that young passes are few and
  walk only what was allocated since the last one;
- a chosen pass (collect what is not frozen, freeze the survivors) runs
  from the steward's own timer, so between two loop callbacks and never
  inside a tick, when the heap has grown by a share since the last pass
  or a burst of document loads has settled, never more often than every
  few seconds, and while the heap grows at all no more rarely than every
  ten: a cycle waits seconds for its collection, not for 50,000
  allocations. It walks only what was allocated since the last freeze;
- a frozen heap never frees a cycle, and what leaves the server is
  cyclic: an unloaded document's items link left and right, a closed
  connection holds its callbacks and they hold it. So departures are
  counted, and past a share of the population the next pass thaws
  everything first (`gc.unfreeze()`): a full walk, as dear as the ones
  CPython used to start, at a time the server picked, and counted.

The collector is process state, so this is for the process the server
owns: `cli.build_server` adds `HeapStewardExtension`; an embedded
`Server` is left alone unless the embedder adds it (docs/guides/
embedding.md). Installs are counted: servers sharing a process share the
steward, and the last one out restores the thresholds it found and thaws
the heap.
"""

from __future__ import annotations

import asyncio
import gc
import sys
import time
from functools import partial
from typing import Optional

from ..observability.metrics import Counter, Gauge
from ..observability.tracing import get_tracer
from .types import Extension, Payload

# the generation-2 threshold no count of passes reaches
NEVER = 2**31 - 1


class HeapSteward:
    """Process-global (``get_heap_steward()``): thresholds, the frozen
    heap and the chosen passes. Construct instances only for tests that
    never install them."""

    def __init__(self) -> None:
        # young passes: 700 (CPython's) makes ~700 of them in 20 s of
        # conflict traffic, 50,000 makes 2-4 of at most ~25 ms (PERF.md)
        self.gen0_threshold = 50_000
        self.min_interval_s = 5.0
        self.max_interval_s = 10.0
        self.growth_share = 0.2
        self.load_settle_s = 1.0
        # departures (documents unloaded, connections closed) since the
        # last thaw, as a share of what is loaded and connected
        self.churn_share = 0.25
        self.churn_floor = 16
        self.stats = {
            "heap_passes": 0,
            "heap_pass_ms_total": 0.0,
            "heap_unfreezes": 0,
            "heap_frozen_blocks": 0,
            "gc_auto_full_passes": 0,
        }
        self.last_pass: Optional[dict] = None
        self._installs = 0
        self._found_threshold: Optional[tuple] = None
        self._passing = False
        self._metrics = self._build_metrics()
        self._reset()

    def _reset(self) -> None:
        self._last_pass_at = 0.0
        self._blocks_at_pass = 0
        self._loads_pending = False
        self._last_load_at = 0.0
        self._population = 0
        self._departed = 0

    # -- install / restore ---------------------------------------------------

    @property
    def installed(self) -> bool:
        return self._installs > 0

    def install(self) -> None:
        self._installs += 1
        if self._installs > 1:
            return
        self._found_threshold = gc.get_threshold()
        gc.callbacks.append(self._on_gc)
        gc.set_threshold(self.gen0_threshold, self._found_threshold[1], NEVER)
        self._reset()
        self.run_pass("boot")

    def uninstall(self) -> None:
        if not self._installs:
            return
        self._installs -= 1
        if self._installs:
            return
        gc.callbacks.remove(self._on_gc)
        # whatever was frozen, by the steward or before it: freezing is
        # process state and cannot be thawed in part
        gc.unfreeze()
        gc.set_threshold(*self._found_threshold)
        self.stats["heap_frozen_blocks"] = 0

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "stop" and info["generation"] == 2 and not self._passing:
            self.stats["gc_auto_full_passes"] += 1

    # -- what the server tells it ----------------------------------------------

    def note_load(self) -> None:
        self._population += 1
        self._loads_pending = True
        self._last_load_at = time.monotonic()

    def note_connect(self) -> None:
        self._population += 1

    def note_departure(self) -> None:
        """A document was unloaded or a connection closed: frozen or not,
        it is cyclic garbage now."""
        self._population = max(self._population - 1, 0)
        self._departed += 1

    def thaw_due(self) -> bool:
        return self._departed >= max(self.churn_floor, self.churn_share * self._population)

    # -- passes ------------------------------------------------------------------

    def tick(self) -> None:
        """Run a pass if one is due. Called from a loop timer of every
        server that installed the steward, so from no span and no tick."""
        if not self._installs:
            return
        now = time.monotonic()
        if now - self._last_pass_at < self.min_interval_s:
            return
        if self._loads_pending and now - self._last_load_at >= self.load_settle_s:
            self.run_pass("loads")
        elif self.thaw_due():
            self.run_pass("churn")
        else:
            blocks = sys.getallocatedblocks()
            if blocks >= self._blocks_at_pass * (1 + self.growth_share):
                self.run_pass("growth")
            elif blocks > self._blocks_at_pass and now - self._last_pass_at >= self.max_interval_s:
                self.run_pass("timer")

    def run_pass(self, reason: str) -> None:
        """Collect everything that is not frozen and freeze the survivors;
        with enough departures since the last thaw, thaw first."""
        unfreeze = self.thaw_due()
        started = time.perf_counter()
        self._passing = True
        try:
            with get_tracer().span("heap.pass", reason=reason, unfreeze=unfreeze):
                if unfreeze:
                    gc.unfreeze()
                collected = gc.collect()
                gc.freeze()
        finally:
            self._passing = False
        ms = 1000 * (time.perf_counter() - started)
        blocks = sys.getallocatedblocks()
        self._last_pass_at = time.monotonic()
        self._blocks_at_pass = blocks
        self._loads_pending = False
        stats = self.stats
        stats["heap_passes"] += 1
        stats["heap_pass_ms_total"] += ms
        stats["heap_frozen_blocks"] = blocks
        if unfreeze:
            stats["heap_unfreezes"] += 1
            self._departed = 0
        self.last_pass = {
            "reason": reason,
            "unfreeze": unfreeze,
            "collected": collected,
            "blocks": blocks,
            "ms": round(ms, 3),
        }

    # -- exposition (adopted by the Metrics registry) -------------------------------

    def metrics(self) -> list:
        return self._metrics

    def _build_metrics(self) -> list:
        return [
            kind("hocuspocus_" + key, help, fn=partial(self.stats.__getitem__, key))
            for kind, key, help in (
                (Counter, "heap_passes", "Collector passes the heap steward chose (collect the unfrozen, freeze the survivors)"),
                (Counter, "heap_pass_ms_total", "Milliseconds the loop was stopped in the steward's passes"),
                (Counter, "heap_unfreezes", "Passes that thawed the frozen heap first (full walks, driven by unloads and disconnects)"),
                (Gauge, "heap_frozen_blocks", "Allocated blocks (sys.getallocatedblocks) at the last freeze"),
                (Counter, "gc_auto_full_passes", "Generation-2 passes the steward did not ask for while installed (should stay 0)"),
            )
        ]


_default = HeapSteward()


def get_heap_steward() -> HeapSteward:
    return _default


class HeapStewardExtension(Extension):
    """Installs the process's steward while this server listens, tells it
    of loads, unloads, connects and disconnects, and ticks it from this
    server's loop."""

    # first in every chain: another extension's failing on_destroy must
    # not leave the process's collector as the steward set it
    priority = 995
    tick_s = 0.5

    def __init__(self, steward: Optional[HeapSteward] = None) -> None:
        self.steward = steward or get_heap_steward()
        self._timer: Optional[asyncio.TimerHandle] = None

    async def on_listen(self, data: Payload) -> None:
        if self._timer is None:
            self.steward.install()
            self._arm()

    def _arm(self) -> None:
        self._timer = asyncio.get_running_loop().call_later(self.tick_s, self._tick)

    def _tick(self) -> None:
        self._arm()
        self.steward.tick()

    async def after_load_document(self, data: Payload) -> None:
        self.steward.note_load()

    async def connected(self, data: Payload) -> None:
        self.steward.note_connect()

    async def after_unload_document(self, data: Payload) -> None:
        self.steward.note_departure()

    async def on_disconnect(self, data: Payload) -> None:
        self.steward.note_departure()

    async def on_destroy(self, data: Payload) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
            self.steward.uninstall()
