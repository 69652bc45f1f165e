"""The server loop's stages by their top-level spans, for the readers that
split the loop's time by stage (bench/metrics/loop_*_share.py).

On the loop thread no one of LOOP_STAGES opens inside another (a test holds
the program to that on a served session), so with the harness's own spans of
the selector they and the unnamed rest partition the traced window. Every
other span of the loop opens inside one of them: `connection.dispatch` and
`message.update_apply` inside `connection.receive`, `plane.post_flush` inside
`plane.flush_turn`, `transport.write_inline` inside whatever called `send`.
`spans.LOOP_TOP_LEVEL` is the older partition of `loop_unattributed_share`,
kept as it was.
"""

from __future__ import annotations

from spans import ASLEEP, seconds

LOOP_STAGES = (
    "connection.receive",  # the reader task's pieces of one received frame
    "transport.read",  # the socket's read-ready callback: recv, frame parse, hand-off
    "transport.write_queued",  # a writer task's write of one queued frame
    "plane.broadcast",
    "fanout.tick",
    "plane.flush_turn",  # the flush cycle's pieces on the loop, and the timer that starts it
    "wal.commit_done",  # the log's landing on the loop, less the gated deliveries
    "heap.pass",
    "cells.rebalance",
)
# The stages the program names since the loop was split by stage: a program
# without any of them (an older commit) has the old partition alone.
NEW_STAGES = ("connection.receive", "transport.read", "plane.flush_turn", "wal.commit_done")
# The loop in a selector call that returns at once (timeout 0), where the
# harness times those apart from its sleeps; 0 where it does not.
POLL = "bench.loop_poll"
# What an idle gap of the device is labelled by, as `tracereduce.reduce`
# takes it (`label_prefix`, which `str.startswith` reads as a tuple): the
# harness's own spans and the loop's stages, disjoint on the loop thread.
IDLE_LABELS = ("bench.",) + LOOP_STAGES


def unnamed_share(run: dict) -> "float | None":
    """The traced window less the loop asleep, less its zero-timeout polls,
    less every stage: asyncio's own machinery, the executor's and the lane
    thread's landings, and whatever else has no span. As computed, never
    clipped: a negative reading means stages that overlap or count twice.
    None on a program without the new stages."""
    if seconds(run, NEW_STAGES) is None:
        return None
    trace = run["trace"]
    window = trace["window_s"]
    have = trace["span_seconds"]
    named = sum(have.get(name, 0.0) for name in (ASLEEP, POLL) + LOOP_STAGES)
    return 100.0 * (window - named) / window
