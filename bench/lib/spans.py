"""The program's own spans as shares of the traced window, for the
`program_span` readers of bench/metrics/.

In a traced run the program's `Tracer.span` sites annotate the profiler's
capture, so `trace["span_seconds"]` (lib/tracereduce.py) holds the seconds
under each by name. A span a reader sums wraps a synchronous section: the
spans of one thread never interleave, and a name's sum is that thread's time.
A parent's sum includes its children's (a child is a span opened inside it, on
the same thread), so a stage's own time is its sum less its children's.

A program without the span (a parent commit, a rehearsal on the CPU, whose
host plane stands in for the device) gives None, never 0.
"""

from __future__ import annotations

ASLEEP = "bench.loop_asleep"  # the harness's own span: the loop in its selector

# The loop thread's top-level spans: no one of them opens inside another, so
# with ASLEEP they and the unattributed rest partition the window.
LOOP_TOP_LEVEL = (
    "connection.dispatch",
    "message.update_apply",
    "plane.broadcast",
    "fanout.tick",
    "plane.post_flush",
)
# What opens inside message.update_apply: the document's observer runs the
# log's append and the plane's capture while the update is applied.
APPLY_CHILDREN = ("wal.append", "plane.capture")


def seconds(run: dict, spans, minus=()) -> "float | None":
    """Seconds of the traced window under `spans`, less those under `minus`
    (their children); None where the run has no trace or none of `spans`."""
    trace = run.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    have = trace["span_seconds"]
    if not any(name in have for name in spans):
        return None
    return sum(have.get(name, 0.0) for name in spans) - sum(have.get(name, 0.0) for name in minus)


def share(run: dict, spans, minus=()) -> "float | None":
    """`seconds` as a percentage of the traced window."""
    found = seconds(run, spans, minus)
    return None if found is None else 100.0 * found / run["trace"]["window_s"]


def unattributed_share(run: dict) -> "float | None":
    """The window less the loop asleep less every top-level span of the loop:
    transport, asyncio, hooks, the Logger, the collector. As computed, never
    clipped: a negative reading means spans that interleave or count twice."""
    spanned = seconds(run, LOOP_TOP_LEVEL)
    if spanned is None:
        return None
    window = run["trace"]["window_s"]
    asleep = run["trace"]["span_seconds"].get(ASLEEP, 0.0)
    return 100.0 * (window - asleep - spanned) / window


def per(delta: dict, total: str, count: str) -> "float | None":
    """A counter's total over its count, between a window's edges; None where
    the program does not keep it or counted nothing."""
    if total not in delta or not delta.get(count):
        return None
    return delta[total] / delta[count]
