"""What the integrate and append programs have to move, whatever kernel
implements them, and the share of the chip's memory bandwidth that the
traced time of those programs amounts to.

A flush batch names B rows of the arena (its 'KxB' dispatch shape), B being
the bucket that the busy rows were rounded up to. The rows it really
touches are more than the next smaller bucket holds and at most B; the
program exports no count, so the least is taken: a share can read low by
the padding, never high. Each touched row is read once and written once, as
far as its document reaches (`doc_units`, not the row's capacity). A unit
holds the id's client (4 B) and clock (4 B), the rank (4 B), the origin's
rank (4 B) and the tombstone (1 B).
"""

from __future__ import annotations

UNIT_BYTES = 4 + 4 + 4 + 4 + 1


def width(shape: str) -> int:
    return int(shape.split("x")[1])


def rows_at_least(bucket: int, buckets: "list[int]") -> int:
    """The fewest busy rows that are rounded up to `bucket`, given every
    bucket the program has."""
    smaller = [b for b in buckets if b < bucket]
    return max(smaller) + 1 if smaller else 1


def batch_bytes(dispatches: "dict[str, int]", doc_units: int, buckets: "list[int]") -> int:
    """Bytes that `dispatches` ({'KxB': count}) have to move at the least."""
    rows = sum(count * rows_at_least(width(shape), buckets) for shape, count in dispatches.items())
    return 2 * rows * doc_units * UNIT_BYTES


def dispatches_of(before: dict, after: dict, site: str) -> "dict[str, int]":
    """{'KxB': dispatches between two readings of `serve.dispatch_counts`}."""
    return {
        shape: count - before.get((at, shape), 0)
        for (at, shape), count in after.items()
        if at == site and count > before.get((at, shape), 0)
    }


def buckets_of(counts: dict, site: str) -> "list[int]":
    """Every bucket width that `site` has dispatched, its warm-up included."""
    return sorted({width(shape) for (at, shape) in counts if at == site})


def share(run: dict, site: str, program: str) -> "float | None":
    """Percent of the peak memory bandwidth: the least time the chip could
    take for the traced batches of `site`, over the traced device time of
    the programs with `program` in their name. None where nothing ran."""
    trace = run.get("trace")
    if not trace:
        return None
    seconds = sum(s for name, s in trace["program_seconds"].items() if program in name)
    before, after = run["traced_dispatch"]
    moved = batch_bytes(dispatches_of(before, after, site), run["doc_units"], buckets_of(after, site))
    if not seconds or not moved:
        return None
    return 100.0 * moved / run["peaks"]["hbm_bytes_per_s"] / seconds
