"""The run-length integrate programs' share of the memory roofline in the traced window.

The byte count is this file's own (`lib/roofline.py` counts the unit arena): a batch of bucket width B stands for at
least `rows_at_least(B)` busy rows, and each is read once and written once as the kernels sweep it today, all R entries
of the row (`row_capacity`, from the booted planes), whatever the document occupies of them: an entry is five 4-byte
planes (the run's client, clock, length, rank and its origin's rank) and the tombstone flag, 21 B
(`tpu/kernels_rle.py` `RleState`). Over the traced device seconds of the programs with "integrate" in their name. A
kernel that sweeps less than the whole row (the occupied entries, say) moves fewer bytes than this counts and makes
the count stale: change it with the kernel. None on a unit arena, without a trace, and where nothing was integrated.
"""

SOURCE = "device_trace"
ENTRY_BYTES = 5 * 4 + 1
SITE = "integrate_sparse"


def read(run):
    trace = run.get("trace")
    if not trace or run.get("arena") != "rle":
        return None
    from roofline import buckets_of, dispatches_of, rows_at_least, width

    before, after = run["traced_dispatch"]
    buckets = buckets_of(after, SITE)
    rows = sum(count * rows_at_least(width(shape), buckets) for shape, count in dispatches_of(before, after, SITE).items())
    seconds = sum(s for name, s in trace["program_seconds"].items() if "integrate" in name)
    if not rows or not seconds:
        return None
    moved = 2 * rows * run["row_capacity"] * ENTRY_BYTES
    return 100.0 * moved / run["peaks"]["hbm_bytes_per_s"] / seconds
