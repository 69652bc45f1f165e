"""Rows that really carried ops over the bucket widths they were padded to, over the window's device batches."""

SOURCE = "program_counter"


def read(run):
    from spans import per

    fill = per(run["plane_delta"], "flush_busy_rows", "flush_bucket_rows")
    return None if fill is None else 100.0 * fill
