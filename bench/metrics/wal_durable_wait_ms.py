"""Mean time from a commit batch's oldest pending append to its futures being resolved on the loop: the longest a
fan-out tick of that batch can have been gated."""

SOURCE = "program_counter"


def read(run):
    from spans import per

    return per(run["wal_delta"], "durable_wait_ms_total", "commit_batches")
