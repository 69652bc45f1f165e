"""Mean duration of a group commit of the write-ahead log over the window, on its executor thread."""

SOURCE = "program_counter"


def read(run):
    from spans import per

    return per(run["wal_delta"], "commit_ms_total", "commit_batches")
