"""Records per group commit of the write-ahead log over the window."""

SOURCE = "program_counter"


def read(run):
    delta = run["wal_delta"]
    return delta["appended_records"] / delta["commit_batches"] if delta.get("commit_batches") else None
