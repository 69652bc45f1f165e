"""Fan-out ticks delivered from inside a durability gate's resolution, per group commit of the write-ahead log over the
window: how many gated ticks a commit's completion released itself. A tick that found its gate already done is not
counted; a program that keeps no such counter (its gate is a future awaited by tasks) reads None."""

SOURCE = "program_counter"


def read(run):
    from spans import per

    return per(run["wal_delta"], "ticks_released", "commit_batches")
