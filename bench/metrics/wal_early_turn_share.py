"""Share of the write-ahead log's group commits over the window whose commit-done step found records buffered and handed
them to the lane thread before it released its own gate (`WalManager.stats` `commits_turned_early` over
`commit_batches`): how often the next batch's write runs beside the deliveries of the last. It reads high where updates
arrive during every commit (`conflict-midinsert`), lower where a commit often lands with nothing behind it (the open
loops); a program that keeps no such counter (it turns the lane after the deliveries) reads None. The same file reads
`wal_early_turn_share.open`, the entry of the open-loop cells, which report the tail and not the throughput."""

SOURCE = "program_counter"


def read(run):
    from spans import per

    share = per(run["wal_delta"], "commits_turned_early", "commit_batches")
    return None if share is None else 100.0 * share
