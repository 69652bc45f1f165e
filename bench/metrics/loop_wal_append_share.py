"""Share of the traced window the server's loop spent handing updates to the write-ahead log (`wal.append`)."""

SOURCE = "program_span"


def read(run):
    from spans import share

    return share(run, ("wal.append",))
