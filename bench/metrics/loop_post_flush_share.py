"""Share of the traced window the server's loop spent on what a flush cycle runs back on it (`plane.post_flush`):
the serving refresh and the desync sweep."""

SOURCE = "program_span"


def read(run):
    from spans import share

    return share(run, ("plane.post_flush",))
