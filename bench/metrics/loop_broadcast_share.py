"""Share of the traced window the server's loop spent in broadcast passes (`plane.broadcast`)."""

SOURCE = "program_span"


def read(run):
    from spans import share

    return share(run, ("plane.broadcast",))
