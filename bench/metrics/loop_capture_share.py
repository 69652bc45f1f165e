"""Share of the traced window the server's loop spent capturing updates for the plane (`plane.capture`,
with the lowering to ops, `plane.lower`, inside it)."""

SOURCE = "program_span"


def read(run):
    from spans import share

    return share(run, ("plane.capture",))
