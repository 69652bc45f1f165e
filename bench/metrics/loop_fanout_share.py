"""Share of the traced window the server's loop spent in fan-out ticks (`fanout.tick`): coalesce, frame build,
the audience's socket writes."""

SOURCE = "program_span"


def read(run):
    from spans import share

    return share(run, ("fanout.tick",))
