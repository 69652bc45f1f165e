"""Share of the traced window an executor thread spent in the plane's flush (`merge_plane.flush`); it holds the
interpreter's lock against the loop for part of this."""

SOURCE = "program_span"


def read(run):
    from spans import share

    return share(run, ("merge_plane.flush",))
