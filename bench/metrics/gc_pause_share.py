"""Share of the window that Python's garbage collector held the server's loop."""

SOURCE = "host_clock"


def read(run):
    return 100.0 * sum(run["gc_pause_s"]) / run["seconds"] if run["gc_pause_s"] else None
