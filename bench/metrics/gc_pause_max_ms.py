"""The longest stop of the server's loop by Python's garbage collector in the window."""

SOURCE = "host_clock"


def read(run):
    return max(run["gc_pause_s"]) * 1000.0 if run["gc_pause_s"] else None
