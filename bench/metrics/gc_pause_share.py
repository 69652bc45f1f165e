"""Share of the window that Python's garbage collector held the server's loop:
0 where the collector never ran in it, nothing where the run took no readings
of it."""

SOURCE = "host_clock"


def read(run):
    pauses = run.get("gc_pause_s")
    return None if pauses is None else 100.0 * sum(pauses) / run["seconds"]
