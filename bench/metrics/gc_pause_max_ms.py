"""The longest stop of the server's loop by Python's garbage collector in the
window: 0 where the collector never ran in it, nothing where the run took no
readings of it."""

SOURCE = "host_clock"


def read(run):
    pauses = run.get("gc_pause_s")
    return None if pauses is None else max(pauses, default=0.0) * 1000.0
