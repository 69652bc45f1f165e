"""Share of the window in which the server's event loop ran callbacks instead of sleeping in its selector."""

SOURCE = "host_clock"


def read(run):
    return 100.0 * (1.0 - run["loop_asleep_s"] / run["seconds"]) if run["seconds"] else None
