"""Updates due per second of the window, open loop only."""

SOURCE = "host_clock"


def read(run):
    return run["offered"] / run["seconds"] if run["open_loop"] and run["offered"] else None
