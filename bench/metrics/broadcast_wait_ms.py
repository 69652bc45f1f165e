"""Mean time from when a broadcast pass was scheduled (the first capture since the last pass) to when it ran:
the coalescing window, the phase alignment and the loop's lag."""

SOURCE = "program_counter"


def read(run):
    from spans import per

    return per(run["plane_delta"], "broadcast_wait_ms_total", "broadcast_passes")
