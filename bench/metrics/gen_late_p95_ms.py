"""How late the generator sent: p95 of (sent - due) over the window's updates, open loop only."""

SOURCE = "host_clock"


def read(run):
    from stats import percentile

    late = percentile(run["late_s"], 0.95)
    return None if late is None else late * 1000.0
