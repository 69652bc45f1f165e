"""p95 of how late a 10 ms timer fired on the server's event loop."""

SOURCE = "host_clock"


def read(run):
    from stats import percentile

    lag = percentile(run["loop_lag_s"], 0.95)
    return None if lag is None else lag * 1000.0
