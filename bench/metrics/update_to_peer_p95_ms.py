"""The end-to-end tail read as a per-layer number, for a cell whose tail
the host's stalls make too unsteady to bound (PERF.md section 2): p95 over
all the window's updates of (the last intended peer applied it - it was
due), as `run.py` reads `update_to_peer_p95_ms`."""

SOURCE = "host_clock"


def read(run):
    from stats import percentile

    tail = percentile(run["latency_s"], 0.95)
    return None if tail is None else tail * 1000.0
