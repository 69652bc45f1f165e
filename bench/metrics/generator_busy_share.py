"""CPU time of the busiest client process over the window, as a share of it: near 100 the generator, not the server, sets the pace."""

SOURCE = "host_clock"


def read(run):
    return 100.0 * max(run["clients_cpu_s"]) / run["seconds"] if run["clients_cpu_s"] else None
