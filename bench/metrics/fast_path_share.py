"""Share of the ops flushed in the window that took the run-append path."""

SOURCE = "program_counter"


def read(run):
    fast, slow = run["plane_delta"]["flush_fast_ops"], run["plane_delta"]["flush_slow_ops"]
    return 100.0 * fast / (fast + slow) if fast + slow else None
