"""Ops per device batch over the window: flushed ops over flush batches."""

SOURCE = "program_counter"


def read(run):
    delta = run["plane_delta"]
    ops = delta["flush_fast_ops"] + delta["flush_slow_ops"]
    batches = sum(delta["flush_batches_" + kind] for kind in ("fast", "sparse", "dense"))
    return ops / batches if batches else None
