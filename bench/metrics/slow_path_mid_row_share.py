"""Share of the ops flushed in the window that took the full-row integrate because they were not at their row's tail
(`slow_ops_mid_row` of the planes' counters): an insert whose origins lie inside the text. A program that does not
say why an op was slow (the parent commit) reads None."""

SOURCE = "program_counter"


def read(run):
    delta = run["plane_delta"]
    flushed = delta.get("flush_fast_ops", 0) + delta.get("flush_slow_ops", 0)
    if "slow_ops_mid_row" not in delta or not flushed:
        return None
    return 100.0 * delta["slow_ops_mid_row"] / flushed
