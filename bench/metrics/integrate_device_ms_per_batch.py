"""Device milliseconds one integrate batch takes: the traced device time of the programs with "integrate" in their
name over the integrate dispatches of the traced seconds, sparse and dense, whatever kernel ran them."""

SOURCE = "device_trace"


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    from roofline import dispatches_of

    seconds = sum(s for name, s in trace["program_seconds"].items() if "integrate" in name)
    batches = sum(
        sum(dispatches_of(*run["traced_dispatch"], site).values()) for site in ("integrate_sparse", "integrate_dense")
    )
    return 1000.0 * seconds / batches if seconds and batches else None
