"""The run-append program's share of the memory roofline in the traced window."""

SOURCE = "device_trace"


def read(run):
    from roofline import share

    return share(run, "append_sparse", "append_run")
