"""The YATA integrate programs' share of the memory roofline in the traced window."""

SOURCE = "device_trace"


def read(run):
    from roofline import share

    return share(run, "integrate_sparse", "integrate")
