"""Share of the sparse integrate batches of the window that were 16 rows wide or more."""

SOURCE = "program_counter"


def read(run):
    from roofline import dispatches_of

    shapes = dispatches_of(run["dispatch"][0], run["dispatch"][1], "integrate_sparse")
    total = sum(shapes.values())
    wide = sum(count for shape, count in shapes.items() if int(shape.split("x")[1]) >= 16)  # 5 to 16 busy rows
    return 100.0 * wide / total if total else None
