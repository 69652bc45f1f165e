"""Share of the traced window the server's loop spent in the flush cycles' own turns, `plane.flush_turn` (the timer
that starts a cycle; its head: lane admission, governor, the executor submit; its tail once the executor returns),
less `plane.post_flush`, which opens inside the tail and `loop_post_flush_share` reads. With `--tpu-devices` these are
every cell's cycles on the one loop. None on a program without the span."""

SOURCE = "program_span"


def read(run):
    from spans import share

    return share(run, ("plane.flush_turn",), ("plane.post_flush",))
