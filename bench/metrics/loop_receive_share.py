"""Share of the traced window the server's loop spent in the reader tasks' own turns, `connection.receive` (each
synchronous piece of `handle_message` for one received frame), less `connection.dispatch` and `message.update_apply`,
which open inside it and `loop_apply_share` reads: hook heads, sync and awareness decode, the replies and the ack it
builds and queues. None on a program without the span."""

SOURCE = "program_span"


def read(run):
    from spans import share

    return share(run, ("connection.receive",), ("connection.dispatch", "message.update_apply"))
