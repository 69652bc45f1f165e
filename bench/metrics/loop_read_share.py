"""Share of the traced window the server's loop spent in its connections' read-ready callbacks, `transport.read`: the
socket's `recv`, aiohttp's frame parse and the hand-off to the reader task's queue (top level on the loop). None on a
program without the span, and on a loop whose transports have no `_read_ready_cb` to wrap."""

SOURCE = "program_span"


def read(run):
    from spans import share

    return share(run, ("transport.read",))
