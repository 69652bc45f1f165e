"""Share of the traced window the server's loop spent in the synchronous part of socket writes: `transport.write_inline`
(frames `send()` wrote itself; these open inside whatever called `send`, in the served path `fanout.tick`, and are part of
that stage's share too) plus `transport.write_queued` (the connections' writer tasks; top level, otherwise unattributed).
None on a program without the spans."""

SOURCE = "program_span"


def read(run):
    from spans import share

    return share(run, ("transport.write_inline", "transport.write_queued"))
