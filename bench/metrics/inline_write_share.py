"""Share of the traced window's socket-write time that `send()` spent writing frames itself, in the caller's turn
(`transport.write_inline`), over the time of both ways a frame reaches its socket (with `transport.write_queued`: the
connection's writer task shipping its queue). **Seconds, not frames**: `lib/spans.py` gives a reader the seconds under a
span's name and no count of its events; both spans wrap the same synchronous write, so the two shares lie close. None
on a program without the spans (the parent commit), and without a trace.

**What it should read is the deliveries' share of a cell's frames, not 100**: the served host queues what a socket's own
reader replies (an update's ack), by design, and writes a tick's deliveries through. Two frames of three an update in
the typing cells (the ack; the writer's own copy and the peer's): 55.3 % in `typing-append`, 58.5 % in
`paper-cursor-edit`; one ack to ten deliveries in `conflict-midinsert`, where bursts queue some deliveries behind it:
75.4 % (chip runs of PR 35, one traced run each). A reading well under its cell's figure says deliveries are finding
their sockets busy or held back; 0 with frames written says write-through never engaged."""

SOURCE = "program_span"

INLINE, QUEUED = "transport.write_inline", "transport.write_queued"


def read(run):
    from spans import seconds

    both = seconds(run, (INLINE, QUEUED))
    if not both:
        return None
    return 100.0 * (seconds(run, (INLINE,)) or 0.0) / both
