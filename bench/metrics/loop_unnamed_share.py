"""Share of the traced window the server's loop was awake under no stage (`lib/loop_stages.py`): the window less
`bench.loop_asleep`, less the zero-timeout polls where the harness times them apart (`bench.loop_poll`), less every
top-level span of the loop's stages. Never clipped. None on a program without the stages named since the loop was
split by stage (`transport.read`, `connection.receive`, `plane.flush_turn`, `wal.commit_done`), so an older program
shows no fall that is only a change of yardstick."""

SOURCE = "program_span"


def read(run):
    from loop_stages import unnamed_share

    return unnamed_share(run)
