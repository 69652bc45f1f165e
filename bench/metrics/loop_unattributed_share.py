"""Share of the traced window the server's loop was awake under no span of the program: the window less
`bench.loop_asleep` less every top-level span of the loop. Never clipped."""

SOURCE = "program_span"


def read(run):
    from spans import unattributed_share

    return unattributed_share(run)
