"""Share of the traced window the server's loop spent taking a message in and applying it to the CPU document:
`connection.dispatch` plus `message.update_apply` less the log's append and the plane's capture, which open inside it."""

SOURCE = "program_span"


def read(run):
    from spans import APPLY_CHILDREN, share

    return share(run, ("connection.dispatch", "message.update_apply"), APPLY_CHILDREN)
