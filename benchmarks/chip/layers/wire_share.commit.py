"""Share of a group commit's client-side time spent outside the server's
statement span: the wire, framing and the session threads. Over every
commit in the window: 1 - sum(server elapsed_us) / sum(client time)."""


def read(run):
    return run.wire_share(run.window_commits)
