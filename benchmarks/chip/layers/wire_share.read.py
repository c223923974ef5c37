"""Share of a point read's client-side time spent outside the server's
statement span. Over every point SELECT in the window:
1 - sum(server elapsed_us) / sum(client time)."""


def read(run):
    return run.wire_share(run.window_reads)
