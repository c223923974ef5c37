"""Mean time of the WAL commit span (`span.wal.commit.seconds`: the group's
SGD on the host, the waters, the kernel launch or reorganize, and the
overflow sync), from its window deltas of sum and count."""


def read(run):
    count, total = run.wal_delta
    return 1e3 * total / count if count else None
