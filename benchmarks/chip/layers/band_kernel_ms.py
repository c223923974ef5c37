"""Mean device time of one band-kernel launch in the traced window: the
summed durations of its trace events over their count."""


def read(run):
    t = run.trace
    if not t or not t["kernel_launches"]:
        return None
    return 1e3 * t["kernel_s"] / t["kernel_launches"]
