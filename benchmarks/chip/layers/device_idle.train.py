"""Share of the traced window in which no operation ran on the device
(busy time is the union of the device's op intervals)."""


def read(run):
    return run.device_idle()
