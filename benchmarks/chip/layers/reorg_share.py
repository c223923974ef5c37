"""Reorganizes per maintenance round in the window, from the view driver's
counters: rounds = kernel launches + reorganizes SKIING called without one."""


def read(run):
    kernel, overflows, reorgs = run.counter_delta
    rounds = kernel + (reorgs - overflows)
    return 100.0 * reorgs / rounds if rounds else None
