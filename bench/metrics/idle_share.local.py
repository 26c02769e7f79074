"""The share of the traced window, in %, in which no operation ran on the
device (1 − busy ÷ window, busy the union of the operation intervals,
averaged over the chips)."""


def read(run):
    window = run.trace.window_s()
    if window <= 0:
        return None
    return 100 * (1 - run.trace.busy_s() / window)
