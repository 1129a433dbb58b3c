"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window."""


def read(run):
    return 100.0 * run.summary.idle_share
