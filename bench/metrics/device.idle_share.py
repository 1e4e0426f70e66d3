"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device's operation intervals) / window, in %
(layer: device)."""


def read(run):
    return None if run.summary is None else 100.0 * run.summary.idle_share
