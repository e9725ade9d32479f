"""Share of the traced window in which no operation ran on the device, in
percent.  Layer: the device.  1 - (union of the device's operation
intervals) / (the window the benchmark's host spans bound)."""


def read(run):
    if run.trace.busy_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
