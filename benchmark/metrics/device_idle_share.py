"""Share of the traced window in which no op ran on the device (mean over
the chips), layer: device under the engines' host chunk loop."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
