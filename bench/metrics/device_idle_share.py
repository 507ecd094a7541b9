"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, averaged over the chips."""

from fedbench import trace as tr


def read(win):
    if win.trace is None or not win.trace.ops:
        return None
    return 100.0 * (1.0 - tr.busy_s(win.trace) / tr.window_s(win.trace))
