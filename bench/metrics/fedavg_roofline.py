"""Least time of the weighted mean of each (K, P) float32 stack the server
folded (``fedbench.roofline.fedavg`` on ``bench/peaks.json``), over the
device time of every operation inside the ``fedavg`` span.  It counts the
operation, not a kernel name: whatever implements the fold reads the same
work."""

from fedbench import readers


def read(win):
    return readers.share_of_roofline(win, "fedavg",
                                     readers.fedavg_least_s(win))
