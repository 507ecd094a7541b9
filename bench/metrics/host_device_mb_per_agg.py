"""Megabytes per aggregation copied between host and device, both ways
(``device.h2d_bytes`` + ``device.d2h_bytes``): the training stack in and
out, and the wire and FedAvg kernels' inputs and results."""

from fedbench import readers

NAMES = ("device.h2d_bytes", "device.d2h_bytes")


def read(win):
    got = [c[n] for c in (getattr(r, "counters", {}) for r in win.rounds)
           for n in NAMES if n in c]
    return readers.per_agg(win, sum(got) / 1e6) if got else None
