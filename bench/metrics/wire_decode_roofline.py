"""Least time of the uplink decode stages' work (int8 blocks to float32,
top-k scatter to dense rows; ``fedbench.roofline.decode``), over the device
time of every operation inside the ``wire_decode_batch`` span."""

from fedbench import readers


def read(win):
    return readers.share_of_roofline(win, "wire_decode_batch",
                                     readers.decode_least_s(win))
