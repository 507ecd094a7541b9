"""The ``lm_mla`` local training step's share of its roofline, read as
``lm_train_roofline`` reads the ``lm`` step's: the least time of the
window's training (``flops_per_update`` of ``bench/reference/lm_mla.py``
times the unpadded flush rows, at the bf16 peak; or the frozen base read
once per local step of every flush, at the HBM bandwidth; whichever is
larger), over the device time inside the probe's ``train`` span."""

from fedbench import spec


def read(win):
    return spec.reader("lm_train_roofline")(win)
