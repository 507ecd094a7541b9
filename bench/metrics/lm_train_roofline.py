"""The local training step's share of its roofline: the least time of the
window's training (``flops_per_update`` of the model's reference times the
unpadded flush rows, at the bf16 peak; or the frozen base read once per
local step of every flush, at the HBM bandwidth; whichever is larger), over
the device time inside the probe's ``train`` span.  Nothing where the
model's reference counts no frozen base (``base_bytes``)."""

from fedbench import readers, roofline, spec


def read(win):
    if win.trace is None or not win.peak or not win.flush_sizes:
        return None
    ref = spec.reference(win.config["model"])
    if not hasattr(ref, "base_bytes"):
        return None
    flops = ref.flops_per_update(win.config) * sum(win.flush_sizes)
    nbytes = (ref.base_bytes(win.config) * len(win.flush_sizes)
              * int(win.config["model_args"]["local_steps"]))
    return readers.share_of_roofline(
        win, "train", roofline.least_seconds(flops, nbytes, win.peak))
