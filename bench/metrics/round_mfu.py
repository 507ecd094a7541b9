"""The whole round's share of the chip's bf16 peak: the model FLOPs of
every client update trained in the window (the unpadded flush sizes of the
batched trainer, times ``flops_per_update`` of the model's reference in
``bench/reference/<model>.py``), over the traced window.  It bounds what
any kernel's roofline can give back end to end."""

from fedbench import spec
from fedbench import trace as tr


def read(win):
    if win.trace is None or not win.peak or not win.flush_sizes:
        return None
    ref = spec.reference(win.config["model"])
    if not hasattr(ref, "flops_per_update"):
        raise AttributeError(
            f"bench/reference/{win.config['model']}.py has no "
            "flops_per_update(config); round_mfu cannot count its FLOPs")
    flops = ref.flops_per_update(win.config) * sum(win.flush_sizes)
    return 100.0 * flops / win.peak["bf16_flops_per_s"] / tr.window_s(
        win.trace)
