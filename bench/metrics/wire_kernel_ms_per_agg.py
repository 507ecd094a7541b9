"""Host milliseconds per aggregation in the wire's device kernels
(``wire.kernel``: top-k gather and scatter, int8 quantize and dequantize),
from dispatch until the result is numpy."""

from fedbench import readers


def read(win):
    got = [s["wire.kernel"][1] for s in (getattr(r, "spans", {})
                                         for r in win.rounds)
           if "wire.kernel" in s]
    return readers.per_agg(win, sum(got) / readers.MS) if got else None
