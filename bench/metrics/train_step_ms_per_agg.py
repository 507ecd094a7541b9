"""Host milliseconds per aggregation in the jitted training step
(``train.step``), from the inputs' copy to the device until the trained
stack is numpy."""

from fedbench import readers


def read(win):
    got = [s["train.step"][1] for s in (getattr(r, "spans", {})
                                        for r in win.rounds)
           if "train.step" in s]
    return readers.per_agg(win, sum(got) / readers.MS) if got else None
