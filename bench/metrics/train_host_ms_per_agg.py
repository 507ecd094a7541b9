"""Host milliseconds per aggregation in the batched trainer's own work:
the self time of ``train.flush`` (``BatchTrainer.flush``), which stacks
the rows, pads them and unflattens the results, outside the jitted
step."""

from fedbench import readers


def read(win):
    got = [s["train.flush"][2] for s in (getattr(r, "spans", {})
                                         for r in win.rounds)
           if "train.flush" in s]
    return readers.per_agg(win, sum(got) / readers.MS) if got else None
