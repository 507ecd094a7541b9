"""Host milliseconds per aggregation in local training: the batched
trainer's flushes, or each client's step on the ``python`` backend."""

from fedbench import readers


def read(win):
    if not win.spans.get("train"):
        return None
    return readers.per_agg(win, readers.span_ms(win, "train"))
