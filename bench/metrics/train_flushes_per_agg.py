"""Batched-trainer flushes per aggregation (``BatchTrainer.batch_sizes``):
how finely the event schedule splits a round's training."""

from fedbench import readers


def read(win):
    if not win.flush_sizes:
        return None
    return readers.per_agg(win, float(len(win.flush_sizes)))
