"""Simulator events processed per aggregation
(``Simulator.events_processed``)."""

from fedbench import readers


def read(win):
    return readers.per_agg(win, float(win.events))
