"""Helpers the per-layer readers in ``bench/metrics/`` share."""

from __future__ import annotations

from fedbench import roofline
from fedbench import trace as tr

MS = 1e6      # ns per ms


def span_ms(win, *names) -> float:
    """Host milliseconds covered by the named spans (their union)."""
    return tr.total([iv for n in names for iv in win.spans.get(n, [])]) / MS


def per_agg(win, value):
    return None if value is None or not win.n_aggs else value / win.n_aggs


def share_of_roofline(win, span: str, least_s: float):
    """Least time over the device time inside ``span``, in percent; nothing
    when there is no trace, no peak, no work or no device time."""
    if win.trace is None or not win.peak or least_s <= 0:
        return None
    device_s = tr.device_s_in(win.trace, span)
    return 100.0 * least_s / device_s if device_s > 0 else None


def decode_least_s(win) -> float:
    return sum(roofline.least_seconds(*roofline.decode(items, stages),
                                      win.peak)
               for items, stages in win.decode_work) if win.peak else 0.0


def fedavg_least_s(win) -> float:
    return sum(roofline.least_seconds(*roofline.fedavg(k, p), win.peak)
               for k, p in win.fedavg_work) if win.peak else 0.0
