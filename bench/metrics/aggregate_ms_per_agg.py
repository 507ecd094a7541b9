"""Host milliseconds per aggregation in the server's fold
(``ServerCore.apply_aggregation``), without the uplink decode it runs
inside (``wire_decode_batch``)."""

from fedbench import readers
from fedbench import trace as tr


def read(win):
    agg = win.spans.get("aggregate", [])
    inner = win.spans.get("wire_decode_batch", [])
    return readers.per_agg(win, (tr.total(agg) - tr.overlap(agg, inner))
                           / readers.MS)
