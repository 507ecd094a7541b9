"""Host milliseconds per aggregation that no program span names: the
aggregations' wall time (``win.round_spans``) less the self time of every
span in their ``RoundResult.spans``.  What is left is the event calendar's
loop, the event handlers and the glue between the named layers."""

from fedbench import readers


def read(win):
    spans = [getattr(r, "spans", None) for r in win.rounds]
    if not spans or any(s is None for s in spans):
        return None
    wall = sum(t1 - t0 for t0, t1 in win.round_spans)
    named = sum(v[2] for s in spans for v in s.values())
    return readers.per_agg(win, (wall - named) / readers.MS)
