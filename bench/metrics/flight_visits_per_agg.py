"""Flights the event engine's flight pass visited per aggregation
(``engine.flight_visits``, ``Simulator._flight_pass``)."""

from fedbench import readers


def read(win):
    got = [c["engine.flight_visits"] for c in (getattr(r, "counters", {})
                                               for r in win.rounds)
           if "engine.flight_visits" in c]
    return readers.per_agg(win, float(sum(got))) if got else None
