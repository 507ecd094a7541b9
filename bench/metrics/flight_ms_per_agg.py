"""Host milliseconds per aggregation in the event engine's flights: burst
planning (``engine.burst``, ``Simulator.transmit_burst``) and bulk ingest
(``engine.flight_pass``, ``Simulator._flight_pass``), self time."""

from fedbench import readers

NAMES = ("engine.burst", "engine.flight_pass")


def read(win):
    got = [s[n][2] for s in (getattr(r, "spans", {}) for r in win.rounds)
           for n in NAMES if n in s]
    return readers.per_agg(win, sum(got) / readers.MS) if got else None
