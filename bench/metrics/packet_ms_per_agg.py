"""Host milliseconds per aggregation building packets (``packet.build``:
slicing, one Adler-32 and one ``Packet`` per packet) and reassembling
them (``packet.reassemble``: verify and join), self time."""

from fedbench import readers

NAMES = ("packet.build", "packet.reassemble")


def read(win):
    got = [s[n][2] for s in (getattr(r, "spans", {}) for r in win.rounds)
           for n in NAMES if n in s]
    return readers.per_agg(win, sum(got) / readers.MS) if got else None
