"""DATA packets the packetizer built per aggregation (``packets.built``)."""

from fedbench import readers


def read(win):
    got = [c["packets.built"] for c in (getattr(r, "counters", {})
                                         for r in win.rounds)
           if "packets.built" in c]
    return readers.per_agg(win, float(sum(got))) if got else None
