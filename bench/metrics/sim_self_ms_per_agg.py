"""Host milliseconds per aggregation of the round outside every layer span:
the event engine (simulator, transport, packetization) and the glue."""

from fedbench import readers
from fedbench import trace as tr

LAYERS = ("train", "wire_encode", "wire_decode", "wire_decode_batch",
          "aggregate", "fedavg")


def read(win):
    rounds = win.round_spans
    inner = [iv for n in LAYERS for iv in win.spans.get(n, [])]
    self_ns = tr.total(rounds) - tr.overlap(rounds, inner)
    return readers.per_agg(win, self_ns / readers.MS)
