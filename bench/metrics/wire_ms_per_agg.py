"""Host milliseconds per aggregation in the wire layer: every encode, the
clients' downlink decodes and the server's batched uplink decode."""

from fedbench import readers


def read(win):
    return readers.per_agg(win, readers.span_ms(
        win, "wire_encode", "wire_decode", "wire_decode_batch"))
