"""Share of the rows the training step ran that were padding
(``train.pad_rows`` over ``train.rows`` + ``train.pad_rows``): the vmap
backend pads each flush to a power of two."""


def read(win):
    counters = [getattr(r, "counters", {}) for r in win.rounds]
    if not any("train.rows" in c for c in counters):
        return None
    rows = sum(c.get("train.rows", 0) for c in counters)
    pad = sum(c.get("train.pad_rows", 0) for c in counters)
    return 100.0 * pad / (rows + pad)
