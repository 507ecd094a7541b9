"""How unevenly the router loads the experts: E x ``moe.rows_max`` over
``moe.rows`` across the window (1 is even).  ``moe.rows_max`` sums, over
each silo, layer and local step, the token-expert rows of that silo's
busiest expert; ``moe.rows`` all of them.  Nothing where the program
counts no expert rows."""


def read(win):
    counters = [getattr(r, "counters", {}) for r in win.rounds]
    rows = sum(c.get("moe.rows", 0) for c in counters)
    if not rows:
        return None
    busiest = sum(c.get("moe.rows_max", 0) for c in counters)
    return int(win.config["num_experts"]) * busiest / rows
