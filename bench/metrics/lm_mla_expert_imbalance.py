"""How unevenly the sigmoid router loads the routed experts:
``n_routed_experts`` x ``moe.rows_max`` over ``moe.rows`` across the window
(1 is even).  ``moe.rows_max`` sums, over each silo, MoE layer and local
step, the token-expert rows of that silo's busiest routed expert;
``moe.rows`` all of them (the shared experts take every token and count in
neither).  Nothing where the program counts no expert rows."""


def read(win):
    counters = [getattr(r, "counters", {}) for r in win.rounds]
    rows = sum(c.get("moe.rows", 0) for c in counters)
    if not rows:
        return None
    busiest = sum(c.get("moe.rows_max", 0) for c in counters)
    return int(win.config["n_routed_experts"]) * busiest / rows
