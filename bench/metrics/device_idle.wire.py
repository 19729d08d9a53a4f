"""Share of the traced window in which no operation ran on the chips
(averaged over the cell's chips): 1 - busy union / window, in %."""
from bench import trace


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - trace.busy_ns(tr) / trace.window_ns(tr))
