"""Device time per structure of the collective operations with no other
operation beside them on the chip (``trace.exposed_ns``), averaged over
the cell's chips, in ms."""
from bench import trace


def read(ctx):
    n = ctx["counters"].get("structures")
    tr = ctx["trace"]
    if not n or trace.op_ns(tr, trace.COLLECTIVE.pattern) <= 0:
        return None
    return trace.exposed_ns(tr) / n / 1e6
