"""Device time per structure of the collective operations (the payload
all-gather and whatever else the runtime gathers or reduces), averaged
over the cell's chips, in ms."""
from bench import trace


def read(ctx):
    n = ctx["counters"].get("structures")
    ns = trace.op_ns(ctx["trace"], trace.COLLECTIVE.pattern)
    return ns / n / 1e6 if n and ns > 0 else None
