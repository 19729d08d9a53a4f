"""Device time per structure of the device Boruvka solve, in ms."""
from bench import trace

MODULE = r"^jit_boruvka_mst(\(|$)"


def read(ctx):
    n = ctx["counters"].get("structures")
    ns = trace.module_ns(ctx["trace"], MODULE)
    return ns / n / 1e6 if n and ns > 0 else None
