"""Device time per sweep of the sweep's weights stage (sample, encode,
Gram, weights; one launch per sweep point), in ms. The stage is found by
its XLA module name, which the program does not fix yet."""
from bench import trace

MODULE = r"^jit_f(\(|$)"


def read(ctx):
    sweeps = ctx["counters"].get("sweeps")
    ns = trace.module_ns(ctx["trace"], MODULE)
    return ns / sweeps / 1e6 if sweeps and ns > 0 else None
