"""Share of its roofline that the packed sign Gram kernel reaches over
the cell's chips, in %: one (d, d) Gram of n samples per structure,
counted once, its least time on all the cell's chips over the kernel's
device time per chip. A Gram computed on every chip counts as waste."""
from bench import roofline_wire, trace

KERNELS = r"^(sign_corr_packed|sign_corr)(\.\d+)?$"


def read(ctx):
    c, cfg, peak = ctx["counters"], ctx["config"], ctx["peak"]
    ns = trace.op_ns(ctx["trace"], KERNELS)
    if not c.get("structures") or ns <= 0 or peak is None:
        return None
    t = roofline_wire.gram_least_seconds(cfg["n"], cfg["d"], cfg["method"],
                                         cfg["wire"], peak, ctx["chips"])
    return 100.0 * t * c["structures"] / (ns / 1e9)
